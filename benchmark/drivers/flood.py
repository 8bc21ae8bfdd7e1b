"""A flood of independent tasks through the live in-process cluster of
``drivers/live.py``, with the whole-graph planner bypassed.

Each closed-loop client submits one ``Client.map(graphs.node, salts)``
a round (``map_tasks`` distinct salts from ``graphs.rng_for(seed,
client, round)``), gathers every result, copies the last ``replicate``
results to ``replicate_to`` workers (which feeds ``ReduceReplicas``) and
releases the round before.  The program runs at the configuration's
settings: a map below ``scheduler.jax.min-batch`` (512) is declined by
the planner after its batch filter.  Set-up, the window and its
snapshots are ``live.py``'s; the measurements are the same keys.  After
the window every result is compared with ``[graphs.node(s) for s in
salts]``, and the window must hold no plan and a run of every other
device path (the device steal cycle and the device ``ReduceReplicas``
round).
"""

from __future__ import annotations

import asyncio
import contextlib
import logging
import time

from benchmark import graphs
from benchmark.drivers.live import (
    _ErrorRecords,
    _Loop,
    _window,
    class_attrs,
    device_path_counters,
)

#: the function every task runs (the controls swap it)
TASK = graphs.node


class _MapLoop(_Loop):
    """The closed loop of one client: one ``Client.map`` a round."""

    def __init__(self, client, k: int, seed: int, traffic):
        super().__init__(client, k, None, traffic)
        self.seed, self.n = seed, traffic["map_tasks"]

    async def one(self, timeout: float) -> None:
        """Map, gather, replicate."""
        salts = graphs.rng_for(self.seed, self.k, self.round).choice(
            graphs.MOD, self.n, replace=False).tolist()
        # Client.map opens no phase of its own; compute_graph's is this
        with self.c.wall.phase("client.submit"):
            flat = self.c.map(TASK, salts, key=f"c{self.k}r{self.round}")
        self.round += 1
        self.inflight = flat
        got = await asyncio.wait_for(self.c.gather(flat), timeout)
        self.gathered.append(time.perf_counter())
        self.inflight = []
        self.results.append((salts, got))
        await self.c.replicate(flat[-self.replicate:], n=self.replicate_to)
        for f in self.held:
            f.release()
        self.held = flat


async def _run(config, traffic, seed, seconds, trace, devices, t_process, m):
    from distributed_tpu.client.client import Client
    from distributed_tpu.deploy.local import LocalCluster

    timeout = traffic["graph_timeout_s"]
    async with LocalCluster(n_workers=config["workers"],
                            threads_per_worker=config["threads"]) as cluster:
        async with contextlib.AsyncExitStack() as stack:
            clients = [await stack.enter_async_context(
                Client(cluster.scheduler_address))
                for _ in range(traffic["clients"])]
            state = cluster.scheduler.state
            placement = state.placement
            if placement is None:
                raise RuntimeError("the scheduler built no JaxPlacement")
            loops = [_MapLoop(c, k, seed, traffic)
                     for k, c in enumerate(clients)]
            with device_path_counters(state) as counts:
                await asyncio.gather(*(lp.one(timeout) for lp in loops))
                deadline = time.monotonic() + timeout
                while not (placement.precompiled is not None
                           and placement.precompiled.done()):
                    if time.monotonic() > deadline:
                        raise TimeoutError("the planner's precompile job")
                    await asyncio.sleep(0.05)
                m["setup_s"] = time.perf_counter() - t_process
                out = await _window(loops, state, placement, counts,
                                    seconds, trace, traffic, m,
                                    traffic["map_tasks"], timeout)
    m["trace_devices"] = [d.id for d in devices]
    return loops, out


def run(config, traffic, seed, seconds, trace, devices, t_process, m):
    from distributed_tpu import config as dconfig

    handler = _ErrorRecords()
    root = logging.getLogger("distributed_tpu")
    root.addHandler(handler)
    try:
        with dconfig.set(config.get("program_config", {})), \
                class_attrs(config.get("class_attrs", {})):
            loops, out = asyncio.run(_run(config, traffic, seed, seconds,
                                          trace, devices, t_process, m))
    finally:
        root.removeHandler(handler)
    if trace:
        m["trace"] = m.pop("tracer").reduce(m["trace_devices"])
    else:
        m.pop("tracer")

    # the plain reference, after the window
    wrong = failed = 0
    for lp in loops:
        for salts, got in lp.results:
            want = [graphs.node(s) for s in salts]
            bad = sum(a != b for a, b in zip(got, want))
            bad += abs(len(got) - len(want))
            wrong += bad
            failed += bool(bad)
    paths = out["device_paths"]
    missing = sum(1 for p, v in paths.items()
                  if p != "planner_plans" and v < 1)
    return {
        "ok": out["graphs"] > 0,
        "attempted": sum(len(lp.results) for lp in loops),
        "failed": failed,
        "checks": [
            ("wrong_results", wrong, 0),
            ("planner_plans", paths["planner_plans"], 0),
            ("device_paths_missing", missing, 0),
            ("error_records", len(handler.records), 0),
        ],
        "notes": {**paths, "error_records": handler.records[:3]},
    }

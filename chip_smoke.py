"""Smoke run of the scheduler co-processor on one attached TPU chip.

``python chip_smoke.py`` drives the main path once through the entry
points users call, at the sizes they run, and fails (non-zero exit, no
result line) if any phase fails:

1. identity: the default JAX device is a TPU and the native library
   loads;
2. whole-graph placement at the north-star size: ``bench.build_graph``
   (1M tasks, 0-2 dependencies each) onto 512 workers x 2 threads
   through ``ops.leveled.place_graph_streamed`` — the call the scheduler
   makes — validated, and compared with the same call on the host CPU
   backend in this process;
3. a live 64-worker ``LocalCluster`` + ``Client``: a seeded 50,000-task
   numeric DAG whose results must equal a plain-Python evaluation, with
   the device planner, the mirror, a device steal cycle and the device
   ``ReduceReplicas`` path all required to have run on the chip, no
   ERROR record from a ``distributed_tpu`` logger, and no backend
   compile on a second submission of the same graph.

``python chip_smoke.py --chips 4`` runs only the four-chip path: the
10M-task / 4096-worker graph through the sharded engine over
``make_engine_mesh(4)`` against the single-device engine on chip 0, and
the ICI all-to-all shuffle against numpy.

Every line before the last is a ``#`` comment (phase times, compile
seconds, counters, agreement).  The last line is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Each phase function takes its sizes as arguments, so the tests run the
same code on the CPU at tiny sizes; only :func:`main` insists on a TPU.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import logging
import sys
import time

import numpy as np

#: cost-model bandwidth of every engine call here (bench.py's)
BANDWIDTH = 100e6

def say(msg: str) -> None:
    print(f"# {msg}", flush=True)


class CompileCounter(logging.Handler):
    """Counts XLA backend compiles (and their seconds) while active.
    JAX's ``jax_log_compiles`` records are kept off the console and
    only quoted when a compile is a failure."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.n = 0
        self.seconds = 0.0
        self.log: list[str] = []

    def _listen(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration

    def emit(self, record: logging.LogRecord) -> None:
        self.log.append(record.getMessage()[:300])

    def __enter__(self) -> "CompileCounter":
        import jax
        import jax.monitoring

        self._log_compiles = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        log = logging.getLogger("jax")
        self._saved = (log.handlers[:], log.propagate, log.level)
        log.handlers = [self]
        log.propagate = False
        log.setLevel(logging.DEBUG)
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._listen)
        log = logging.getLogger("jax")
        log.handlers, log.propagate, level = self._saved
        log.setLevel(level)
        jax.config.update("jax_log_compiles", self._log_compiles)


# --------------------------------------------------------------- phase 1


def phase_identity() -> dict:
    """The default device as JAX reports it; fails unless the native
    library (graph pack, transition engine) loads."""
    import jax

    from distributed_tpu import native

    devices = jax.devices()
    if native.load() is None:
        raise RuntimeError("distributed_tpu.native.load() returned None")
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


# --------------------------------------------------------------- phase 2


def parity(ref, res, n_workers: int) -> dict:
    """Two placements of one graph must be equal element by element;
    the load imbalance of each is reported beside the agreement."""
    agreement = float((ref.assignment == res.assignment).mean())

    def imbalance(assignment) -> float:
        counts = np.bincount(assignment, minlength=n_workers)
        return float(counts.max() / max(counts.mean(), 1))

    out = {
        "agreement": agreement,
        "imbalance_ref": imbalance(ref.assignment),
        "imbalance": imbalance(res.assignment),
    }
    if agreement < 1.0:
        first = int(np.flatnonzero(ref.assignment != res.assignment)[0])
        out["first_diff_level"] = int(ref.level[first])
        raise AssertionError(f"placements differ: {out}")
    return out


def _fleet(n_workers: int, threads: int):
    return (
        np.full(n_workers, threads, np.int32),
        np.zeros(n_workers, np.float32),
        np.ones(n_workers, bool),
    )


def phase_whole_graph(graph, n_workers: int, threads: int = 2,
                      min_stream: int = 262144) -> dict:
    """``graph`` = ``(durations, out_bytes, src, dst)``.  One warm-up
    call, one timed call ending in the host read of the assignment,
    ``validate_leveled``, then the same call on the host CPU backend.
    ``min_stream`` is the driver's own threshold (its default), lowered
    only to stream a tiny graph."""
    import jax

    from distributed_tpu.ops.leveled import (
        place_graph_streamed,
        validate_leveled,
    )

    durations, out_bytes, src, dst = graph
    nthreads, occ0, running = _fleet(n_workers, threads)
    args = (durations, out_bytes, src, dst, nthreads, occ0, running)
    kw = {"bandwidth": BANDWIDTH, "min_stream": min_stream}
    with CompileCounter() as warm:
        t0 = time.perf_counter()
        place_graph_streamed(*args, **kw)
        warm_s = time.perf_counter() - t0
    tm: dict = {}
    with CompileCounter() as timed:
        t0 = time.perf_counter()
        packed, res = place_graph_streamed(*args, timings=tm, **kw)
        assignment = np.asarray(res.assignment)
        wall_s = time.perf_counter() - t0
    if tm.get("fallback"):
        raise RuntimeError("streamed driver fell back to pack-then-place")
    validate_leveled(packed, res, src, dst, running)
    with jax.default_device(jax.devices("cpu")[0]):
        _, ref = place_graph_streamed(*args, **kw)
    # the chip must place exactly as the host CPU does (measured equal
    # on a v5e, PR 21): f16->f32 converts, 1/threads, the scatter-add
    # load sums and the stable argsort agree bit for bit there
    same = parity(ref, res, n_workers)
    out = {
        "tasks": len(durations),
        "workers": n_workers,
        "pack_ms": tm["topo_s"] * 1e3,
        "device_ms": (wall_s - tm["topo_s"]) * 1e3,
        "wall_ms": wall_s * 1e3,
        "waves": res.n_waves,
        "fmt": tm["fmt"],
        "warmup_s": warm_s,
        "compiles_warmup": warm.n,
        "compile_s_warmup": warm.seconds,
        "compiles_timed": timed.n,
        "assigned": int((assignment >= 0).sum()),
        **same,
    }
    if timed.n:
        raise AssertionError(f"the timed call compiled: {out}")
    return out


# --------------------------------------------------------------- phase 3

_MOD = 1_000_003

def _node(salt, *deps):
    return (salt * 31 + sum(deps)) % _MOD


def random_dag(n_tasks: int, seed: int):
    """0-2 dependencies per task on uniformly random earlier tasks
    (bench.build_graph's shape) and an integer salt per task."""
    rng = np.random.default_rng(seed)
    n_deps = rng.integers(0, 3, n_tasks)
    n_deps[0] = 0
    dst = np.repeat(np.arange(n_tasks), n_deps)
    src = (rng.random(len(dst)) * np.maximum(dst, 1)).astype(np.int64)
    salt = rng.integers(0, _MOD, n_tasks)
    deps: list[list[int]] = [[] for _ in range(n_tasks)]
    for s, d in zip(src.tolist(), dst.tolist()):
        deps[d].append(s)
    return salt.tolist(), deps


def evaluate_dag(salt: list[int], deps: list[list[int]]) -> list[int]:
    """The plain-Python reference: every task in index order."""
    vals: list[int] = []
    for i, ds in enumerate(deps):
        vals.append(_node(salt[i], *(vals[j] for j in ds)))
    return vals


def _graph(prefix: str, salt, deps):
    from distributed_tpu.graph.spec import Graph, TaskRef, TaskSpec

    g = Graph()
    for i, ds in enumerate(deps):
        g.tasks[f"{prefix}-{i}"] = TaskSpec(
            _node, (salt[i], *(TaskRef(f"{prefix}-{j}") for j in ds))
        )
    return g


class _ErrorRecords(logging.Handler):
    def __init__(self):
        super().__init__(logging.ERROR)
        self.records: list[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        self.records.append(f"{record.name}: {record.getMessage()}")


@contextlib.contextmanager
def _device_path_counters(state):
    """Count, for the phase, the scheduler's device steal cycles (the
    flight-recorder ``steal-cycle`` stamps with ``dest="device"``) and
    the device kernel calls of stealing and ``ReduceReplicas``."""
    from distributed_tpu.ops import amm as ops_amm
    from distributed_tpu.ops import stealing as ops_stealing

    counts = {"steal_cycles_device": 0, "steal_cycles_host": 0,
              "steal_plans": 0, "amm_drop_plans": 0}
    emit = state.trace.emit
    plan_steals = ops_stealing.plan_steals
    plan_drops = ops_amm.plan_drops

    def counting_emit(cat, name, stim, key="", n=0, dest=""):
        if cat == "kernel" and name == "steal-cycle":
            counts[f"steal_cycles_{dest}"] += 1
        emit(cat, name, stim, key, n, dest)

    def counting_steals(*a, **kw):
        counts["steal_plans"] += 1
        return plan_steals(*a, **kw)

    def counting_drops(*a, **kw):
        counts["amm_drop_plans"] += 1
        return plan_drops(*a, **kw)

    state.trace.emit = counting_emit
    ops_stealing.plan_steals = counting_steals
    ops_amm.plan_drops = counting_drops
    try:
        yield counts
    finally:
        del state.trace.emit
        ops_stealing.plan_steals = plan_steals
        ops_amm.plan_drops = plan_drops


async def _wait_for(cond, timeout: float, what: str) -> None:
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > deadline:
            raise TimeoutError(f"timed out after {timeout}s waiting for {what}")
        await asyncio.sleep(0.05)


async def _live_cluster(n_workers, threads, n_tasks, n_replicate, seed,
                        timeout) -> dict:
    import jax

    from distributed_tpu.client.client import Client
    from distributed_tpu.deploy.local import LocalCluster

    t0 = time.perf_counter()
    salt, deps = random_dag(n_tasks, seed)
    want = evaluate_dag(salt, deps)
    out: dict = {"tasks": n_tasks, "workers": n_workers,
                 "reference_s": time.perf_counter() - t0}
    async with LocalCluster(n_workers=n_workers,
                            threads_per_worker=threads) as cluster:
        async with Client(cluster.scheduler_address) as c:
            state = cluster.scheduler.state
            placement = state.placement
            if placement is None:
                raise RuntimeError("the scheduler built no JaxPlacement")
            with _device_path_counters(state) as counts:
                async def run(prefix: str):
                    g = _graph(prefix, salt, deps)
                    keys = list(g.tasks)
                    t = time.perf_counter()
                    futs = c.compute_graph(g, keys)
                    got = await asyncio.wait_for(
                        c.gather([futs[k] for k in keys]), timeout
                    )
                    wall = time.perf_counter() - t
                    if got != want:
                        bad = sum(a != b for a, b in zip(got, want))
                        raise AssertionError(
                            f"{prefix}: {bad} of {n_tasks} results differ "
                            f"from the plain-Python evaluation"
                        )
                    return futs, keys, wall

                with CompileCounter() as first:
                    futs_a, keys_a, out["first_wall_s"] = await run("a")
                    # ReduceReplicas candidates: results held by this
                    # client, copied to 3 workers each
                    await c.replicate(
                        [futs_a[k] for k in keys_a[-n_replicate:]], n=3
                    )
                    await _wait_for(
                        lambda: counts["amm_drop_plans"] >= 1, 30.0,
                        "a device ReduceReplicas round",
                    )
                    await _wait_for(
                        lambda: placement.plans_inflight == 0
                        and not cluster.scheduler.extensions[
                            "stealing"
                        ]._device_plan_inflight,
                        timeout, "in-flight device plans to land",
                    )
                    # the planner's ahead-of-time compiles for the
                    # fleet's capacity (every row bucket of the steal,
                    # AMM and mirror-sync programs)
                    await _wait_for(
                        lambda: placement.precompiled is not None
                        and placement.precompiled.done(),
                        timeout, "the planner's precompile job",
                    )
                    out["precompiled"] = placement.precompiled.result()
                out["first_compiles"] = first.n
                out["first_compile_s"] = first.seconds
                with CompileCounter() as second:
                    _, _, out["second_wall_s"] = await run("b")
                out["second_compiles"] = second.n
                second_log = second.log
            out.update(counts)
            out["plans_computed"] = placement.plans_computed
            out["plan_hits"] = placement.plan_hits
            out["planner_enabled"] = placement.enabled
            dev = getattr(state.mirror, "_dev", {}) if state.mirror else {}
            out["mirror_platforms"] = sorted({
                d.platform for a in dev.values() for d in a.devices()
            })
    want_platform = jax.devices()[0].platform
    problems = []
    if out["plans_computed"] < 1 or not out["planner_enabled"]:
        problems.append("the device planner did not plan or was disabled")
    if out["mirror_platforms"] != [want_platform]:
        problems.append(f"mirror arrays not on {want_platform}")
    if out["steal_cycles_device"] < 1:
        problems.append("no device steal cycle")
    if out["amm_drop_plans"] < 1:
        problems.append("no device ReduceReplicas round")
    if out["second_compiles"]:
        problems.append(f"the second submission compiled: {second_log}")
    if problems:
        raise AssertionError(f"{'; '.join(problems)}: {out}")
    return out


def phase_live_cluster(n_workers: int = 64, threads: int = 2,
                       n_tasks: int = 50_000, n_replicate: int = 128,
                       seed: int = 0, timeout: float = 300.0) -> dict:
    """A live cluster through Client -> Scheduler -> Worker; raises on
    a wrong result, a device path that did not run, a compile on the
    second submission, or any ERROR record from ``distributed_tpu``."""
    handler = _ErrorRecords()
    root = logging.getLogger("distributed_tpu")
    root.addHandler(handler)
    try:
        out = asyncio.run(_live_cluster(
            n_workers, threads, n_tasks, n_replicate, seed, timeout
        ))
    finally:
        root.removeHandler(handler)
    if handler.records:
        raise AssertionError(f"ERROR records: {handler.records[:5]}")
    return out


# --------------------------------------------------------------- phase 4


def _mix32_numpy(x: np.ndarray) -> np.ndarray:
    """murmur3's 32-bit finalizer in numpy (the routing reference)."""
    z = x.astype(np.uint32)
    z ^= z >> np.uint32(16)
    z *= np.uint32(0x85EBCA6B)
    z ^= z >> np.uint32(13)
    z *= np.uint32(0xC2B2AE35)
    z ^= z >> np.uint32(16)
    return z


def _spans_devices(arr, n_devices: int) -> bool:
    return len({s.device for s in arr.addressable_shards}) == n_devices


def phase_four_chips(graph, n_workers: int, n_devices: int = 4,
                     shuffle_rows: int = 1 << 22, threads: int = 2,
                     min_stream: int = 262144) -> dict:
    """The sharded engine over ``make_engine_mesh(n_devices)`` against
    the single-device engine on device 0, then the ICI all-to-all
    shuffle against numpy.  ``min_stream`` as in
    :func:`phase_whole_graph`."""
    import jax

    from distributed_tpu.ops.ici import (
        compact_shuffle_output,
        make_mesh_1d,
        shuffle_on_mesh,
    )
    from distributed_tpu.ops.leveled import (
        place_graph_streamed,
        validate_leveled,
    )
    from distributed_tpu.ops.partition import make_engine_mesh
    from distributed_tpu.scheduler.state import SchedulerState

    if len(jax.devices()) < n_devices:
        raise RuntimeError(f"need {n_devices} devices, have {jax.devices()}")
    durations, out_bytes, src, dst = graph
    mesh = make_engine_mesh(n_devices)
    if len(set(mesh.devices.flat)) != n_devices:
        raise AssertionError(f"mesh repeats devices: {mesh.devices}")
    # the scheduler's mesh plan path: fleet rows from the mirror's
    # workers-axis device shards
    state = SchedulerState()
    for i in range(n_workers):
        state.add_worker_state(f"tcp://smoke:{i}", nthreads=threads,
                               memory_limit=2**30, name=f"w{i}")
    fv = state.mirror.fleet_view()
    args = (durations, out_bytes, src, dst, fv.nthreads.copy(),
            fv.occupancy.copy(), fv.running.copy())
    kw = {"bandwidth": BANDWIDTH, "min_stream": min_stream}
    out: dict = {"tasks": len(durations), "workers": n_workers,
                 "mesh": "x".join(str(v) for v in mesh.shape.values())}

    with jax.default_device(jax.devices()[0]):
        place_graph_streamed(*args, **kw)
        t0 = time.perf_counter()
        _, single = place_graph_streamed(*args, **kw)
        out["single_wall_s"] = time.perf_counter() - t0

    with CompileCounter() as warm:
        fleet_dev = state.mirror.sharded_device_view(mesh)
        place_graph_streamed(*args, mesh=mesh, fleet_dev=fleet_dev, **kw)
    out["sharded_compile_s"] = warm.seconds
    stats: dict = {}
    t0 = time.perf_counter()
    packed, sharded = place_graph_streamed(
        *args, mesh=mesh, fleet_dev=state.mirror.sharded_device_view(mesh),
        stats=stats, **kw,
    )
    out["sharded_wall_s"] = time.perf_counter() - t0
    validate_leveled(packed, sharded, src, dst, args[-1])
    if not all(_spans_devices(a, n_devices) for a in fleet_dev.values()):
        raise AssertionError("mirror fleet shards do not span the mesh")
    out["shards"] = stats["shards"]
    out.update(parity(single, sharded, n_workers))

    shuffle_mesh = make_mesh_1d(n_devices)
    rng = np.random.default_rng(1)
    keys = rng.integers(0, 1 << 30, shuffle_rows).astype(np.int32)
    vals = rng.random((shuffle_rows, 2)).astype(np.float32)
    t0 = time.perf_counter()
    ko, vo, counts, _ = shuffle_on_mesh(shuffle_mesh, keys, vals)
    if not _spans_devices(ko, n_devices):
        raise AssertionError("shuffle output does not span the mesh")
    parts = compact_shuffle_output(ko, vo, counts, n_devices)
    out["shuffle_wall_s"] = time.perf_counter() - t0
    dest = (_mix32_numpy(keys) % np.uint32(n_devices)).astype(np.int64)
    for d, (k, v) in enumerate(parts):
        want_k, want_v = keys[dest == d], vals[dest == d]
        o_got = np.lexsort((v[:, 1], v[:, 0], k))
        o_want = np.lexsort((want_v[:, 1], want_v[:, 0], want_k))
        if not (np.array_equal(k[o_got], want_k[o_want])
                and np.array_equal(v[o_got], want_v[o_want])):
            raise AssertionError(f"shuffle partition {d} != numpy")
    out["shuffle_rows"] = shuffle_rows
    return out


# ------------------------------------------------------------------ main


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    opts = parser.parse_args(argv)

    ident = phase_identity()
    say(f"devices: {ident}")
    if ident["platform"] != "tpu":
        raise SystemExit(f"no TPU: the default device is {ident['platform']}")

    from distributed_tpu.ops.compile_cache import enable_compile_cache

    say(f"compile cache: {enable_compile_cache()}")
    import bench

    t0 = time.perf_counter()
    if opts.chips == 4:
        graph = bench.build_graph_10m(np.random.default_rng(0))
        say(f"phase 4 graph built in {time.perf_counter() - t0:.1f} s")
        out = phase_four_chips(graph, bench.N10_WORKERS, n_devices=4)
        say(f"phase 4 four chips: {json.dumps(out)}")
    else:
        graph = bench.build_graph(np.random.default_rng(0))
        say(f"phase 2 graph built in {time.perf_counter() - t0:.1f} s")
        out = phase_whole_graph(graph, bench.N_WORKERS)
        say(f"phase 2 whole graph: {json.dumps(out)}")
        del graph
        t0 = time.perf_counter()
        out = phase_live_cluster()
        say(f"phase 3 live cluster ({time.perf_counter() - t0:.1f} s): "
            f"{json.dumps(out)}")
    import jax

    devices = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

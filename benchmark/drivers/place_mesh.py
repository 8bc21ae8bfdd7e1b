"""Whole-graph placement through the sharded engine, the call the
scheduler's planner makes on a multi-chip host
(``JaxPlacement._plan_from_arrays`` with ``scheduler.jax.mesh.enabled``
at ``auto``): ``place_graph_streamed`` over ``make_engine_mesh`` with the
fleet rows the mirror keeps sharded over the mesh's ``workers`` axis.

Set-up registers the fleet in a ``SchedulerState`` (``add_worker_state``,
so every row lives in the mirror), builds the mesh, makes the traffic's
graphs from the seed and places each once (the warm-up, which compiles
or loads every program the window uses).  The window and the comparison
with the plain reference are those of ``drivers/place.py``.

There is no other path: the run stops with an error, and prints no
result, when the host has fewer devices than the mesh, the mirror gives
no sharded view, or a placement did not run through the sharded program
with one shard per device and the planned number of fused runs.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import graphs, reference, roofline
from benchmark.compiles import CompileCounter
from benchmark.trace import Tracer


class MeshRefused(RuntimeError):
    """The run did not, or could not, place through the sharded engine."""


def run(config, traffic, seed, seconds, trace, devices, t_process, m):
    import jax

    from distributed_tpu.ops.leveled import _plan_streamed, place_graph_streamed
    from distributed_tpu.ops.partition import make_engine_mesh
    from distributed_tpu.scheduler.state import SchedulerState

    n, W, D = config["tasks"], config["workers"], config["chips"]
    if len(devices) < D or len(jax.devices()) < D:
        raise MeshRefused(f"the mesh needs {D} devices, the run has "
                          f"{len(devices)}")
    mesh = make_engine_mesh(D)
    if len(set(mesh.devices.flat)) != D:
        raise MeshRefused(f"make_engine_mesh({D}) gave {mesh.devices}")
    state = SchedulerState()
    for i in range(W):
        state.add_worker_state(f"tcp://bench:{i}", nthreads=config["threads"])
    fv = state.mirror.fleet_view()
    fleet = (fv.nthreads.copy(), fv.occupancy.copy(), fv.running.copy())
    gs = [graphs.make_graph(traffic, n, seed, i)
          for i in range(traffic["graphs"])]
    kw = {"bandwidth": config["bandwidth"], "latency": config["latency_s"]}
    if "min_stream" in config:
        kw["min_stream"] = config["min_stream"]

    def place(g, timings=None):
        fleet_dev = state.mirror.sharded_device_view(mesh)
        if fleet_dev is None:
            raise MeshRefused("the mirror gave no sharded fleet view")
        stats: dict = {}
        with jax.profiler.TraceAnnotation("bench.place"):
            # the assignment comes back to the host inside the call
            packed, res = place_graph_streamed(
                *g, *fleet, mesh=mesh, fleet_dev=fleet_dev, stats=stats,
                timings=timings, **kw
            )
        return packed, res, stats

    def check(stats, planned: int) -> None:
        if stats.get("n_shards") != D or stats.get("runs") != planned:
            raise MeshRefused(
                f"the placement ran {stats.get('runs')} fused runs on "
                f"{stats.get('n_shards')} shards; the sharded plan has "
                f"{planned} on {D}")

    got: list[tuple[int, np.ndarray]] = []
    planned = []  # fused runs of each graph's sharded plan
    with CompileCounter() as warm:
        for i, g in enumerate(gs):
            packed, res, stats = place(g)
            planned.append(len(_plan_streamed(packed.offsets, mesh)[1]))
            check(stats, planned[i])
            got.append((i, res.assignment))
    fleet_dev = state.mirror.sharded_device_view(mesh)
    if any(len({s.device for s in a.addressable_shards}) != D
           for a in fleet_dev.values()):
        raise MeshRefused("the mirror's fleet shards do not span the mesh")
    m["setup_s"] = time.perf_counter() - t_process
    m["setup_compiles"] = warm.n

    tracer = Tracer(trace, traffic["trace_seconds"])
    lat, topo, tiles = [], [], []
    attempted = 0
    with CompileCounter() as window:
        t0 = time.perf_counter()
        end = t0 + seconds
        tracer.start()
        while time.perf_counter() < end:
            i = attempted % len(gs)
            attempted += 1
            tm: dict = {}
            t = time.perf_counter()
            _, res, stats = place(gs[i], tm)
            lat.append(time.perf_counter() - t)
            check(stats, planned[i])
            topo.append(tm["topo_s"])
            if "tiles_s" in tm:
                tiles.append(tm["tiles_s"])
            got.append((i, res.assignment))
            tracer.step()
        window_s = time.perf_counter() - t0
        tracer.stop()
    m.update(window_s=window_s, latencies_s=lat, completed=len(lat),
             topo_s=topo, tiles_s=tiles, compiles_window=window.n)
    if trace:
        m["trace"] = tracer.reduce([d.id for d in devices[:D]])
        m["traced_ops"] = tracer.steps
        m["shards"] = D

    # the plain reference, once per graph, after the window; one thread
    # a graph (numpy's sorts drop the GIL), since at 10M tasks it holds
    # the four chips for as long as it runs
    t_ref = time.perf_counter()
    with ThreadPoolExecutor(len(gs)) as pool:
        refs = list(pool.map(
            lambda g: reference.place(*g, W, config["threads"],
                                      config["bandwidth"],
                                      config["latency_s"]), gs))
    ref_s = time.perf_counter() - t_ref
    if trace:
        # mean over the graphs of the cycle (the traced placements cycle)
        m["bytes_per_op"] = float(np.mean([
            roofline.placement_bytes(len(g[0]), W, int(level.max()) + 1)
            for g, (_, level) in zip(gs, refs)
        ]))
        m["tasks_per_op"] = n
    wrong = [int((a != refs[i][0]).sum()) for i, a in got]
    return {
        "ok": bool(lat),
        "attempted": attempted,
        "failed": sum(1 for w in wrong[len(gs):] if w),
        "checks": [("misplaced_tasks", sum(wrong), 0)],
        "notes": {"mesh": dict(mesh.shape), "reference_s": ref_s,
                  "misplaced_per_placement_max": max(wrong)},
    }

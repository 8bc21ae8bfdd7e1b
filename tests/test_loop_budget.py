"""One wall budget per event loop, on the device trace's clock
(diagnostics/selfprofile.py ``loop_budget``; docs/observability.md
"Self-profiling"): every role on a loop bills one budget, the loop's
selector wait is ``loop.idle``, no phase spans an ``await``, phases and
``ops/leveled.py``'s ``place.*`` spans show on a ``jax.profiler`` trace,
and the benchmark's readers of the new phases."""

from __future__ import annotations

import asyncio
import glob
import importlib.util
import os
import tempfile
import threading
import time

import numpy as np
import pytest

from distributed_tpu import tracing
from distributed_tpu.client.client import Client
from distributed_tpu.deploy.local import LocalCluster
from distributed_tpu.diagnostics.selfprofile import (
    LOOP_IDLE,
    WallBudget,
    loop_budget,
)
from distributed_tpu.graph.spec import Graph, TaskRef, TaskSpec
from distributed_tpu.scheduler.state import SchedulerState
from distributed_tpu.worker.state_machine import WorkerState

from conftest import gen_test

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _add(*xs):
    return sum(xs)


def _chain_graph(prefix: str, n: int) -> Graph:
    g = Graph()
    for i in range(n):
        deps = (TaskRef(f"{prefix}-{i - 1}"),) if i else ()
        g.tasks[f"{prefix}-{i}"] = TaskSpec(_add, (i, *deps))
    return g


# --------------------------------------------------------- the gate


class _CountingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: counts the
    objects built and entered."""

    enabled = False
    built: list[str] = []
    exited = 0

    def __init__(self, name):
        type(self).built.append(name)

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        type(self).exited += 1


@pytest.fixture
def counting_annotation(monkeypatch):
    _CountingAnnotation.enabled = False
    _CountingAnnotation.built = []
    _CountingAnnotation.exited = 0
    monkeypatch.setattr(tracing, "_TraceAnnotation", _CountingAnnotation)
    return _CountingAnnotation


def test_wall_budget_builds_no_annotation_without_a_trace(counting_annotation):
    b = WallBudget()
    with b.phase("ingest.graph"):
        with b.phase("engine.drain"):
            pass
    assert counting_annotation.built == []
    assert b.snapshot_counts() == {"ingest.graph": 1, "engine.drain": 1}


def test_wall_budget_mirrors_each_phase_while_a_trace_is_active(
        counting_annotation):
    counting_annotation.enabled = True
    b = WallBudget()
    with b.phase("ingest.graph"):
        with b.phase("engine.drain"):
            pass
    assert counting_annotation.built == ["ingest.graph", "engine.drain"]
    assert counting_annotation.exited == 2


@pytest.mark.parametrize("traced", [False, True])
def test_a_phase_self_time_excludes_its_mirror(counting_annotation,
                                               monkeypatch, traced):
    # each clock read advances 1 s, each annotation enter or exit 100 s:
    # self times read the same whether the phases are mirrored or not
    now = [0.0]

    def clock():
        now[0] += 1.0
        return now[0]

    def enter(self):
        now[0] += 100.0
        return self

    def leave(self, *exc):
        now[0] += 100.0

    monkeypatch.setattr(counting_annotation, "__enter__", enter)
    monkeypatch.setattr(counting_annotation, "__exit__", leave)
    counting_annotation.enabled = traced
    b = WallBudget(clock=clock)
    with b.phase("ingest.graph"):
        with b.phase("engine.drain"):
            pass
    assert b.snapshot() == {"ingest.graph": 2.0, "engine.drain": 1.0}
    assert len(counting_annotation.built) == (2 if traced else 0)


def test_stream_plane_bills_only_a_server_that_names_its_phase():
    from distributed_tpu.rpc.core import Server
    from distributed_tpu.scheduler.server import Scheduler
    from distributed_tpu.worker.server import Worker

    assert Server.stream_phase is None
    assert Scheduler.stream_phase == "ingest.stream"
    assert Worker.stream_phase == "wshell.stream"


def test_annotate_hands_out_one_shared_no_op_without_a_trace(
        counting_annotation):
    from distributed_tpu.diagnostics import device_profile

    assert device_profile.annotate("x") is device_profile.annotate("y")
    counting_annotation.enabled = True
    with device_profile.annotate("task-1"):
        pass
    assert counting_annotation.built == ["task-1"]
    assert counting_annotation.exited == 1


def test_trace_span_is_balanced_and_gated(counting_annotation):
    with tracing.TraceSpan("a"):
        pass
    counting_annotation.enabled = True
    with tracing.TraceSpan("b"):
        pass
    assert counting_annotation.built == ["b"]
    assert counting_annotation.exited == 1


def _host_span_names(logdir: str) -> set[str]:
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    assert files
    pd = ProfileData.from_file(files[0])
    return {e.name for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events}


def test_phases_and_place_spans_show_on_a_cpu_trace():
    import jax

    from distributed_tpu.ops.leveled import place_graph_streamed

    T, W = 4000, 8
    rng = np.random.default_rng(3)
    dst = np.arange(1, T, dtype=np.int32)
    src = (rng.integers(0, 1 << 30, T - 1) % dst).astype(np.int32)
    args = (rng.uniform(0.01, 1, T).astype(np.float32),
            rng.uniform(1e3, 1e7, T).astype(np.float32), src, dst,
            np.full(W, 2, np.int32), np.zeros(W, np.float32),
            np.ones(W, bool))
    place_graph_streamed(*args, min_stream=1, chunk_rows=1024)  # compile
    logdir = tempfile.mkdtemp(prefix="loop-budget-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    b = WallBudget()
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        with b.phase("ingest.graph"):
            place_graph_streamed(*args, min_stream=1, chunk_rows=1024)
    finally:
        jax.profiler.stop_trace()
    names = _host_span_names(logdir)
    assert "ingest.graph" in names
    assert {"place.topo", "place.fill_wait", "place.upload",
            "place.dispatch", "place.finalize"} <= names


# --------------------------------------------------- one loop, one budget


def test_loop_budget_is_per_loop_and_bare_states_keep_their_own():
    assert loop_budget() is not loop_budget()  # no loop: fresh each time

    async def twice():
        return loop_budget(), loop_budget()

    a1, a2 = asyncio.run(twice())
    b1, _ = asyncio.run(twice())
    assert a1 is a2 and a1 is not b1
    assert SchedulerState().wall is not SchedulerState().wall
    assert WorkerState().wall is not WorkerState().wall
    shared = WallBudget()
    assert SchedulerState(wall=shared).wall is shared
    assert WorkerState(wall=shared).wall is shared


@gen_test()
async def test_local_cluster_bills_every_role_into_the_loop_budget():
    budget = loop_budget()
    # every selector wait must find the loop thread outside every phase:
    # a phase left open there would span an await
    loop = asyncio.get_running_loop()
    select = loop._selector.select
    ident = threading.get_ident()
    at_wait: list[tuple[str, str]] = []

    def checked_select(timeout=None):
        at_wait.append(budget.current(ident))
        return select(timeout)

    loop._selector.select = checked_select
    async with LocalCluster(n_workers=2, threads_per_worker=1) as cluster:
        async with Client(cluster.scheduler_address) as client:
            assert cluster.scheduler.wall is budget
            assert cluster.scheduler.state.wall is budget
            assert all(w.state.wall is budget for w in cluster.workers)
            assert client.wall is budget
            g = _chain_graph("lb", 20)
            keys = list(g.tasks)
            futs = client.compute_graph(g, keys)
            got = await client.gather([futs[k] for k in keys])
            assert got[-1] == sum(range(20))
    totals = budget.snapshot()
    for phase in (LOOP_IDLE, "ingest.graph", "ingest.stream",
                  "wengine.stimulus", "wshell.instructions",
                  "wshell.stream", "client.submit", "client.gather",
                  "engine.drain"):
        assert totals.get(phase, 0.0) > 0.0, phase
    assert at_wait and set(at_wait) == {("", "")}


# ------------------------------------------------------ place.* timings


def test_place_graph_streamed_fills_its_span_timings():
    from distributed_tpu.ops.leveled import place_graph_streamed

    T, W = 20000, 16
    rng = np.random.default_rng(5)
    dst = np.arange(1, T, dtype=np.int32)
    src = (rng.integers(0, 1 << 30, T - 1) % dst).astype(np.int32)
    args = (rng.uniform(0.01, 1, T).astype(np.float32),
            rng.uniform(1e3, 1e7, T).astype(np.float32), src, dst,
            np.full(W, 2, np.int32), np.zeros(W, np.float32),
            np.ones(W, bool))
    place_graph_streamed(*args, min_stream=1, chunk_rows=4096)  # compile
    tm: dict = {}
    t0 = time.perf_counter()
    place_graph_streamed(*args, min_stream=1, chunk_rows=4096, timings=tm)
    wall = time.perf_counter() - t0
    spans = ("topo_s", "fill_wait_s", "upload_s", "dispatch_s",
             "finalize_s")
    assert set(tm) == {*spans, "tiles_s", "fmt", "wave_rows", "padded_rows",
                       "topo_threads", "tile_threads"}
    assert all(tm[k] > 0 for k in spans)
    assert tm["tiles_s"] == 0.0  # off the mesh: no sharded tiles
    assert tm["tile_threads"] == 0
    assert sum(tm[k] for k in spans) <= wall


# ------------------------------------------------- the benchmark readers


def _reader(name: str):
    path = os.path.join(REPO, "benchmark", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


WALL = {
    "loop.idle": 4.0, "ingest.graph": 0.5, "ingest.stream": 0.25,
    "plan.snapshot": 0.1, "plan.merge": 0.02, "steal.cycle": 0.2,
    "amm.cycle": 0.1, "mirror.upload": 0.05, "telemetry.fold": 0.05,
    "wengine.stimulus": 1.0, "wshell.instructions": 0.5,
    "wshell.stream": 0.25, "client.submit": 0.3, "client.gather": 0.1,
    "engine.drain": 2.0, "kernel.dispatch": 0.3,
}


@pytest.mark.parametrize("name, want", [
    ("loop_busy_pct", 100 * (1 - 4.0 / 10.0)),
    ("ingest_us_per_task", 0.75 / 1000 * 1e6),
    ("plan_us_per_task", 0.12 / 1000 * 1e6),
    ("periodic_us_per_task", 0.4 / 1000 * 1e6),
    ("worker_us_per_task", 1.75 / 1000 * 1e6),
    ("client_us_per_task", 0.4 / 1000 * 1e6),
])
def test_new_readers_on_a_hand_built_wall_delta(name, want):
    read = _reader(name)
    m = {"wall_delta": dict(WALL), "tasks_done": 1000, "window_s": 10.0}
    assert read(m) == pytest.approx(want)
    # the parent's budget: the scheduler's phases only, none of the new
    parent = {k: v for k, v in WALL.items()
              if k in ("engine.drain", "kernel.dispatch", "mirror.upload",
                       "telemetry.fold")}
    assert read({**m, "wall_delta": parent}) is None
    assert read({"tasks_done": 1000, "window_s": 10.0}) is None

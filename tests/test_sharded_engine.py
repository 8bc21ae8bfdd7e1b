"""Sharded leveled engine (ops/leveled.place_graph_leveled_sharded):
lockstep parity with the single-device engine across device meshes.

On every mesh the kernel computes the same floating-point expressions
in the same order as ``_place_run`` (each shard sums the gathered
per-task load terms in task order), so assignments, choices, occupancy
and start times must be bit-identical to the single-device engine.

Role model: the reference keeps scheduler decisions identical under
transport changes; here the mesh partitioning is the "transport" of the
placement co-processor (same contract style as
tests/test_leveled_streamed.py).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax

from distributed_tpu import config
from distributed_tpu.ops.leveled import (
    pack_graph,
    place_graph_leveled,
    place_graph_leveled_sharded,
    place_graph_streamed,
    validate_leveled,
)
from distributed_tpu.ops import leveled
from distributed_tpu.ops.partition import make_engine_mesh, shard_bucket
from distributed_tpu import native

from test_leveled import BW, random_dag, relabel, stencil_dag, workers

MESH_LAYOUTS = ["1x1", "2x1", "4x2", "8x1"]

needs_native = pytest.mark.skipif(
    native.load() is None, reason="native toolchain unavailable"
)


def _needed(layout: str) -> int:
    dt, dw = (int(p) for p in layout.split("x"))
    return dt * dw


def _mesh_or_skip(layout: str):
    if len(jax.devices()) < _needed(layout):
        pytest.skip(f"mesh {layout} needs {_needed(layout)} devices")
    return make_engine_mesh(layout=layout)


# ------------------------------------------------------------- parity


@pytest.mark.parametrize("layout", MESH_LAYOUTS)
@pytest.mark.parametrize("seed,T,W", [(0, 3000, 16), (1, 12_000, 64)])
def test_lockstep_parity_randomized(layout, seed, T, W):
    """Randomized graphs + non-uniform fleets (mixed occupancy, stopped
    workers) against every mesh shape, bit-identical on each."""
    mesh = _mesh_or_skip(layout)
    rng = np.random.default_rng(seed)
    durations, out_bytes, src, dst = random_dag(rng, T)
    nthreads, occ0, running = workers(W, stopped=(2,) if W > 8 else ())
    occ0 = rng.uniform(0, 2.0, W).astype(np.float32)
    packed = pack_graph(durations, out_bytes, src, dst, bandwidth=BW)
    res = place_graph_leveled(packed, nthreads, occ0, running)
    res_sh = place_graph_leveled_sharded(
        mesh, packed, nthreads, occ0, running
    )
    assert (res_sh.assignment >= 0).all()
    assert running[res_sh.assignment].all()
    np.testing.assert_array_equal(res_sh.assignment, res.assignment)
    np.testing.assert_array_equal(res_sh.choice, res.choice)
    np.testing.assert_array_equal(res_sh.occupancy, res.occupancy)
    np.testing.assert_array_equal(res_sh.start_time, res.start_time)


@pytest.mark.parametrize("layout", ["1x1", "4x2"])
def test_uniform_fleet_takes_uniform_kernel_path(layout):
    """A homogeneous idle fleet routes both engines through their
    ``uniform`` fast path; parity must hold there too (the scalar
    queue-cost specialization changes the fp expression tree)."""
    mesh = _mesh_or_skip(layout)
    rng = np.random.default_rng(3)
    durations, out_bytes, src, dst = random_dag(rng, 5_000)
    nthreads, occ0, running = workers(32)
    packed = pack_graph(durations, out_bytes, src, dst, bandwidth=BW)
    res = place_graph_leveled(packed, nthreads, occ0, running)
    res_sh = place_graph_leveled_sharded(
        mesh, packed, nthreads, occ0, running
    )
    np.testing.assert_array_equal(res_sh.assignment, res.assignment)
    np.testing.assert_array_equal(res_sh.choice, res.choice)


@needs_native
def test_streamed_sharded_matches_oneshot_sharded():
    """The streamed driver's sharded branch (per-run tiles assembled
    while the pack fill is still running) must equal the one-shot
    sharded engine — the overlap is transport, not semantics."""
    mesh = _mesh_or_skip("4x2")
    rng = np.random.default_rng(11)
    durations, out_bytes, src, dst = random_dag(rng, 40_000)
    nthreads, occ0, running = workers(16)
    packed = pack_graph(durations, out_bytes, src, dst, bandwidth=BW)
    res_one = place_graph_leveled_sharded(
        mesh, packed, nthreads, occ0, running
    )
    tm: dict = {}
    stats: dict = {}
    packed2, res_str = place_graph_streamed(
        durations, out_bytes, src, dst, nthreads, occ0, running,
        bandwidth=BW, chunk_rows=7_000, min_stream=1, mesh=mesh,
        timings=tm, stats=stats,
    )
    assert tm["fmt"] == "f16"  # sharded wire is always exact
    np.testing.assert_array_equal(res_str.assignment, res_one.assignment)
    np.testing.assert_array_equal(res_str.choice, res_one.choice)
    validate_leveled(packed2, res_str, src, dst, running)
    # per-shard H2D accounting: every shard shipped the same tile bytes
    assert stats["n_shards"] == 8
    bytes_per_shard = {row["h2d_bytes"] for row in stats["shards"]}
    assert len(bytes_per_shard) == 1 and bytes_per_shard.pop() > 0


def fanout_dag(rng):
    """One root, 6,000 leaves, 60 reducers of 100 leaves, 6 of 10
    reducers, one final task: five waves, so K = 8 where they fuse."""
    n_leaf, n_mid, n_top = 6000, 60, 6
    n = 1 + n_leaf + n_mid + n_top + 1
    leaves = np.arange(1, 1 + n_leaf)
    mids = 1 + n_leaf + np.arange(n_mid)
    tops = mids[-1] + 1 + np.arange(n_top)
    src = np.concatenate([np.zeros(n_leaf, np.int64), leaves,
                          mids, tops])
    dst = np.concatenate([leaves, mids[(leaves - 1) // 100],
                          tops[np.arange(n_mid) // 10],
                          np.full(n_top, n - 1)])
    return (rng.uniform(0.01, 1.0, n).astype(np.float32),
            rng.uniform(1e3, 1e7, n).astype(np.float32),
            src.astype(np.int32), dst.astype(np.int32))


TILE_GRAPHS = {
    "random": lambda rng: random_dag(rng, 12_000),
    "relabelled": lambda rng: relabel(rng, random_dag(rng, 8_000, 3)),
    "stencil": lambda rng: stencil_dag(rng, steps=21, chunks=300),
    "fanout": fanout_dag,
}


def _poisoned(buf, lo: int, hi: int):
    """``buf`` with every row outside ``[lo, hi)`` made unusable: NaN,
    or an index no array has."""
    out = np.array(buf)
    bad = np.nan if out.dtype.kind == "f" else np.iinfo(out.dtype).min
    out[:lo] = bad
    out[hi:] = bad
    return out


def _expected_tile(src, dtype, offsets, waves, K: int, F: int):
    """A run's ``[K, F]`` tile as the kernel may be given it: each wave's
    rows cast to the wire dtype, every other lane zero."""
    tile = np.zeros((K, F), dtype)
    for i, w in enumerate(waves):
        a, b = offsets[w], offsets[w + 1]
        tile[i, : b - a] = src[a:b]
    return tile


@needs_native
@pytest.mark.parametrize("assembly", ["inline", "pooled"])
@pytest.mark.parametrize("engine", ["streamed", "oneshot"])
@pytest.mark.parametrize("graph", sorted(TILE_GRAPHS))
@pytest.mark.parametrize("layout", ["1x1", "2x2", "4x2"])
def test_shard_tiles_read_only_their_waves(layout, graph, engine, assembly,
                                           monkeypatch):
    """Every run's tiles hold its waves' rows and zeros, whoever builds
    them: with each host row outside the run's waves (the ``Tp`` tail
    too) poisoned before the tiles read it, the shipped tiles equal the
    plain tile byte for byte, and the placement equals both one-shot
    engines bit for bit.  In line and on the tile pool (the gate forced
    either way) give the same bytes."""
    mesh = _mesh_or_skip(layout)
    D = _needed(layout)
    g = TILE_GRAPHS[graph](np.random.default_rng(17))
    nthreads, occ0, running = workers(24, stopped=(3,))
    packed = pack_graph(*g, bandwidth=BW)
    want = place_graph_leveled(packed, nthreads, occ0, running)
    runs = leveled._plan_runs_sharded(packed.offsets, D)
    sizes = np.diff(packed.offsets)
    # the runs this graph gives have padding rows and narrow waves
    assert any(leveled._bucket(len(ws), floor=1) > len(ws) for _, ws in runs)
    assert any(sizes[w] < Fl * D for Fl, ws in runs for w in ws)

    monkeypatch.setattr(leveled, "_TILE_POOL_LANES",
                        -1 if assembly == "pooled" else 2**62)
    shipped = []
    tiles = leveled._ShardedRunState.tiles

    def poisoned_tiles(self, host_bufs, Fl, waves):
        lo = int(self.packed.offsets[waves[0]])
        hi = int(self.packed.offsets[waves[-1] + 1])
        out = tiles(self, tuple(_poisoned(b, lo, hi) for b in host_bufs),
                    Fl, waves)
        shipped.append((Fl, waves, [np.asarray(t) for t in out]))
        return out

    monkeypatch.setattr(leveled._ShardedRunState, "tiles", poisoned_tiles)
    tm: dict = {}
    if engine == "streamed":
        packed, res = place_graph_streamed(
            *g, nthreads, occ0, running, bandwidth=BW, chunk_rows=3_000,
            min_stream=1, mesh=mesh, timings=tm,
        )
        threads = leveled._tile_threads(6 * D) if assembly == "pooled" else 1
        assert tm["tile_threads"] == threads
    else:
        res = place_graph_leveled_sharded(mesh, packed, nthreads, occ0,
                                          running)
    for field in ("assignment", "choice", "occupancy", "start_time"):
        np.testing.assert_array_equal(getattr(res, field),
                                      getattr(want, field), err_msg=field)
    assert [(Fl, ws) for Fl, ws, _ in shipped] == runs
    srcs = (packed.duration_s, packed.heavy_s, packed.heavy2_s,
            packed.xfer_pref_s, packed.xfer_pref2_s, packed.xfer_all_s)
    dtypes = leveled._ShardedRunState._TASK_DTYPES
    for Fl, waves, got in shipped:
        K = leveled._bucket(len(waves), floor=1)
        for src, dtype, tile in zip(srcs, dtypes, got):
            ref = _expected_tile(src, dtype, packed.offsets, waves, K, Fl * D)
            assert tile.dtype == ref.dtype and tile.shape == ref.shape
            assert tile.tobytes() == ref.tobytes()


def test_streamed_sharded_fallback_below_threshold():
    """Below min_stream the mesh path delegates to pack + one-shot
    sharded place — same results, no fill thread."""
    mesh = _mesh_or_skip("2x1")
    rng = np.random.default_rng(14)
    durations, out_bytes, src, dst = random_dag(rng, 2_000)
    nthreads, occ0, running = workers(8)
    packed = pack_graph(durations, out_bytes, src, dst, bandwidth=BW)
    res0 = place_graph_leveled_sharded(mesh, packed, nthreads, occ0,
                                       running)
    tm: dict = {}
    _, res1 = place_graph_streamed(
        durations, out_bytes, src, dst, nthreads, occ0, running,
        bandwidth=BW, min_stream=1_000_000, mesh=mesh, timings=tm,
    )
    np.testing.assert_array_equal(res1.assignment, res0.assignment)
    assert tm["tile_threads"] == 1  # small tiles: built in line


def test_stopped_workers_never_assigned_on_mesh():
    mesh = _mesh_or_skip("4x2")
    rng = np.random.default_rng(13)
    durations, out_bytes, src, dst = random_dag(rng, 6_000)
    nthreads, occ0, running = workers(16, stopped=(2, 5, 11))
    packed = pack_graph(durations, out_bytes, src, dst, bandwidth=BW)
    res = place_graph_leveled_sharded(mesh, packed, nthreads, occ0,
                                      running)
    assert (res.assignment >= 0).all()
    assert running[res.assignment].all()


def test_shard_bucket_geometry():
    assert shard_bucket(0, 8, floor=512) == 512
    assert shard_bucket(4096, 8, floor=512) == 512
    assert shard_bucket(4097, 8, floor=512) == 1024
    assert shard_bucket(4096, 1, floor=512) == 4096
    # never below one lane per shard even for degenerate floors
    assert shard_bucket(5, 8, floor=1) == 1


# ----------------------------------------------- mirror-resident fleet


def test_mirror_fleet_dev_path_matches_host_upload():
    """The engine fed the mirror's workers-axis device shards must place
    identically to the same engine fed replicated host arrays — and a
    fresh second cycle must ship zero fleet rows on every shard."""
    from distributed_tpu.scheduler.state import SchedulerState

    mesh = _mesh_or_skip("4x2")
    state = SchedulerState()
    assert state.mirror is not None
    W = 32
    for i in range(W):
        state.add_worker_state(f"tcp://se:{i}", nthreads=2,
                               memory_limit=2**30, name=f"w{i}")
    fv = state.mirror.fleet_view()
    nthreads = fv.nthreads.copy()
    occ0 = fv.occupancy.copy()
    running = fv.running.copy()
    rng = np.random.default_rng(21)
    durations, out_bytes, src, dst = random_dag(rng, 4_000)
    packed = pack_graph(durations, out_bytes, src, dst, bandwidth=BW)

    res_host = place_graph_leveled_sharded(
        mesh, packed, nthreads, occ0, running
    )
    fleet_dev = state.mirror.sharded_device_view(mesh)
    assert fleet_dev is not None
    res_dev = place_graph_leveled_sharded(
        mesh, packed, nthreads, occ0, running, fleet_dev=fleet_dev
    )
    np.testing.assert_array_equal(res_dev.assignment, res_host.assignment)

    before = state.mirror.sharded_stats()
    res_dev2 = place_graph_leveled_sharded(
        mesh, packed, nthreads, occ0, running,
        fleet_dev=state.mirror.sharded_device_view(mesh),
    )
    after = state.mirror.sharded_stats()
    assert after["rows_uploaded"] == before["rows_uploaded"]
    assert after["full_packs"] == before["full_packs"]
    np.testing.assert_array_equal(res_dev2.assignment, res_dev.assignment)


# -------------------------------------------------- mesh plan path


def _inc(x):
    return x + 1


def test_jax_placement_mesh_plan_path_and_stats():
    """JaxPlacement with the mesh subtree enabled plans through the
    sharded engine: hints land, the state records per-shard engine
    stats, and the mirror's shards stay cold on a fresh plan."""
    from distributed_tpu.graph.spec import TaskSpec
    from distributed_tpu.scheduler.jax_placement import JaxPlacement
    from distributed_tpu.scheduler.state import SchedulerState

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    with config.set({
        "scheduler.jax.mesh.enabled": True,
        "scheduler.jax.mesh.layout": "4x2",
        "scheduler.jax.partitioner": "off",
    }):
        placement = JaxPlacement(min_batch=4, min_workers=0, sync=True,
                                 min_transfer_ratio=0)
        state = SchedulerState(placement=placement)
        for i in range(16):
            state.add_worker_state(f"tcp://mp:{i}", nthreads=2,
                                   memory_limit=2**30, name=f"w{i}")
        tasks = {}
        deps: dict = {}
        for i in range(120):
            tasks[f"a-{i}"] = TaskSpec(_inc, (i,))
            deps[f"a-{i}"] = set()
            tasks[f"b-{i}"] = TaskSpec(_inc, (i,))
            deps[f"b-{i}"] = {f"a-{i}"}
        state.update_graph_core(tasks, deps, list(tasks), client="t",
                                stimulus_id="mesh-plan")
        assert placement.plans_computed == 1
        assert len(state.engine_shards) == 8
        assert all(r["h2d_bytes"] > 0 for r in state.engine_shards)
        assert all(r["plans"] == 1 for r in state.engine_shards)
        ss = state.mirror.sharded_stats()
        assert ss["n_shards"] == 2
        assert ss["rows_uploaded"] == [0, 0]  # fresh fleet: full pack only
        assert ss["full_packs"] == [1, 1]


def test_jax_placement_mesh_auto_default(monkeypatch):
    """``scheduler.jax.mesh.enabled`` defaults to "auto" (ROADMAP item
    2 leftover): on when more than one device is visible at mesh-build
    time, single-device path otherwise, explicit booleans force."""
    from distributed_tpu.scheduler.jax_placement import JaxPlacement

    # default parses to auto (None)
    placement = JaxPlacement(min_batch=4, min_workers=0, sync=True)
    assert placement.mesh_enabled is None

    # explicit off stays off, never builds
    with config.set({"scheduler.jax.mesh.enabled": False}):
        off = JaxPlacement(min_batch=4, min_workers=0, sync=True)
        assert off.mesh_enabled is False
        assert off._get_mesh(build=True) is None

    # auto on a 1-device host: the single-device path (a 1x1 mesh is
    # bit-identical but pays dispatch overhead for nothing)
    single = JaxPlacement(min_batch=4, min_workers=0, sync=True)
    all_devices = jax.devices()
    with monkeypatch.context() as m:
        m.setattr(jax, "devices", lambda *a: all_devices[:1])
        assert single._get_mesh(build=True) is None

    # auto on this multi-device host: the mesh builds
    if len(jax.devices()) >= 2:
        multi = JaxPlacement(min_batch=4, min_workers=0, sync=True)
        mesh = multi._get_mesh(build=True)
        assert mesh is not None
        assert mesh.devices.size == len(jax.devices())


def test_jax_placement_bad_layout_falls_back():
    """An impossible layout must not kill planning: the mesh builder
    logs and the planner degrades to the single-device engine."""
    from distributed_tpu.graph.spec import TaskSpec
    from distributed_tpu.scheduler.jax_placement import JaxPlacement
    from distributed_tpu.scheduler.state import SchedulerState

    with config.set({
        "scheduler.jax.mesh.enabled": True,
        "scheduler.jax.mesh.layout": "64x64",  # more than any host has
        "scheduler.jax.partitioner": "off",
    }):
        placement = JaxPlacement(min_batch=4, min_workers=0, sync=True,
                                 min_transfer_ratio=0)
        assert placement._get_mesh(build=True) is None
        state = SchedulerState(placement=placement)
        for i in range(8):
            state.add_worker_state(f"tcp://fb:{i}", nthreads=2,
                                   memory_limit=2**30, name=f"w{i}")
        tasks = {f"t-{i}": TaskSpec(_inc, (i,)) for i in range(64)}
        state.update_graph_core(tasks, {k: set() for k in tasks},
                                list(tasks), client="t",
                                stimulus_id="mesh-fallback")
        # the plan still landed — through the single-device engine
        assert placement.plans_computed == 1
        assert state.engine_shards == []

"""Parity tests: JAX placement kernels vs a straight-line float32 python
oracle replicating the reference's decide_worker/worker_objective semantics
(scheduler.py:8550, 3131).  Runs on the 8-device CPU mesh from conftest."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_tpu.ops.placement import (
    PlacementBatch,
    WorkerArrays,
    build_batch_arrays,
    decide_workers,
    occupancy_after_finish,
    pad_to_bucket,
    place_rootish,
)
from distributed_tpu.ops.wavefront import GraphArrays, PlacementResult, place_graph, validate_placement

BW = 100e6


def random_problem(rng, B=50, W=8, D=30, E=120, restrict_frac=0.0):
    occ = rng.uniform(0, 5, W).astype(np.float32)
    threads = rng.integers(1, 5, W).astype(np.int32)
    wnbytes = rng.uniform(0, 1e9, W).astype(np.float32)
    running = np.ones(W, bool)
    running[rng.random(W) < 0.2] = False
    if not running.any():
        running[0] = True
    durations = rng.uniform(0.001, 1.0, B).astype(np.float32)
    dep_bytes = rng.uniform(1e3, 1e8, D).astype(np.float32)
    has = rng.random((D, W)) < 0.3
    edge_task = rng.integers(0, B, E).astype(np.int32)
    edge_dep = rng.integers(0, D, E).astype(np.int32)
    restrict = None
    if restrict_frac:
        restrict = np.ones((B, W), bool)
        mask_rows = rng.random(B) < restrict_frac
        for i in np.flatnonzero(mask_rows):
            allowed = rng.random(W) < 0.4
            restrict[i] = allowed
    workers = WorkerArrays(
        nthreads=jnp.asarray(threads),
        occupancy=jnp.asarray(occ),
        nbytes=jnp.asarray(wnbytes),
        running=jnp.asarray(running),
    )
    batch = build_batch_arrays(durations, (edge_task, edge_dep), dep_bytes, has,
                               restrict=restrict)
    raw = dict(
        occ=occ, threads=threads, wnbytes=wnbytes, running=running,
        durations=durations, dep_bytes=dep_bytes, has=has,
        edge_task=edge_task, edge_dep=edge_dep, restrict=restrict,
    )
    return workers, batch, raw


def oracle_sequential(raw, bandwidth=BW):
    """Float32 replica of the reference decide_worker loop."""
    B = len(raw["durations"])
    W = len(raw["threads"])
    occ = raw["occ"].copy()
    thr = np.maximum(raw["threads"], 1).astype(np.float32)
    inv_bw = np.float32(1.0 / bandwidth)
    # per-task dep lists
    deps = [[] for _ in range(B)]
    for t, d in zip(raw["edge_task"], raw["edge_dep"]):
        deps[t].append(d)
    out = np.full(B, -1, np.int32)
    for t in range(B):
        missing = np.zeros(W, np.float32)
        holder = np.zeros(W, bool)
        for d in deps[t]:
            missing += np.float32(raw["dep_bytes"][d]) * (~raw["has"][d])
            holder |= raw["has"][d]
        holder &= raw["running"]
        cand = holder if holder.any() else raw["running"].copy()
        if raw["restrict"] is not None:
            r = cand & raw["restrict"][t]
            if not r.any():
                r = raw["restrict"][t] & raw["running"]
            cand = r
        if not cand.any():
            continue
        cost = occ / thr + missing * inv_bw
        best = min(
            np.flatnonzero(cand),
            key=lambda w: (cost[w], raw["wnbytes"][w], w),
        )
        out[t] = best
        # raw seconds booked; divide once at compare (reference :3140)
        occ[best] += np.float32(raw["durations"][t]) + missing[best] * inv_bw
    return out, occ


@pytest.mark.parametrize("seed", range(5))
def test_decide_workers_matches_oracle(seed):
    rng = np.random.default_rng(seed)
    workers, batch, raw = random_problem(rng)
    assign, occ = decide_workers(workers, batch, BW, sequential=True)
    expected, occ_expected = oracle_sequential(raw)
    B = len(raw["durations"])
    np.testing.assert_array_equal(np.asarray(assign)[:B], expected)
    np.testing.assert_allclose(np.asarray(occ), occ_expected, rtol=1e-5)


@pytest.mark.parametrize("seed", range(3))
def test_decide_workers_with_restrictions(seed):
    rng = np.random.default_rng(100 + seed)
    workers, batch, raw = random_problem(rng, restrict_frac=0.5)
    assign, _ = decide_workers(workers, batch, BW, sequential=True)
    expected, _ = oracle_sequential(raw)
    B = len(raw["durations"])
    np.testing.assert_array_equal(np.asarray(assign)[:B], expected)


def test_decide_workers_parallel_mode_valid():
    rng = np.random.default_rng(7)
    workers, batch, raw = random_problem(rng, B=100)
    assign, occ = decide_workers(workers, batch, BW, sequential=False)
    a = np.asarray(assign)[:100]
    assert (a >= 0).all()
    assert raw["running"][a].all()  # never places on stopped workers


def test_padding_rows_unassigned():
    rng = np.random.default_rng(3)
    workers, batch, raw = random_problem(rng, B=10)
    assert batch.duration.shape[0] == pad_to_bucket(10)
    assign, _ = decide_workers(workers, batch, BW, sequential=True)
    assert (np.asarray(assign)[10:] == -1).all()


def test_place_rootish_balanced():
    W = 8
    threads = np.array([2, 2, 2, 2, 4, 4, 1, 1], np.int32)
    running = np.ones(W, bool)
    running[3] = False
    workers = WorkerArrays(
        nthreads=jnp.asarray(threads),
        occupancy=jnp.zeros(W, jnp.float32),
        nbytes=jnp.zeros(W, jnp.float32),
        running=jnp.asarray(running),
    )
    n = 160
    assign = np.asarray(place_rootish(jnp.int32(n), workers, max_tasks=256))
    live = assign[:n]
    assert (live >= 0).all()
    assert not (live == 3).any()  # stopped worker skipped
    counts = np.bincount(live, minlength=W)
    # proportional to threads (2,2,2,0,4,4,1,1 = 16 capacity for 160 tasks)
    expected = threads * np.where(running, 1, 0) * 10
    assert (np.abs(counts - expected) <= 16).all(), (counts, expected)
    # contiguity: siblings co-assigned in blocks (like tg.last_worker)
    changes = (np.diff(live) != 0).sum()
    assert changes <= len(np.unique(live))  # one contiguous block per worker
    assert (assign[n:] == -1).all()


def test_occupancy_after_finish():
    occ = jnp.asarray(np.array([5.0, 3.0, 1.0], np.float32))
    threads = jnp.asarray(np.array([2, 1, 1], np.int32))
    fw = jnp.asarray(np.array([0, 0, 1, -1], np.int32))
    fd = jnp.asarray(np.array([2.0, 2.0, 1.0, 99.0], np.float32))
    out = np.asarray(occupancy_after_finish(occ, threads, fw, fd))
    # raw-seconds booking: worker 0 releases 4.0, worker 1 releases 1.0
    np.testing.assert_allclose(out, [1.0, 2.0, 1.0])


# ---------------------------------------------------------- wavefront

def chain_graph(n=50):
    durations = np.ones(n, np.float32)
    out_bytes = np.full(n, 1e6, np.float32)
    src = np.arange(n - 1, dtype=np.int64)
    dst = src + 1
    return GraphArrays.from_arrays(durations, out_bytes, src, dst,
                                   pad_tasks=n + 1, pad_edges=n)


def mapreduce_graph(width=64, reducers=8):
    """width roots -> reducers -> 1 total."""
    n = width + reducers + 1
    durations = np.ones(n, np.float32)
    out_bytes = np.full(n, 1e6, np.float32)
    src, dst = [], []
    per = width // reducers
    for r in range(reducers):
        for i in range(r * per, (r + 1) * per):
            src.append(i)
            dst.append(width + r)
    for r in range(reducers):
        src.append(width + r)
        dst.append(width + reducers)
    return n, GraphArrays.from_arrays(
        durations, out_bytes,
        np.asarray(src, np.int64), np.asarray(dst, np.int64),
        pad_tasks=n + 7, pad_edges=len(src) + 5,
    )


def _workers(W=4, threads=2):
    return (
        jnp.full(W, threads, jnp.int32),
        jnp.zeros(W, jnp.float32),
        jnp.ones(W, bool),
    )


def test_wavefront_chain():
    g = chain_graph(50)
    nthreads, occ, running = _workers(4)
    res = place_graph(g, nthreads, occ, running, bandwidth=BW)
    validate_placement(g, res, np.asarray(running))
    assert int(res.n_waves) == 50  # one wave per chain link
    a = np.asarray(res.assignment)[:50]
    # locality: the chain should stay on one worker (heavy-dep following)
    assert len(np.unique(a)) == 1


def test_wavefront_mapreduce():
    n, g = mapreduce_graph(64, 8)
    nthreads, occ, running = _workers(8, threads=2)
    res = place_graph(g, nthreads, occ, running, bandwidth=BW)
    validate_placement(g, res, np.asarray(running))
    assert int(res.n_waves) == 3
    a = np.asarray(res.assignment)
    roots = a[:64]
    counts = np.bincount(roots, minlength=8)
    assert counts.max() <= 2 * counts.min() + 2, counts  # spread evenly
    # each reducer lands with its heaviest input (one of its 8 feeders)
    for r in range(8):
        feeders = set(roots[r * 8:(r + 1) * 8])
        assert a[64 + r] in feeders


def test_wavefront_respects_stopped_workers():
    n, g = mapreduce_graph(32, 4)
    nthreads, occ, running = _workers(4)
    running = running.at[2].set(False)
    res = place_graph(g, nthreads, occ, running, bandwidth=BW)
    a = np.asarray(res.assignment)
    valid = np.asarray(g.valid)
    assert not (a[valid] == 2).any()


def test_wavefront_random_dag():
    rng = np.random.default_rng(0)
    n = 500
    durations = rng.uniform(0.01, 1, n).astype(np.float32)
    out_bytes = rng.uniform(1e3, 1e7, n).astype(np.float32)
    src, dst = [], []
    for t in range(1, n):
        for d in rng.integers(0, t, rng.integers(0, 3)):
            src.append(d)
            dst.append(t)
    g = GraphArrays.from_arrays(
        durations, out_bytes,
        np.asarray(src, np.int64), np.asarray(dst, np.int64),
        pad_tasks=512, pad_edges=pad_to_bucket(len(src)),
    )
    nthreads, occ, running = _workers(16)
    res = place_graph(g, nthreads, occ, running, bandwidth=BW)
    validate_placement(g, res, np.asarray(running))
    # placement must track dependency order: start[dst] >= start[src] is not
    # guaranteed by the model, but wave count must be <= depth bound
    assert 1 <= int(res.n_waves) <= n


# ---------------------------------------------------------- sharded

def test_sharded_matches_single_device():
    from distributed_tpu.parallel.mesh import make_mesh, sharded_decide_workers

    if len(jax.devices()) < 2:
        pytest.skip("needs multi-device mesh")
    rng = np.random.default_rng(42)
    workers, batch, raw = random_problem(rng, B=64, W=16, D=32, E=200)
    mesh = make_mesh(8)
    sharded = sharded_decide_workers(mesh, workers, batch, BW)
    single, _ = decide_workers(workers, batch, BW, sequential=False)
    np.testing.assert_array_equal(np.asarray(sharded), np.asarray(single))


def test_make_mesh_shapes():
    from distributed_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8)
    assert mesh.shape["tasks"] * mesh.shape["workers"] == 8


def test_sharded_leveled_matches_single_device():
    """The sharded (data-parallel over waves, all_gathers per wave)
    leveled engine must reproduce the single-device engine the live
    scheduler runs (parallel/mesh.py place_graph_leveled_sharded)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from distributed_tpu.ops.leveled import pack_graph, place_graph_leveled
    from distributed_tpu.parallel.mesh import place_graph_leveled_sharded

    rng = np.random.default_rng(0)
    T, W = 512, 16
    dur = rng.uniform(0.01, 1, T).astype(np.float32)
    ob = rng.uniform(1e3, 1e6, T).astype(np.float32)
    src, dst = [], []
    for t in range(1, T):
        for d in rng.integers(0, t, rng.integers(0, 3)):
            src.append(int(d))
            dst.append(t)
    src = np.asarray(src, np.int32)
    dst = np.asarray(dst, np.int32)
    packed = pack_graph(dur, ob, src, dst)
    nth = np.full(W, 2, np.int32)
    occ = rng.uniform(0, 0.5, W).astype(np.float32)
    run = np.ones(W, bool)

    n_dev = min(8, len(jax.devices()))
    mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("tasks",))
    a_sh, load_sh = place_graph_leveled_sharded(mesh, packed, nth, occ, run)
    res = place_graph_leveled(packed, nth, occ, run)
    assert (a_sh >= 0).all() and (a_sh < W).all()
    # identical decisions: the same math, summed in the same order
    np.testing.assert_array_equal(a_sh, res.assignment)
    np.testing.assert_array_equal(load_sh, res.occupancy)

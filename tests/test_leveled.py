"""Level-synchronous placement engine (ops/leveled.py): C++ pack parity
with the numpy fallback, placement invariants, policy behaviors.

Mirrors the reference's placement semantics tests in spirit
(decide_worker locality + rootish spreading, scheduler.py:8550,2135);
the engine itself is validated against host oracles, not the reference.
"""

from __future__ import annotations

import ctypes
import functools
import os

import numpy as np
import pytest

from distributed_tpu.ops.leveled import (
    SMALL_WAVE,
    _pack_numpy,
    _plan_runs,
    pack_graph,
    place_graph_leveled,
    validate_leveled,
)

BW = 100e6


def random_dag(rng, n, max_deps=2):
    durations = rng.uniform(0.01, 1.0, n).astype(np.float32)
    out_bytes = rng.uniform(1e3, 1e7, n).astype(np.float32)
    n_deps = rng.integers(0, max_deps + 1, n)
    n_deps[0] = 0
    dst = np.repeat(np.arange(n), n_deps).astype(np.int32)
    src = (rng.random(len(dst)) * np.maximum(dst, 1)).astype(np.int32)
    return durations, out_bytes, src, dst


def workers(W, threads=2, stopped=()):
    running = np.ones(W, bool)
    for s in stopped:
        running[s] = False
    return (
        np.full(W, threads, np.int32),
        np.zeros(W, np.float32),
        running,
    )


# ------------------------------------------------------------------ pack


def test_pack_native_matches_numpy_fallback():
    rng = np.random.default_rng(1)
    durations, out_bytes, src, dst = random_dag(rng, 3000)
    packed = pack_graph(durations, out_bytes, src, dst, bandwidth=BW)
    lv, perm, heavy, heavy2, dep_total, offsets, L = _pack_numpy(
        durations, out_bytes, src.astype(np.int64), dst.astype(np.int64)
    )
    assert packed.n_levels == L
    np.testing.assert_array_equal(packed.level, lv)
    np.testing.assert_array_equal(packed.perm, perm)
    np.testing.assert_array_equal(packed.offsets, offsets)
    inv = np.empty(3000, np.int32)
    inv[perm] = np.arange(3000)
    hp = heavy[perm]
    np.testing.assert_array_equal(
        packed.heavy_s, np.where(hp >= 0, inv[np.maximum(hp, 0)], -1)
    )
    h2p = heavy2[perm]
    np.testing.assert_array_equal(
        packed.heavy2_s, np.where(h2p >= 0, inv[np.maximum(h2p, 0)], -1)
    )
    indeg = np.zeros(3000, np.float32)
    np.add.at(indeg, dst[(src != dst)], 1.0)
    np.testing.assert_allclose(
        packed.xfer_all_s,
        dep_total[perm] / BW + 0.001 * indeg[perm],
        rtol=1e-5, atol=1e-7,
    )
    np.testing.assert_array_equal(packed.duration_s, durations[perm])
    # latency=0 strips the per-dependency round-trip term
    packed0 = pack_graph(durations, out_bytes, src, dst, bandwidth=BW,
                         latency=0.0)
    np.testing.assert_allclose(
        packed0.xfer_all_s, dep_total[perm] / BW, rtol=1e-5
    )


def test_pack_levels_are_topological():
    rng = np.random.default_rng(2)
    _, _, src, dst = random_dag(rng, 2000)
    packed = pack_graph(*random_dag(np.random.default_rng(2), 2000))
    lv = packed.level
    assert (lv[dst] > lv[src]).all()
    # level 0 == tasks with no deps
    has_dep = np.zeros(2000, bool)
    has_dep[dst] = True
    np.testing.assert_array_equal(lv == 0, ~has_dep)


def test_pack_cycle_detected():
    durations = np.ones(3, np.float32)
    out_bytes = np.ones(3, np.float32)
    src = np.asarray([0, 1, 2], np.int32)
    dst = np.asarray([1, 2, 0], np.int32)
    with pytest.raises(ValueError, match="cycle"):
        pack_graph(durations, out_bytes, src, dst)
    with pytest.raises(ValueError, match="cycle"):
        _pack_numpy(durations, out_bytes, src.astype(np.int64),
                    dst.astype(np.int64))


def test_pack_empty_and_single():
    p = pack_graph(np.ones(1, np.float32), np.ones(1, np.float32),
                   np.zeros(0, np.int32), np.zeros(0, np.int32))
    assert p.n_levels == 1
    assert p.offsets.tolist() == [0, 1]


# ------------------------------------------- threaded topology pass


def relabel(rng, graph):
    """Tasks renamed by a random permutation, edges ordered by consumer,
    as a scheduler's key order leaves them: every access by task id of
    the topology pass lands somewhere else."""
    durations, out_bytes, src, dst = graph
    label = rng.permutation(len(durations)).astype(np.int32)
    src, dst = label[src], label[dst]
    order = np.argsort(dst, kind="stable")
    return durations, out_bytes, src[order], dst[order]


def messy_dag(rng, n):
    """A random DAG whose edge list also holds self-loops, endpoints out
    of range and duplicate edges, in shuffled order."""
    durations, out_bytes, src, dst = random_dag(rng, n)
    k = 5000
    loops = rng.integers(0, n, k).astype(np.int32)
    dup = rng.integers(0, len(src), k)
    src = np.concatenate([src, loops, np.full(k, -1, np.int32),
                          np.full(k, n + 5, np.int32), src[dup],
                          rng.integers(0, n, k).astype(np.int32)])
    dst = np.concatenate([dst, loops, rng.integers(0, n, k).astype(np.int32),
                          rng.integers(0, n, k).astype(np.int32), dst[dup],
                          np.full(k, -7, np.int32)])
    order = rng.permutation(len(src))
    return durations, out_bytes, src[order], dst[order]


def topo_graph(name):
    """The parity cases, each run at explicit thread counts (1 is the
    serial pass).  Wide levels (>= 16,384 tasks) take the pool's split
    peel, narrow ones the calling thread's; the chain has more levels
    than per-thread level histograms may cost, so it sorts on one
    range."""
    rng = np.random.default_rng(11)
    if name == "random":
        return relabel(rng, random_dag(rng, 300_000))
    if name == "stencil":
        return stencil_dag(rng, steps=15, chunks=20_000)
    if name == "chains":
        n = 300_000  # 150 chains of 2,000: 2,000 levels of 150 tasks
        src = np.arange(n - 150, dtype=np.int32)
        graph = (rng.uniform(0.01, 1, n).astype(np.float32),
                 rng.uniform(1e3, 1e7, n).astype(np.float32), src, src + 150)
        return relabel(rng, graph)
    if name == "chain":
        n = 20_000
        src = np.arange(n - 1, dtype=np.int32)
        graph = (np.ones(n, np.float32),
                 rng.uniform(1e3, 1e7, n).astype(np.float32), src, src + 1)
        return relabel(rng, graph)
    if name == "messy":
        return messy_dag(rng, 300_000)
    if name == "cycle":
        durations, out_bytes, src, dst = relabel(rng, random_dag(rng, 300_000))
        i = int(np.argmax(dst))  # some edge s -> d; add d -> ... -> s
        return (durations, out_bytes, np.append(src, [dst[i]]),
                np.append(dst, [src[i]]))
    raise ValueError(name)


def native_topo(graph, threads):
    """``graphpack_topo`` on ``threads`` host threads (1: the serial
    pass): ``(n_levels, outputs)``."""
    from distributed_tpu import native

    lib = native.load()
    if lib is None:
        pytest.skip("native library unavailable")
    durations, out_bytes, src, dst = graph
    T = len(durations)
    out = {k: np.empty(T, np.int32) for k in
           ("level", "perm", "heavy", "heavy2", "indeg", "inv")}
    out["offsets"] = np.empty(T + 1, np.int32)
    out["dep_total"] = np.empty(T, np.float32)
    i32p = ctypes.POINTER(ctypes.c_int32)
    f32p = ctypes.POINTER(ctypes.c_float)

    def ptr(a):
        return a.ctypes.data_as(f32p if a.dtype == np.float32 else i32p)

    n_levels = lib.graphpack_topo(
        T, len(src), ptr(out_bytes), ptr(src), ptr(dst),
        ptr(out["level"]), ptr(out["perm"]), ptr(out["offsets"]),
        ptr(out["heavy"]), ptr(out["heavy2"]), ptr(out["dep_total"]),
        ptr(out["indeg"]), ptr(out["inv"]), threads,
    )
    out["offsets"] = out["offsets"][: n_levels + 1]
    return n_levels, out


@functools.lru_cache(maxsize=None)
def topo_reference(name):
    """``_pack_numpy``'s outputs, plus the per-task reductions it does
    not return: indegree, the inverse permutation, and ``dep_total``
    summed in float32 in edge order (``np.add.at`` is unbuffered)."""
    durations, out_bytes, src, dst = graph = topo_graph(name)
    T = len(durations)
    level, perm, heavy, heavy2, _, offsets, L = _pack_numpy(
        durations, out_bytes, src.astype(np.int64), dst.astype(np.int64)
    )
    keep = (src != dst) & (src >= 0) & (src < T) & (dst >= 0) & (dst < T)
    dep_total = np.zeros(T, np.float32)
    np.add.at(dep_total, dst[keep], out_bytes[src[keep]])
    inv = np.empty(T, np.int32)
    inv[perm] = np.arange(T, dtype=np.int32)
    return graph, L, dict(
        level=level, perm=perm, offsets=offsets, heavy=heavy, heavy2=heavy2,
        dep_total=dep_total, indeg=np.bincount(dst[keep], minlength=T),
        inv=inv,
    )


@pytest.mark.parametrize("threads", [1, 2, 3, 8, 32])
@pytest.mark.parametrize("name",
                         ["random", "stencil", "chains", "chain", "messy"])
def test_threaded_topo_is_bit_identical(name, threads):
    """Every output of the topology pass, at every thread count (32 is
    more threads than most hosts have cores), equals the numpy fallback
    bit for bit (``dep_total`` as float32 bits)."""
    graph, L, ref = topo_reference(name)
    n_levels, out = native_topo(graph, threads)
    assert n_levels == L
    for key, want in ref.items():
        got = out[key]
        if key == "dep_total":
            got, want = got.view(np.int32), want.view(np.int32)
        np.testing.assert_array_equal(got, want, err_msg=key)


@pytest.mark.parametrize("threads", [1, 2, 3, 8])
def test_threaded_topo_reports_a_cycle(threads):
    assert native_topo(topo_graph("cycle"), threads)[0] == -1


def test_streamed_topo_threads_engage_above_the_gate():
    """A graph past the host's caches (2^21 tasks) runs the pass on a
    pool; a small one keeps the serial pass.  Both place exactly as the
    unstreamed engine does."""
    from distributed_tpu import native
    from distributed_tpu.ops.leveled import place_graph_streamed

    lib = native.load()
    if lib is None:
        pytest.skip("native library unavailable")
    fleet = workers(16)
    for n, engaged in ((1 << 21, True), (20_000, False)):
        if engaged and len(os.sched_getaffinity(0)) < 2:
            continue  # one core: the pass stays serial
        graph = random_dag(np.random.default_rng(12), n)
        tm: dict = {}
        _, streamed = place_graph_streamed(*graph, *fleet, bandwidth=BW,
                                           min_stream=1, timings=tm)
        assert (tm["topo_threads"] > 1) == engaged, (n, tm["topo_threads"])
        whole = place_graph_leveled(pack_graph(*graph, bandwidth=BW), *fleet)
        for field in ("assignment", "choice"):
            np.testing.assert_array_equal(getattr(streamed, field),
                                          getattr(whole, field),
                                          err_msg=(n, field))


def test_plan_runs_fuses_small_waves():
    # 5 small waves then one big one then 2 small: each fused run is as
    # wide as its widest wave's bucket (512 here), not SMALL_WAVE
    offsets = np.cumsum([0, 10, 20, 30, 40, 50, SMALL_WAVE * 3, 10, 10])
    runs = _plan_runs(offsets.astype(np.int32))
    assert len(runs) == 3
    assert runs[0] == (512, [0, 1, 2, 3, 4])
    assert runs[1][1] == [5]
    assert runs[1][0] > SMALL_WAVE
    assert runs[2] == (512, [6, 7])
    # a fused run that mixes buckets takes the widest; the run
    # boundaries stay where the SMALL_WAVE grouping puts them
    sizes = [600, 9000, SMALL_WAVE * 3, 600, 3000, SMALL_WAVE * 3, 600]
    runs = _plan_runs(np.cumsum([0, *sizes]).astype(np.int32))
    assert [ws for _, ws in runs] == [[0, 1], [2], [3, 4], [5], [6]]
    assert [F for F, _ in runs] == [
        SMALL_WAVE, SMALL_WAVE * 4, 4096, SMALL_WAVE * 4, 1024
    ]


# ------------------------------------------------------------- placement


def test_chain_stays_local():
    n = 50
    durations = np.ones(n, np.float32)
    out_bytes = np.full(n, 1e6, np.float32)
    src = np.arange(n - 1, dtype=np.int32)
    dst = src + 1
    packed = pack_graph(durations, out_bytes, src, dst, bandwidth=BW)
    res = place_graph_leveled(packed, *workers(4))
    validate_leveled(packed, res, src, dst, workers(4)[2])
    assert res.n_waves == n
    assert len(np.unique(res.assignment)) == 1  # heavy-dep following


def test_mapreduce_spreads_roots_and_pins_reducers():
    width, reducers = 64, 8
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
    src = np.asarray(src, np.int32)
    dst = np.asarray(dst, np.int32)
    packed = pack_graph(durations, out_bytes, src, dst, bandwidth=BW)
    nthreads, occ, running = workers(8)
    res = place_graph_leveled(packed, nthreads, occ, running)
    validate_leveled(packed, res, src, dst, running)
    assert res.n_waves == 3
    a = res.assignment
    counts = np.bincount(a[:width], minlength=8)
    assert counts.max() <= 2 * counts.min() + 2, counts
    # each reducer lands with one of its feeders (locality)
    for r in range(reducers):
        feeders = set(a[r * per:(r + 1) * per])
        assert a[width + r] in feeders


def test_stopped_workers_get_nothing():
    rng = np.random.default_rng(3)
    durations, out_bytes, src, dst = random_dag(rng, 800)
    packed = pack_graph(durations, out_bytes, src, dst, bandwidth=BW)
    nthreads, occ, running = workers(8, stopped=(2, 5))
    res = place_graph_leveled(packed, nthreads, occ, running)
    validate_leveled(packed, res, src, dst, running)
    counts = np.bincount(res.assignment, minlength=8)
    assert counts[2] == 0 and counts[5] == 0


def test_random_dag_invariants_and_start_times():
    rng = np.random.default_rng(4)
    durations, out_bytes, src, dst = random_dag(rng, 5000)
    packed = pack_graph(durations, out_bytes, src, dst, bandwidth=BW)
    nthreads, occ, running = workers(16)
    res = place_graph_leveled(packed, nthreads, occ, running)
    validate_leveled(packed, res, src, dst, running)
    # modeled start times respect dependency order
    assert (res.start_time[dst] >= res.start_time[src]).all()
    counts = np.bincount(res.assignment, minlength=16)
    assert counts.max() / counts.mean() < 2.0


def test_initial_occupancy_biases_spread():
    # all workers idle except worker 0 which is very busy: the spread
    # choice must put almost nothing new on worker 0
    n = 1000
    durations = np.ones(n, np.float32)
    out_bytes = np.zeros(n, np.float32)
    src = np.zeros(0, np.int32)
    dst = np.zeros(0, np.int32)
    packed = pack_graph(durations, out_bytes, src, dst, bandwidth=BW)
    nthreads = np.full(4, 2, np.int32)
    occ0 = np.asarray([1e6, 0, 0, 0], np.float32)
    running = np.ones(4, bool)
    res = place_graph_leveled(packed, nthreads, occ0, running)
    counts = np.bincount(res.assignment, minlength=4)
    assert counts[0] <= counts[1:].min()


def test_wide_graph_exercises_fused_and_big_waves():
    # two levels: one tiny (fused path), one far above SMALL_WAVE (big path)
    n_roots = 4
    n_leaves = SMALL_WAVE * 2 + 17
    n = n_roots + n_leaves
    durations = np.ones(n, np.float32)
    out_bytes = np.full(n, 1e3, np.float32)
    dst = np.arange(n_roots, n, dtype=np.int32)
    src = (dst % n_roots).astype(np.int32)
    packed = pack_graph(durations, out_bytes, src, dst, bandwidth=BW)
    nthreads, occ, running = workers(8)
    res = place_graph_leveled(packed, nthreads, occ, running)
    validate_leveled(packed, res, src, dst, running)
    assert res.n_waves == 2
    counts = np.bincount(res.assignment, minlength=8)
    assert counts.max() / counts.mean() < 1.5


# ------------------------------------------------------- fused run width


def stencil_dag(rng, steps, chunks):
    """``map_overlap`` time-stepping: chunk i of step s reads chunks
    i-1..i+1 of step s-1, so every wave is ``chunks`` tasks wide."""
    n = steps * chunks
    durations = rng.uniform(0.05, 0.15, n).astype(np.float32)
    out_bytes = np.full(n, 8e6, np.float32)
    consumer = np.arange(chunks, n)
    src, dst = [], []
    for d in (-1, 0, 1):
        keep = (consumer % chunks + d >= 0) & (consumer % chunks + d < chunks)
        src.append(consumer[keep] - chunks + d)
        dst.append(consumer[keep])
    return (durations, out_bytes, np.concatenate(src).astype(np.int32),
            np.concatenate(dst).astype(np.int32))


@pytest.mark.parametrize("shape", ["stencil", "random"])
def test_fused_run_width_does_not_change_the_placement(monkeypatch, shape):
    """Padding lanes are masked and add exact zeros, so a fused run built
    at its widest wave's bucket places exactly as at SMALL_WAVE."""
    from distributed_tpu.ops import leveled

    rng = np.random.default_rng(6)
    if shape == "stencil":
        graph = stencil_dag(rng, steps=40, chunks=500)
    else:
        graph = random_dag(rng, 5000)
    packed = pack_graph(*graph, bandwidth=BW)
    fleet = workers(16)
    planned = leveled._plan_runs(packed.offsets)
    assert any(F < SMALL_WAVE and len(ws) > 1 for F, ws in planned)
    narrow = place_graph_leveled(packed, *fleet)

    def at_small_wave(offsets):
        return [(max(F, SMALL_WAVE), ws) for F, ws in planned]

    monkeypatch.setattr(leveled, "_plan_runs", at_small_wave)
    wide = place_graph_leveled(packed, *fleet)
    validate_leveled(packed, narrow, graph[2], graph[3], fleet[2])
    for field in ("assignment", "choice", "occupancy", "start_time"):
        np.testing.assert_array_equal(getattr(narrow, field),
                                      getattr(wide, field), err_msg=field)


def test_streamed_counts_wave_and_padded_rows():
    """40 waves of 500 tasks run as one fused run 512 wide."""
    from distributed_tpu.ops.leveled import place_graph_streamed

    graph = stencil_dag(np.random.default_rng(7), steps=40, chunks=500)
    tm: dict = {}
    place_graph_streamed(*graph, *workers(16), bandwidth=BW, min_stream=1,
                         timings=tm)
    assert tm["wave_rows"] == 20_000
    assert tm["padded_rows"] == 40 * 512

"""Streamed pack+place driver (ops/leveled.place_graph_streamed): the
pipelined fill/upload/dispatch path must produce the same placements as
the one-shot driver, and the opt-in compact 11 B/task wire format must keep
placement validity and load quality.

Role model: the reference keeps its scheduler decisions identical under
transport changes (distributed/tests/test_scheduler.py spirit); here the
wire format and upload pipelining are the "transport" of the placement
co-processor.
"""

from __future__ import annotations

import numpy as np
import pytest

from distributed_tpu.ops.leveled import (
    _COST_XMIN,
    _dec_cost,
    _enc_cost,
    _enc_heavy_pair,
    pack_graph,
    place_graph_leveled,
    place_graph_streamed,
    validate_leveled,
)
from distributed_tpu import native

from test_leveled import BW, random_dag, workers


needs_native = pytest.mark.skipif(
    native.load() is None, reason="native toolchain unavailable"
)


# ------------------------------------------------------------ wire format


def test_cost_codec_roundtrip():
    x = np.array(
        [0.0, 1e-7, 1e-6, 1e-4, 3.1e-3, 0.9, 80.0, 9e3, 5e4], np.float32
    )
    dec = np.asarray(_dec_cost(_enc_cost(x)))
    # exact zero survives exactly
    assert dec[0] == 0.0
    # sub-XMIN positives clamp to the smallest nonzero code
    assert dec[1] == pytest.approx(_COST_XMIN, rel=1e-3)
    # in-range values round-trip within the quantization step, including
    # the ~80 s transfers of multi-GB deps (an earlier XMAX=60 saturated
    # exactly those and erased their co-location advantage)
    np.testing.assert_allclose(dec[2:8], x[2:8], rtol=0.06)
    # saturation at the top of the scale
    assert dec[8] == pytest.approx(1e4, rel=0.06)


def test_heavy_pair_codec_roundtrip():
    import jax.numpy as jnp
    from jax import lax

    rng = np.random.default_rng(3)
    h = rng.integers(-1, 2**21 - 2, 10_000).astype(np.int32)
    h2 = rng.integers(-1, 2**21 - 2, 10_000).astype(np.int32)
    lo, hi = _enc_heavy_pair(h, h2)
    assert lo.dtype == np.int32 and hi.dtype == np.uint16
    v = jnp.asarray(lo)
    hhi = jnp.asarray(hi).astype(jnp.int32)
    dh = np.asarray((v & 0x1FFFFF) - 1)
    dh2 = np.asarray(
        ((lax.shift_right_logical(v, 21) & 0x7FF) | (hhi << 11)) - 1
    )
    np.testing.assert_array_equal(dh, h)
    np.testing.assert_array_equal(dh2, h2)


# ------------------------------------------------------- streamed driver


@needs_native
def test_streamed_exact_parity_with_oneshot():
    """compact=False streams the same arrays the one-shot driver uploads:
    same kernel, same wave order, bit-identical placements."""
    rng = np.random.default_rng(11)
    durations, out_bytes, src, dst = random_dag(rng, 40_000)
    nthreads, occ0, running = workers(16)
    packed0 = pack_graph(durations, out_bytes, src, dst, bandwidth=BW)
    res0 = place_graph_leveled(packed0, nthreads, occ0, running)
    packed1, res1 = place_graph_streamed(
        durations, out_bytes, src, dst, nthreads, occ0, running,
        bandwidth=BW, compact=False, chunk_rows=7_000, min_stream=1,
    )
    assert packed1.n_levels == packed0.n_levels
    np.testing.assert_array_equal(packed1.perm, packed0.perm)
    np.testing.assert_array_equal(packed1.heavy_s, packed0.heavy_s)
    np.testing.assert_allclose(
        packed1.xfer_all_s, packed0.xfer_all_s, rtol=1e-6
    )
    np.testing.assert_array_equal(res1.assignment, res0.assignment)
    np.testing.assert_array_equal(res1.choice, res0.choice)
    np.testing.assert_allclose(res1.occupancy, res0.occupancy, rtol=1e-5)


@needs_native
def test_streamed_compact_valid_and_balanced():
    """The 11 B/task wire format may flip near-tie argmins but must keep
    validity and load quality."""
    rng = np.random.default_rng(12)
    durations, out_bytes, src, dst = random_dag(rng, 60_000)
    nthreads, occ0, running = workers(32)
    packed0 = pack_graph(durations, out_bytes, src, dst, bandwidth=BW)
    res0 = place_graph_leveled(packed0, nthreads, occ0, running)
    packed2, res2 = place_graph_streamed(
        durations, out_bytes, src, dst, nthreads, occ0, running,
        bandwidth=BW, compact=True, chunk_rows=9_000, min_stream=1,
    )
    validate_leveled(packed2, res2, src, dst, running)
    W = len(nthreads)
    c0 = np.bincount(res0.assignment, minlength=W)
    c2 = np.bincount(res2.assignment, minlength=W)
    assert c2.max() / c2.mean() < c0.max() / c0.mean() * 1.15 + 0.05
    # quantization flips only near-ties: the vast majority agrees
    assert (res2.assignment == res0.assignment).mean() > 0.5


@needs_native
def test_streamed_default_wire_is_exact():
    """The default wire is the exact f16 format on every backend, so the
    chunked pack/upload overlap is byte-identical to the unchunked path."""
    rng = np.random.default_rng(21)
    durations, out_bytes, src, dst = random_dag(rng, 20_000)
    nthreads, occ0, running = workers(8)
    packed0 = pack_graph(durations, out_bytes, src, dst, bandwidth=BW)
    res0 = place_graph_leveled(packed0, nthreads, occ0, running)
    tm: dict = {}
    _, res1 = place_graph_streamed(
        durations, out_bytes, src, dst, nthreads, occ0, running,
        bandwidth=BW, chunk_rows=6_000, min_stream=1, timings=tm,
    )
    assert tm["fmt"] == "f16"
    np.testing.assert_array_equal(res1.assignment, res0.assignment)
    np.testing.assert_array_equal(res1.choice, res0.choice)


@needs_native
def test_fused_topo_parity_with_numpy_pack_threaded():
    """The fused (and, above 2^18 edges, two-threaded) native topo pass
    must agree with the pure-numpy oracle on every output the placement
    consumes — including the threaded branch."""
    rng = np.random.default_rng(22)
    T = 140_000
    durations, out_bytes, src, dst = random_dag(rng, T, max_deps=4)
    assert len(src) >= (1 << 18), "graph too small to exercise the threads"
    native_pack = pack_graph(durations, out_bytes, src, dst, bandwidth=BW)

    import distributed_tpu.native as native_mod

    real_load = native_mod.load
    try:
        native_mod.load = lambda: None
        numpy_pack = pack_graph(durations, out_bytes, src, dst, bandwidth=BW)
    finally:
        native_mod.load = real_load
    assert native_pack.n_levels == numpy_pack.n_levels
    np.testing.assert_array_equal(native_pack.level, numpy_pack.level)
    np.testing.assert_array_equal(native_pack.perm, numpy_pack.perm)
    np.testing.assert_array_equal(native_pack.offsets, numpy_pack.offsets)
    np.testing.assert_array_equal(native_pack.heavy_s, numpy_pack.heavy_s)
    np.testing.assert_array_equal(native_pack.heavy2_s, numpy_pack.heavy2_s)
    np.testing.assert_allclose(
        native_pack.xfer_pref_s, numpy_pack.xfer_pref_s, rtol=1e-5
    )
    np.testing.assert_allclose(
        native_pack.xfer_all_s, numpy_pack.xfer_all_s, rtol=1e-5
    )


@needs_native
def test_streamed_respects_stopped_workers():
    rng = np.random.default_rng(13)
    durations, out_bytes, src, dst = random_dag(rng, 30_000)
    nthreads, occ0, running = workers(8, stopped=(2, 5))
    _, res = place_graph_streamed(
        durations, out_bytes, src, dst, nthreads, occ0, running,
        bandwidth=BW, chunk_rows=8_000, min_stream=1,
    )
    assert (res.assignment >= 0).all()
    assert running[res.assignment].all()


@needs_native
def test_streamed_chunk_geometry_edge_cases():
    """Chunk > T, chunk == T, T slightly over a power of two, and a
    last-chunk clamp that re-sends overlap rows."""
    nthreads, occ0, running = workers(4)
    for n, chunk in [(1025, 4096), (2048, 2048), (4099, 1000), (513, 512)]:
        rng = np.random.default_rng(n)
        durations, out_bytes, src, dst = random_dag(rng, n)
        packed0 = pack_graph(durations, out_bytes, src, dst, bandwidth=BW)
        res0 = place_graph_leveled(packed0, nthreads, occ0, running)
        _, res1 = place_graph_streamed(
            durations, out_bytes, src, dst, nthreads, occ0, running,
            bandwidth=BW, compact=False, chunk_rows=chunk, min_stream=1,
        )
        np.testing.assert_array_equal(res1.assignment, res0.assignment)


def test_streamed_fallback_below_threshold():
    """Below min_stream (or without the native lib) the driver delegates
    to pack+place — same results, no thread."""
    rng = np.random.default_rng(14)
    durations, out_bytes, src, dst = random_dag(rng, 2_000)
    nthreads, occ0, running = workers(4)
    packed0 = pack_graph(durations, out_bytes, src, dst, bandwidth=BW)
    res0 = place_graph_leveled(packed0, nthreads, occ0, running)
    _, res1 = place_graph_streamed(
        durations, out_bytes, src, dst, nthreads, occ0, running,
        bandwidth=BW, min_stream=1_000_000,
    )
    np.testing.assert_array_equal(res1.assignment, res0.assignment)


@needs_native
def test_streamed_sharded_1x1_is_identity_refactor():
    """The streamed driver's SHARDED branch on a 1x1 mesh must be
    bit-identical to the single-device streamed driver (compact=False):
    same topo, same waves, same kernel math — the mesh is pure
    transport there (ops/leveled sharded engine; tests/
    test_sharded_engine.py covers multi-device meshes)."""
    from distributed_tpu.ops.partition import make_engine_mesh

    rng = np.random.default_rng(31)
    durations, out_bytes, src, dst = random_dag(rng, 30_000)
    nthreads, occ0, running = workers(16)
    _, res0 = place_graph_streamed(
        durations, out_bytes, src, dst, nthreads, occ0, running,
        bandwidth=BW, compact=False, chunk_rows=7_000, min_stream=1,
    )
    mesh = make_engine_mesh(layout="1x1")
    tm: dict = {}
    _, res1 = place_graph_streamed(
        durations, out_bytes, src, dst, nthreads, occ0, running,
        bandwidth=BW, chunk_rows=7_000, min_stream=1, mesh=mesh,
        timings=tm,
    )
    assert tm["fmt"] == "f16"
    np.testing.assert_array_equal(res1.assignment, res0.assignment)
    np.testing.assert_array_equal(res1.choice, res0.choice)
    np.testing.assert_array_equal(res1.occupancy, res0.occupancy)


@needs_native
def test_streamed_cycle_raises():
    src = np.array([0, 1, 2], np.int32)
    dst = np.array([1, 2, 0], np.int32)
    ones = np.ones(3, np.float32)
    nthreads, occ0, running = workers(2)
    with pytest.raises(ValueError, match="cycle"):
        place_graph_streamed(
            ones, ones, src, dst, nthreads, occ0, running, min_stream=1
        )

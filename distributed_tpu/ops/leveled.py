"""Level-synchronous whole-graph placement — second-generation engine.

The round-1 wavefront kernel (`ops.wavefront`) discovers dependency
wavefronts ON DEVICE: every wave re-scans all T tasks and re-scatters all
E edges to update indegrees, so a 28-level 1M-task graph costs 28 full
O(T+E) sweeps regardless of how many tasks are actually ready.  This
engine removes both costs:

- **Topological levels are precomputed on the host** by an O(T+E) C++
  pass over the host's cores (`native/graphpack.cpp`, ctypes; numpy
  fallback).  Tasks are
  sorted by (level, priority-index), so wave *w* is the contiguous slice
  ``[offsets[w], offsets[w+1])`` of the level-sorted arrays — the device
  never sees a dependency edge and keeps no indegree state.
- **Each wave is a frontier-sized program**: the per-wave step slices its
  own tasks out of the level-sorted device arrays (`lax.dynamic_slice`
  with a power-of-two bucket shape for jit-cache reuse) and runs O(F + W)
  work, not O(T + E).  Consecutive small waves are fused into one
  ``lax.fori_loop`` dispatch (per-dispatch overhead dominates tiny
  waves); a fused run is as wide as its widest wave's bucket, since
  every wave of the run pays all F lanes.  All dispatches are enqueued
  asynchronously back-to-back — one host sync for the whole graph.
- **Transfers are small**: uploads are float16/int32 (16 bytes/task),
  the assignment is cast to int16 on device before download.

Placement policy per wave (same semantics as `ops.wavefront`, mirroring
the reference's decide_worker/worker_objective and rootish co-assignment,
distributed/scheduler.py:8550,3131,2135):

- locality: follow the heaviest dependency's worker iff modeled
  (queue + transfer) cost beats the load-balanced alternative;
- spread: priority-contiguous blocks over least-loaded running workers;
- one Jacobi contention round against the tentative wave load so
  dogpiles on a popular producer spill to the spread choice.
"""

from __future__ import annotations

import ctypes
import functools
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from distributed_tpu.tracing import TraceSpan

INT32_MAX = np.int32(2**31 - 1)

# waves whose pow2 bucket is <= this get fused into one fori dispatch
SMALL_WAVE = 16384


class PackedGraph(NamedTuple):
    """Host-side level-sorted encoding of a task graph.

    All per-task arrays are in (level, original-index) sorted order;
    ``perm[i]`` maps sorted position i back to the original task index.
    """

    perm: np.ndarray        # i32[T] original index of sorted task i
    level: np.ndarray       # i32[T] topological level, original order
    offsets: np.ndarray     # i32[L+1] level l = sorted slice [offsets[l], offsets[l+1])
    n_levels: int
    duration_s: np.ndarray  # f32[T] estimated runtime, sorted order
    heavy_s: np.ndarray     # i32[T] heaviest dep as a SORTED index (-1 none)
    heavy2_s: np.ndarray    # i32[T] 2nd-heaviest dep, SORTED index (-1 none)
    xfer_pref_s: np.ndarray  # f32[T] transfer seconds if co-located w/ heavy dep
    xfer_pref2_s: np.ndarray  # f32[T] ... if co-located w/ 2nd-heaviest dep
    xfer_all_s: np.ndarray   # f32[T] transfer seconds if placed anywhere else

    @property
    def n(self) -> int:
        return len(self.perm)


def _pack_numpy(durations, out_bytes, src, dst):
    """Pure-numpy fallback for graphpack (vectorized Kahn peeling)."""
    T = len(durations)
    # match the native pass: self-loops and out-of-range edges are ignored
    keep = (src != dst) & (src >= 0) & (src < T) & (dst >= 0) & (dst < T)
    if not keep.all():
        src = src[keep]
        dst = dst[keep]
    E = len(src)
    indeg = np.zeros(T, np.int64)
    np.add.at(indeg, dst, 1)
    dep_total = np.zeros(T, np.float64)
    src_bytes = out_bytes[src] if E else np.zeros(0, np.float32)
    np.add.at(dep_total, dst, src_bytes)
    heavy = np.full(T, -1, np.int64)
    heavy2 = np.full(T, -1, np.int64)
    if E:
        order = np.lexsort((src, -src_bytes, dst))
        dsorted = dst[order]
        first = np.ones(E, bool)
        first[1:] = dsorted[1:] != dsorted[:-1]
        heavy[dsorted[first]] = src[order][first]
        second = np.zeros(E, bool)
        second[1:] = first[:-1] & ~first[1:]
        heavy2[dsorted[second]] = src[order][second]

    # CSR adjacency grouped by src so each level touches only the
    # frontier's own out-edges (O(T+E) overall like graphpack.cpp, not
    # O(E*L) as the old np.isin-per-level scan was on deep graphs)
    if E:
        eorder = np.argsort(src, kind="stable")
        dst_csr = dst[eorder]
        out_off = np.zeros(T + 1, np.int64)
        np.add.at(out_off, src + 1, 1)
        np.cumsum(out_off, out=out_off)

    level = np.full(T, -1, np.int32)
    placed = 0
    lvl = 0
    offsets = [0]
    perm_parts = []
    frontier = np.nonzero(indeg == 0)[0]
    while len(frontier):
        level[frontier] = lvl
        perm_parts.append(frontier.astype(np.int32))
        placed += len(frontier)
        offsets.append(placed)
        if E:
            starts = out_off[frontier]
            counts = out_off[frontier + 1] - starts
            total = int(counts.sum())
            if total:
                cum = np.cumsum(counts)
                idx = np.arange(total, dtype=np.int64) + np.repeat(
                    starts - (cum - counts), counts
                )
                targets = dst_csr[idx]
                np.add.at(indeg, targets, -1)
                frontier = np.unique(targets[indeg[targets] == 0])
            else:
                frontier = np.zeros(0, np.int64)
        else:
            frontier = np.zeros(0, np.int64)
        lvl += 1
    if placed != T:
        raise ValueError("graph has a cycle: %d tasks never became ready"
                         % (T - placed))
    perm = np.concatenate(perm_parts) if perm_parts else np.zeros(0, np.int32)
    return level, perm, heavy.astype(np.int32), heavy2.astype(np.int32), \
        dep_total.astype(np.float32), np.asarray(offsets, np.int32), lvl


def pack_graph(
    durations: np.ndarray,
    out_bytes: np.ndarray,
    src: np.ndarray,
    dst: np.ndarray,
    bandwidth: float = 100e6,
    latency: float = 0.001,
) -> PackedGraph:
    """O(T+E) pack: levels + heavy deps + transfer costs, level-sorted.

    ``src[i] -> dst[i]`` means dst depends on src.  Uses the native C++
    pass when available (~10x the numpy fallback at 1M tasks).

    ``latency`` is the per-remote-dependency round-trip cost added to the
    transfer model: without it, tiny-payload graphs look free to scatter
    and the placer shreds producer-consumer locality that the per-fetch
    RPC cost makes expensive in practice.  Co-location with the heavy
    dep saves one latency; any other placement pays one per dependency.
    """
    from distributed_tpu import native

    durations = np.ascontiguousarray(durations, np.float32)
    out_bytes = np.ascontiguousarray(out_bytes, np.float32)
    src = np.ascontiguousarray(src, np.int32)
    dst = np.ascontiguousarray(dst, np.int32)
    T = len(durations)
    E = len(src)

    lib = native.load()
    if lib is not None and T:
        level = np.empty(T, np.int32)
        perm = np.empty(T, np.int32)
        offsets_buf = np.empty(T + 1, np.int32)  # pass writes [0, n_levels]
        dur_s = np.empty(T, np.float32)
        heavy_s = np.empty(T, np.int32)
        heavy2_s = np.empty(T, np.int32)
        xp_s = np.empty(T, np.float32)
        xp2_s = np.empty(T, np.float32)
        xa_s = np.empty(T, np.float32)
        i32p = ctypes.POINTER(ctypes.c_int32)
        f32p = ctypes.POINTER(ctypes.c_float)
        # latency terms are folded in by the C++ pass (indegree is known
        # there); numpy post-passes over 1M-row arrays used to cost more
        # than the pack itself
        n_levels = lib.graphpack_full(
            T, E,
            durations.ctypes.data_as(f32p), out_bytes.ctypes.data_as(f32p),
            src.ctypes.data_as(i32p), dst.ctypes.data_as(i32p),
            1.0 / bandwidth, float(latency),
            level.ctypes.data_as(i32p), perm.ctypes.data_as(i32p),
            offsets_buf.ctypes.data_as(i32p),
            dur_s.ctypes.data_as(f32p), heavy_s.ctypes.data_as(i32p),
            heavy2_s.ctypes.data_as(i32p),
            xp_s.ctypes.data_as(f32p), xp2_s.ctypes.data_as(f32p),
            xa_s.ctypes.data_as(f32p),
        )
        if n_levels < 0:
            raise ValueError("graph has a cycle")
        return PackedGraph(
            perm=perm, level=level,
            offsets=offsets_buf[: n_levels + 1].copy(),
            n_levels=int(n_levels),
            duration_s=dur_s, heavy_s=heavy_s, heavy2_s=heavy2_s,
            xfer_pref_s=xp_s, xfer_pref2_s=xp2_s, xfer_all_s=xa_s,
        )

    indeg = np.zeros(T, np.float32)
    if E:
        np.add.at(indeg, dst[(dst >= 0) & (dst < T)], 1.0)
    level, perm, heavy, heavy2, dep_total, offsets, n_levels = _pack_numpy(
        durations, out_bytes, src, dst
    )
    inv = np.empty(max(T, 1), np.int32)
    inv[perm] = np.arange(T, dtype=np.int32)
    heavy_p = heavy[perm]
    heavy2_p = heavy2[perm]
    heavy_s = np.where(heavy_p >= 0, inv[np.maximum(heavy_p, 0)], -1).astype(np.int32)
    heavy2_s = np.where(heavy2_p >= 0, inv[np.maximum(heavy2_p, 0)], -1).astype(np.int32)
    heavy_bytes = np.where(heavy_p >= 0, out_bytes[np.maximum(heavy_p, 0)], 0.0)
    heavy2_bytes = np.where(heavy2_p >= 0, out_bytes[np.maximum(heavy2_p, 0)], 0.0)
    dep_total_p = dep_total[perm]
    indeg_p = indeg[perm]
    inv_bw = np.float32(1.0 / bandwidth)
    extra = latency * np.maximum(indeg_p - 1.0, 0.0)
    return PackedGraph(
        perm=perm, level=level, offsets=offsets, n_levels=int(n_levels),
        duration_s=durations[perm], heavy_s=heavy_s, heavy2_s=heavy2_s,
        xfer_pref_s=(
            (dep_total_p - heavy_bytes) * inv_bw + extra
        ).astype(np.float32),
        xfer_pref2_s=(
            (dep_total_p - heavy2_bytes) * inv_bw + extra
        ).astype(np.float32),
        xfer_all_s=(
            dep_total_p * inv_bw + latency * indeg_p
        ).astype(np.float32),
    )


# ------------------------------------------------------------- device side


def _bucket(n: int, floor: int = 512) -> int:
    """Next power of two >= n (>= floor) — bounds distinct jit shapes."""
    b = floor
    while b < n:
        b *= 2
    return b


def _argmin3(c0, c1, c2):
    """Elementwise argmin over three cost rows with jnp.argmin's
    first-minimum tie-break — selects replace [3, F] gathers, which cost
    ~7 ns/element on TPU (gathers run on the scalar pipeline)."""
    m01 = jnp.where(c0 <= c1, jnp.int32(0), jnp.int32(1))
    v01 = jnp.minimum(c0, c1)
    return jnp.where(v01 <= c2, m01, jnp.int32(2))


def _sel3(ch, a0, a1, a2):
    return jnp.where(ch == 0, a0, jnp.where(ch == 1, a1, a2))


# ---- compact wire format ("packed" fmt): 11 B/task instead of 16 ----
# Opt-in (``compact=True``): trades exactness of the COST MODEL (not of
# placement validity) for H2D bytes.  Whether the saving pays for the
# host-side encode on an attached chip is not measured yet:
#   - heavy + heavy2 sorted indices, 21 bits each, packed into one i32
#     (low 21 + 11 of heavy2) and a u16 (heavy2's high 10) = 6 B vs 8;
#   - xp/xp2/xa as log-quantized u8 (code 0 = exactly 0; else
#     x = XMIN * e^(KLOG * (code-1)), +-4.5% relative error; values
#     outside [XMIN, XMAX] saturate — the range spans 1 µs to ~2.8 h of
#     transfer time, so saturation only touches degenerate estimates)
#     = 3 B vs 6.
# Durations stay f16: they feed load sums where quantization noise
# accumulates, while costs only feed per-task argmin comparisons.
_COST_XMIN = 1e-6
_COST_XMAX = 1e4
_COST_KLOG = float(np.log(_COST_XMAX / _COST_XMIN) / 254.0)
_PACK_LIMIT = 1 << 21  # max T+1 expressible in 21 bits


def _enc_cost(x: np.ndarray) -> np.ndarray:
    """Host-side u8 log encode; exact zero keeps code 0."""
    c = np.zeros(x.shape, np.uint8)
    nz = x > 0
    if nz.any():
        v = np.rint(
            np.log(np.maximum(x[nz], _COST_XMIN) / _COST_XMIN) / _COST_KLOG
        )
        c[nz] = np.clip(v + 1, 1, 255).astype(np.uint8)
    return c


def _enc_heavy_pair(heavy_s: np.ndarray, heavy2_s: np.ndarray):
    """(i32 low word, u16 high bits) for the packed heavy-index pair."""
    hp = (heavy_s.astype(np.int64) + 1).astype(np.uint32)
    h2p = (heavy2_s.astype(np.int64) + 1).astype(np.uint32)
    lo = (hp | ((h2p & 0x7FF) << 21)).view(np.int32)
    hi = (h2p >> 11).astype(np.uint16)
    return lo, hi


def _dec_cost(c):
    return jnp.where(
        c == 0,
        jnp.float32(0.0),
        _COST_XMIN * jnp.exp(_COST_KLOG * (c.astype(jnp.float32) - 1.0)),
    )


# assign/choices/load/spans are donated: they thread through every dispatch
@functools.partial(
    jax.jit,
    static_argnames=("F", "K", "uniform", "fmt"),
    donate_argnums=(6, 7, 8, 9),
)
def _place_run(
    dur_g,      # f16[Tp] level-sorted durations (device-resident)
    heavy_g,    # i32[Tp] heavy dep as sorted index (fmt="packed": low word
                #   of the bit-packed heavy pair)
    heavy2_g,   # i32[Tp] 2nd-heaviest dep as sorted index (fmt="packed":
                #   u16[Tp] high bits of the packed pair)
    xp_g,       # f16[Tp] transfer cost if co-located with heavy dep
                #   (fmt="packed": u8 log code)
    xp2_g,      # f16[Tp] transfer cost if co-located with 2nd dep
    xa_g,       # f16[Tp] transfer cost otherwise
    assign,     # i32[Tp] worker per sorted task (-1 = not yet placed)
    choices,    # i32[Tp] chosen candidate: 0 heavy, 1 heavy2, 2 spread
    load,       # f32[W] cumulative modeled load (spread-ordering fairness)
    spans,      # f32[Lp] per-wave modeled makespan
    offs,       # i32[K] wave starts (sorted order)
    fs,         # i32[K] true wave sizes (<= F; 0 = padding wave)
    widxs,      # i32[K] wave indices (for spans)
    nthreads,   # i32[W]
    running,    # bool[W]
    occ0,       # f32[W] ambient occupancy at request time
    F: int,     # static bucket size
    K: int,     # static number of fused waves
    uniform: bool = False,  # every worker running, equal occ0 & nthreads
    fmt: str = "f16",       # wire format of the six task arrays
):
    # TPU cost model: elementwise math is free next to 1-D gathers
    # (~7 ns/element, scalar pipeline).  The body therefore gathers from
    # PRECOMBINED per-worker cost tables (one gather per candidate per
    # pass) and uses arithmetic selects instead of take_along_axis —
    # ~10 F-sized gathers per wave where the naive stacked form costs
    # ~25, and 6 on the ``uniform`` fast path (a homogeneous idle fleet,
    # the common whole-graph-planning case, makes the per-worker queue
    # cost a SCALAR: three table gathers vanish from pass 1 and the
    # per-thread correction needs no gather in pass 2).
    W = nthreads.shape[0]
    threads_f = jnp.maximum(nthreads, 1).astype(jnp.float32)
    inv_t = 1.0 / threads_f
    w_run = jnp.maximum((running & (nthreads > 0)).sum(), 1).astype(jnp.int32)
    rank = jnp.arange(F, dtype=jnp.int32)
    INF = jnp.float32(np.inf)
    # per-worker queue-cost table; +inf marks non-running workers so any
    # candidate pointing at one loses every argmin without a mask gather
    ovt0 = jnp.where(running, occ0 * inv_t, INF)
    ovt_c = occ0[0] * inv_t[0]  # uniform-path scalar
    inv_c = inv_t[0]

    def body(k, carry):
        offset = offs[k]
        f = fs[k]

        def run_wave(carry):
            assign, choices, load, spans = carry
            dur = lax.dynamic_slice(dur_g, (offset,), (F,)).astype(jnp.float32)
            if fmt == "packed":
                # decode the compact wire format (see _enc_heavy_pair /
                # _enc_cost): all elementwise VPU work, free next to the
                # wave's gathers
                v = lax.dynamic_slice(heavy_g, (offset,), (F,))
                hhi = lax.dynamic_slice(
                    heavy2_g, (offset,), (F,)
                ).astype(jnp.int32)
                heavy = (v & 0x1FFFFF) - 1
                heavy2 = (
                    (lax.shift_right_logical(v, 21) & 0x7FF) | (hhi << 11)
                ) - 1
                xp = _dec_cost(lax.dynamic_slice(xp_g, (offset,), (F,)))
                xp2 = _dec_cost(lax.dynamic_slice(xp2_g, (offset,), (F,)))
                xa = _dec_cost(lax.dynamic_slice(xa_g, (offset,), (F,)))
            else:
                heavy = lax.dynamic_slice(heavy_g, (offset,), (F,))
                heavy2 = lax.dynamic_slice(heavy2_g, (offset,), (F,))
                xp = lax.dynamic_slice(
                    xp_g, (offset,), (F,)
                ).astype(jnp.float32)
                xp2 = lax.dynamic_slice(
                    xp2_g, (offset,), (F,)
                ).astype(jnp.float32)
                xa = lax.dynamic_slice(
                    xa_g, (offset,), (F,)
                ).astype(jnp.float32)
            valid = rank < f

            # locality candidates: the workers that produced the two
            # heaviest dependencies (join-shaped tasks — tensordot,
            # merge — have two comparable inputs; co-locating with
            # either saves a fetch, mirroring decide_worker's who_has
            # candidate set, reference scheduler.py:8550)
            h = jnp.maximum(heavy, 0)
            pref = jnp.where((heavy >= 0) & valid, assign[h], -1)
            p = jnp.maximum(pref, 0)
            ok1 = pref >= 0
            h2 = jnp.maximum(heavy2, 0)
            pref2 = jnp.where((heavy2 >= 0) & valid, assign[h2], -1)
            p2 = jnp.maximum(pref2, 0)
            ok2 = (pref2 >= 0) & (pref2 != pref)

            # spread choice: priority-contiguous equal blocks over the
            # least-loaded running workers (integer block math — exact)
            order = jnp.argsort(jnp.where(running, load * inv_t, jnp.inf))
            # block division instead of rank * w_run // f: the product
            # overflows int32 once F x W exceeds 2^31 (and int64 is
            # unavailable without the x64 flag)
            block = jnp.maximum((f + w_run - 1) // w_run, 1)
            slot = jnp.clip(rank // block, 0, W - 1)
            spread = order[slot]

            # Waves execute after their predecessors complete, so
            # cross-wave occupancy has drained (the reference's occupancy
            # likewise drops on task completion, scheduler.py:3264):
            # costs use the AMBIENT occupancy plus within-wave
            # contention, while the spread ordering above uses cumulative
            # load for cross-wave fairness.
            if uniform:
                c0 = jnp.where(ok1, xp + ovt_c, INF)
                c1 = jnp.where(ok2, xp2 + ovt_c, INF)
                c2 = xa + ovt_c
            else:
                c0 = jnp.where(ok1, ovt0[p] + xp, INF)
                c1 = jnp.where(ok2, ovt0[p2] + xp2, INF)
                c2 = ovt0[spread] + xa  # spread targets running workers
            choice = _argmin3(c0, c1, c2)
            tent = _sel3(choice, p, p2, spread)
            xfer_t = _sel3(choice, xp, xp2, xa)

            # one Jacobi contention round against the tentative wave
            # load: cost = (occ0 + tl - own_contribution) / threads +
            # xfer, prefolded into s_tab = ovt0 + tl / threads
            tw = jnp.where(valid, dur + xfer_t, 0.0)
            tl = jax.ops.segment_sum(
                tw, jnp.maximum(tent, 0), num_segments=W
            )
            if uniform:
                tli = tl * inv_c
                corr = tw * inv_c
                d0 = jnp.where(
                    ok1,
                    tli[p] - jnp.where(p == tent, corr, 0.0) + xp + ovt_c,
                    INF,
                )
                d1 = jnp.where(
                    ok2,
                    tli[p2] - jnp.where(p2 == tent, corr, 0.0) + xp2 + ovt_c,
                    INF,
                )
                d2 = (
                    tli[spread]
                    - jnp.where(spread == tent, corr, 0.0)
                    + xa + ovt_c
                )
            else:
                s_tab = ovt0 + tl * inv_t
                corr = tw * inv_t[tent]  # own share, only where cand == tent
                d0 = jnp.where(
                    ok1, s_tab[p] - jnp.where(p == tent, corr, 0.0) + xp, INF
                )
                d1 = jnp.where(
                    ok2,
                    s_tab[p2] - jnp.where(p2 == tent, corr, 0.0) + xp2,
                    INF,
                )
                d2 = s_tab[spread] - jnp.where(spread == tent, corr, 0.0) + xa
            choice = _argmin3(d0, d1, d2)
            assign_w = _sel3(choice, p, p2, spread)
            xfer = _sel3(choice, xp, xp2, xa)
            # d2 is always finite (spread is running), so validity alone
            # decides placement — non-running prefs are +inf, never win
            assign_w = jnp.where(valid, assign_w, -1)

            work = jnp.where(assign_w >= 0, dur + xfer, 0.0)
            wave_load = jax.ops.segment_sum(
                work, jnp.maximum(assign_w, 0), num_segments=W
            )
            load = load + wave_load
            span = jnp.where(running, wave_load * inv_t, 0.0).max()
            spans = spans.at[widxs[k]].set(span)
            # padding lanes write -1 into [offset+f, offset+F) — slots
            # of LATER waves, still -1 and overwritten by their own wave
            # (arrays are padded past T so the window never clamps back)
            assign = lax.dynamic_update_slice(assign, assign_w, (offset,))
            choices = lax.dynamic_update_slice(choices, choice, (offset,))
            return assign, choices, load, spans

        if K == 1:
            return run_wave(carry)
        # padding waves (f == 0) skip the whole body: a fused run rounds
        # its wave count up to a power of two and the no-op iterations
        # would otherwise pay full F-sized gathers each
        return lax.cond(f > 0, run_wave, lambda c: c, carry)

    if K == 1:
        return body(0, (assign, choices, load, spans))
    return lax.fori_loop(0, K, body, (assign, choices, load, spans))


@functools.partial(jax.jit, static_argnames=("L", "wide"))
def _shrink_window(assign, choices, start, L: int, wide: bool):
    """Packed (assignment, choice) for rows [start, start+L): the
    segmented-download variant — rows final after run k are fetched
    while later runs still compute, hiding the D2H time behind the
    remaining device work."""
    a = lax.dynamic_slice(assign, (start,), (L,))
    c = lax.dynamic_slice(choices, (start,), (L,))
    out = (a + 1) * 4 + jnp.clip(c, 0, 2)
    return out if wide else out.astype(jnp.int16)


class LeveledResult(NamedTuple):
    assignment: np.ndarray   # i32[T] worker per task, ORIGINAL order
    start_time: np.ndarray   # f32[T] modeled start, original order
    occupancy: np.ndarray    # f32[W] final modeled load
    n_waves: int
    level: np.ndarray        # i32[T] topological level, original order
    choice: np.ndarray       # i8[T] 0=heavy-dep 1=2nd-dep 2=spread, orig order


def _compute_pad(T: int, runs, offsets) -> int:
    """Exact pad: just enough that no dynamic_slice window (real wave at
    its offset, padding wave parked at T) reads past the buffer — a
    worst-case pad (max bucket) would ship up to 8 MB of padding per
    array over the wire at 1M tasks."""
    pad = 16
    for F, waves in runs:
        if _bucket(len(waves), floor=1) > len(waves):
            pad = max(pad, F)  # padding waves use window [T, T+F)
        for w in waves:
            pad = max(pad, int(offsets[w]) + F - T)
    return pad


def _plan_runs(
    offsets: np.ndarray,
    bucket_fn=None,
    small: int = SMALL_WAVE,
) -> list[tuple[int, list[int]]]:
    """Group consecutive waves into fused runs: [(F, [wave,...])].
    Waves whose bucket is <= ``small`` fuse into one run; larger
    consecutive waves with the same power-of-two bucket fuse too — one
    fori_loop dispatch per group instead of one program per wave (the
    separate-program overhead dominates mid-sized waves).  A run's F is
    its widest wave's bucket: every wave of the run pays all F lanes,
    so 2,000 waves of 500 tasks run 512 wide, not ``small`` wide.  The
    sharded planner (:func:`_plan_runs_sharded`) reuses this loop with
    a per-shard ``bucket_fn``."""
    if bucket_fn is None:
        bucket_fn = _bucket
    sizes = np.diff(offsets)
    runs: list[tuple[int, list[int]]] = []
    cur: list[int] = []
    cur_key = cur_f = 0
    for w, f in enumerate(sizes):
        b = bucket_fn(int(f))
        key = small if b <= small else b  # all small waves share a group
        if cur and key == cur_key:
            cur.append(w)
            cur_f = max(cur_f, b)
            continue
        if cur:
            runs.append((cur_f, cur))
        cur, cur_key, cur_f = [w], key, b
    if cur:
        runs.append((cur_f, cur))
    return runs


def place_graph_leveled(
    packed: PackedGraph,
    nthreads,
    occupancy0,
    running,
) -> LeveledResult:
    """Place the whole graph; one host sync total.

    All waves are enqueued asynchronously (the device pipeline overlaps
    uploads with earlier waves); only the final fetch blocks.
    """
    T = packed.n
    L = packed.n_levels
    sizes = np.diff(packed.offsets)
    runs = _plan_runs(packed.offsets)
    Tp = T + _compute_pad(T, runs, packed.offsets)
    Lp = _bucket(L + 1, floor=64)  # +1: scratch slot for padding waves

    def pad_buf(arr, fill, dtype):
        buf = np.empty(Tp, dtype)
        buf[:T] = arr
        buf[T:] = fill
        return buf

    # 16 bytes/task on the wire; ONE device_put call for the whole set
    # (per-call staging overhead is material on a single-core host)
    dur_g, heavy_g, heavy2_g, xp_g, xp2_g, xa_g = jax.device_put((
        pad_buf(packed.duration_s, 0, np.float16),
        pad_buf(packed.heavy_s, 0, np.int32),  # pad 0: safe gather index
        pad_buf(packed.heavy2_s, 0, np.int32),
        pad_buf(packed.xfer_pref_s, 0, np.float16),
        pad_buf(packed.xfer_pref2_s, 0, np.float16),
        pad_buf(packed.xfer_all_s, 0, np.float16),
    ))

    wide, uniform, thr_h, run_h, occ_h = _worker_params(
        nthreads, occupancy0, running
    )
    rs = _RunState(packed, Tp, Lp, wide, uniform,
                   jnp.asarray(thr_h), jnp.asarray(run_h), jnp.asarray(occ_h))
    bufs = (dur_g, heavy_g, heavy2_g, xp_g, xp2_g, xa_g)
    for run_i, (F, waves) in enumerate(runs):
        rs.dispatch(bufs, F, waves, last=run_i == len(runs) - 1)
    return rs.finalize()


def _worker_params(nthreads, occupancy0, running):
    """Host-side worker-fleet parameters shared by both drivers."""
    occ_h = np.asarray(occupancy0, np.float32)
    thr_h = np.asarray(nthreads, np.int32)
    run_h = np.asarray(running, bool)
    W = len(occ_h)
    # i16 download only when every (assign+1)*4+choice code fits
    wide = (W + 1) * 4 + 3 > 32767
    # homogeneous idle fleet: the per-worker queue cost is a scalar and
    # the kernel drops 4 of its ~10 F-sized gathers per wave
    uniform = bool(
        W > 0 and run_h.all() and np.ptp(occ_h) == 0 and np.ptp(thr_h) == 0
    )
    return wide, uniform, thr_h, run_h, occ_h


class _RunState:
    """Shared dispatch/download state machine for the two drivers
    (one-shot ``place_graph_leveled`` and streamed
    ``place_graph_streamed``): runs _place_run per fused wave group and
    fetches segmented downloads behind the remaining device work."""

    def __init__(self, packed: PackedGraph, Tp: int, Lp: int, wide: bool,
                 uniform: bool, nthreads, running, occ0, fmt: str = "f16"):
        self.packed = packed
        self.Tp = Tp
        self.Lp = Lp
        self.wide = wide
        self.uniform = uniform
        self.fmt = fmt
        self.nthreads = nthreads
        self.running = running
        self.occ0 = occ0
        self.sizes = np.diff(packed.offsets)
        self.assign = jnp.full(Tp, -1, jnp.int32)
        self.choices = jnp.full(Tp, 2, jnp.int32)
        # distinct buffer: load is donated, occ0 is not
        self.load = occ0 + 0.0
        self.spans = jnp.zeros(Lp, jnp.float32)
        # segmented downloads: rows [0, end_of_run_k) are FINAL once run
        # k's dispatch completes (later runs only write later rows + pad
        # tail), so fetch them asynchronously while the remaining runs
        # compute — the last segment is the only D2H the host actually
        # waits for.  Window lengths are bucketed (bounded jit shapes);
        # windows overlap backward into already-fetched rows, which the
        # host just rewrites.
        self.segments: list = []  # (start, window, device_array)
        self.seg_from = 0
        self.SEG_MIN = max(packed.n // 4, 4096)

    def dispatch(self, bufs, F: int, waves: list[int], last: bool) -> None:
        packed = self.packed
        K = _bucket(len(waves), floor=1)
        # padding waves (f=0) place nothing, but their update window
        # still writes -1 over [off, off+F) — park it on the pad tail
        offs = np.full(K, packed.n, np.int32)
        fs = np.zeros(K, np.int32)
        widxs = np.full(K, self.Lp - 1, np.int32)  # scratch span slot
        for i, w in enumerate(waves):
            offs[i] = packed.offsets[w]
            fs[i] = self.sizes[w]
            widxs[i] = w
        self.assign, self.choices, self.load, self.spans = _place_run(
            *bufs,
            self.assign, self.choices, self.load, self.spans,
            jnp.asarray(offs), jnp.asarray(fs), jnp.asarray(widxs),
            self.nthreads, self.running, self.occ0,
            F=F, K=K, uniform=self.uniform, fmt=self.fmt,
        )
        self._maybe_segment(int(packed.offsets[waves[-1] + 1]), last)

    def _maybe_segment(self, rows_done: int, last: bool) -> None:
        """Fetch rows final after this dispatch behind the remaining
        device work (shared by the single-device and sharded drivers)."""
        if rows_done - self.seg_from >= self.SEG_MIN or (
            last and rows_done > self.seg_from
        ):
            # window must fit the Tp-sized buffers: the pow2 bucket can
            # overshoot them for graphs a bit over a power of two, so
            # clamp — a window reaching past rows_done only copies rows
            # a LATER (always-overlapping-backward) segment rewrites
            Lw = min(_bucket(rows_done - self.seg_from, floor=4096), self.Tp)
            start = max(rows_done - Lw, 0)
            seg = _shrink_window(
                self.assign, self.choices, jnp.int32(start),
                L=Lw, wide=self.wide,
            )
            try:
                seg.copy_to_host_async()
            except AttributeError:  # pragma: no cover - non-array backend
                pass
            self.segments.append((start, Lw, seg))
            self.seg_from = rows_done

    def finalize(self) -> LeveledResult:
        return _finalize(self.packed, self.segments, self.spans, self.load,
                         self.packed.n, self.packed.n_levels)


@jax.jit
def _apply_chunk(bufs, chunks, start):
    """Land one uploaded chunk into the six device-resident task arrays
    (plain copies, no donation: runs dispatched against earlier buffer
    versions must keep reading them)."""
    return tuple(
        lax.dynamic_update_slice(b, c, (start,)) for b, c in zip(bufs, chunks)
    )


class _Span(TraceSpan):
    """One ``place.<phase>`` span of :func:`place_graph_streamed`: it
    shows on any active profiler trace, and its seconds add to
    ``timings["<phase>_s"]``."""

    __slots__ = ("_timings", "_key", "_t0")

    def __init__(self, name: str, timings: dict | None):
        super().__init__(name)
        self._timings = timings
        self._key = name.split(".", 1)[1] + "_s"

    def __enter__(self) -> "_Span":
        super().__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._timings is not None:
            self._timings[self._key] += time.perf_counter() - self._t0
        super().__exit__(*exc)


#: the ``timings`` keys of ``place_graph_streamed``'s spans
_SPAN_KEYS = ("topo_s", "fill_wait_s", "upload_s", "tiles_s", "dispatch_s",
              "finalize_s")


def _plan_streamed(offsets: np.ndarray, mesh):
    """``(runs, sharded_runs)`` of :func:`place_graph_streamed`: the
    fused runs at their whole width F, and with a mesh the same runs at
    the per-shard width (``None`` without one)."""
    if mesh is None:
        return _plan_runs(offsets), None
    n_shard = _mesh_shards(mesh)[2]
    sharded_runs = _plan_runs_sharded(offsets, n_shard)
    return [(Fl * n_shard, ws) for Fl, ws in sharded_runs], sharded_runs


def _row_counts(runs, offsets: np.ndarray) -> dict:
    """``wave_rows``, the task rows the runs place (every wave is in one
    run), and ``padded_rows``, each run's F times its real waves (the
    padding waves that round K up skip their body and count nothing):
    how full the wave programs run."""
    return {
        "wave_rows": int(offsets[-1]),
        "padded_rows": sum(F * len(ws) for F, ws in runs),
    }


def place_graph_streamed(
    durations,
    out_bytes,
    src,
    dst,
    nthreads,
    occupancy0,
    running,
    bandwidth: float = 100e6,
    latency: float = 0.001,
    compact: bool = False,
    chunk_rows: int = 131072,
    min_stream: int = 262144,
    timings: dict | None = None,
    mesh=None,
    fleet_dev=None,
    stats: dict | None = None,
) -> tuple[PackedGraph, LeveledResult]:
    """Fused pack+place: the H2D wire overlaps the pack AND the compute.

    ``place_graph_leveled`` serializes pack → upload → waves: nothing
    crosses the wire until the whole pack is done, and no wave runs
    until all six arrays have landed.  This driver pipelines all three
    phases:

    - phase 1 (topology: edge passes + Kahn peel + counting sort, on
      ``graphpack_threads(T)`` host threads) overlaps nothing — sorted
      order doesn't exist before it;
    - phase 2 (the per-row fill into level-sorted arrays) runs on a
      worker thread (the C call drops the GIL) in ``chunk_rows`` chunks;
    - the main thread uploads each finished chunk (async ``device_put``
      + a device-side copy into the full buffers) and dispatches every
      fused wave run whose rows have landed — early waves compute while
      later chunks are still crossing the wire, and the segmented D2H
      of ``_RunState`` overlaps the tail as before.

    The default wire is the exact f16 format on every backend, so the
    result is bit-identical to ``place_graph_leveled``.  With
    ``compact=True`` chunks use the 11 B/task wire format (see
    ``_enc_heavy_pair``/``_enc_cost``) instead of 16 B/task — placement
    validity is unaffected (same kernel, same wave order); the cost
    model carries ±4.5% quantization on transfer seconds and saturates
    outside [1 µs, ~2.8 h].

    Falls back to pack+place (same results, no overlap) when the native
    library is unavailable or the graph is under ``min_stream`` tasks.

    With ``mesh`` (an engine mesh from ops/partition.make_engine_mesh)
    the waves dispatch through the SHARDED engine instead: each fused
    run's task tiles are placed with ``NamedSharding`` — per-shard H2D,
    async against both the pack fill and earlier runs' compute — and
    ``fleet_dev``/``stats`` pass through to
    :func:`place_graph_leveled_sharded`.  The sharded wire is always
    the exact f16 format (``compact`` is ignored).

    ``timings``, when given, receives the wire ``fmt`` and the seconds
    of the main thread's spans: ``topo_s`` (phase 1), ``fill_wait_s``
    (waiting on the fill thread), ``upload_s`` (device buffers, chunk
    encode, ``device_put`` + ``_apply_chunk``), ``tiles_s`` (the sharded
    engine's per-shard ``[K, Fl]`` blocks, built on host threads above
    ``_TILE_POOL_LANES`` lanes a run, and their batched ``device_put``;
    0.0 off the mesh, whose host fill arrays are never zero-filled),
    ``dispatch_s`` (enqueueing the wave runs) and ``finalize_s`` (the
    last download and the unpack).
    Each span shows as ``place.<phase>`` on any active profiler trace.
    It also receives the counts ``wave_rows`` and ``padded_rows``
    (:func:`_row_counts`): their ratio is how full the wave programs run;
    ``topo_threads``, the host threads of phase 1 (1: the serial
    pass, which the numpy fallback and graphs under ~2M tasks take);
    and ``tile_threads``, the most host threads any run's tile blocks
    were built on (1: in line, under the gate; 0: off the mesh).

    Returns ``(packed, result)``; ``packed``'s host arrays are fully
    filled by return time.
    """
    from distributed_tpu import native

    durations = np.ascontiguousarray(durations, np.float32)
    out_bytes = np.ascontiguousarray(out_bytes, np.float32)
    src = np.ascontiguousarray(src, np.int32)
    dst = np.ascontiguousarray(dst, np.int32)
    T = len(durations)
    E = len(src)
    if timings is not None:
        timings.update(dict.fromkeys(_SPAN_KEYS, 0.0))

    lib = native.load()
    if lib is None or T < min_stream:
        # fallback path: the whole pack is serial, and place.topo holds
        # it, so callers never read a zero pack phase
        with _Span("place.topo", timings):
            packed = pack_graph(durations, out_bytes, src, dst,
                                bandwidth=bandwidth, latency=latency)
        if timings is not None:
            timings["fmt"] = "f16"
            timings["topo_threads"] = (
                1 if lib is None else lib.graphpack_threads(T)
            )
            timings.update(_row_counts(
                _plan_streamed(packed.offsets, mesh)[0], packed.offsets
            ))
        tile_threads = 0
        if mesh is not None:
            rs = _run_sharded(mesh, packed, nthreads, occupancy0, running,
                              fleet_dev, stats)
            tile_threads = rs.tile_threads
            result = rs.finalize()
        else:
            result = place_graph_leveled(packed, nthreads, occupancy0,
                                         running)
        if timings is not None:
            timings["tile_threads"] = tile_threads
        return packed, result

    # phase 1, the topology pass: nothing overlaps it
    topo_threads = lib.graphpack_threads(T)
    if timings is not None:
        timings["topo_threads"] = topo_threads
    with _Span("place.topo", timings):
        i32p = ctypes.POINTER(ctypes.c_int32)
        f32p = ctypes.POINTER(ctypes.c_float)
        level = np.empty(T, np.int32)
        perm = np.empty(T, np.int32)
        offsets_buf = np.empty(T + 1, np.int32)  # pass writes [0, n_levels]
        heavy = np.empty(T, np.int32)
        heavy2 = np.empty(T, np.int32)
        dep_total = np.empty(T, np.float32)
        indeg = np.empty(T, np.int32)
        inv = np.empty(T, np.int32)
        n_levels = lib.graphpack_topo(
            T, E,
            out_bytes.ctypes.data_as(f32p),
            src.ctypes.data_as(i32p), dst.ctypes.data_as(i32p),
            level.ctypes.data_as(i32p), perm.ctypes.data_as(i32p),
            offsets_buf.ctypes.data_as(i32p),
            heavy.ctypes.data_as(i32p), heavy2.ctypes.data_as(i32p),
            dep_total.ctypes.data_as(f32p), indeg.ctypes.data_as(i32p),
            inv.ctypes.data_as(i32p), topo_threads,
        )
        if n_levels < 0:
            raise ValueError("graph has a cycle")
        offsets = offsets_buf[: n_levels + 1].copy()

        runs, sharded_runs = _plan_streamed(offsets, mesh)
        Tp = T + _compute_pad(T, runs, offsets)
        Lp = _bucket(n_levels + 1, floor=64)
        # host fill targets are Tp-sized with a zero tail so chunk windows
        # (fixed length C, clamped into [0, Tp)) always slice cleanly; only
        # the tail needs zeroing — the fill chunks cover every row in [0, T)
        # and np.zeros over six 1M-row arrays costs real milliseconds of the
        # serial phase on a one-core host.  The SHARDED dispatch zeroes
        # nothing here: its tiles read only the rows of their own waves
        # (``_shard_block``), all filled before the run dispatches.
        dur_s = np.empty(Tp, np.float32)
        heavy_s = np.empty(Tp, np.int32)
        heavy2_s = np.empty(Tp, np.int32)
        xp_s = np.empty(Tp, np.float32)
        xp2_s = np.empty(Tp, np.float32)
        xa_s = np.empty(Tp, np.float32)
        if mesh is None:
            for _buf in (dur_s, heavy_s, heavy2_s, xp_s, xp2_s, xa_s):
                _buf[T:] = 0
        packed = PackedGraph(
            perm=perm, level=level, offsets=offsets, n_levels=int(n_levels),
            duration_s=dur_s[:T], heavy_s=heavy_s[:T], heavy2_s=heavy2_s[:T],
            xfer_pref_s=xp_s[:T], xfer_pref2_s=xp2_s[:T], xfer_all_s=xa_s[:T],
        )

    wide, uniform, thr_h, run_h, occ_h = _worker_params(
        nthreads, occupancy0, running
    )
    if mesh is not None:
        compact = False  # sharded wire is always the exact f16 format
    fmt = "packed" if (compact and Tp < _PACK_LIMIT) else "f16"
    if timings is not None:
        timings["fmt"] = fmt
        timings.update(_row_counts(runs, offsets))

    C = min(chunk_rows, T)
    with _Span("place.upload", timings):
        if mesh is not None:
            bufs = None  # sharded runs ship per-run tiles, no chunk buffers
        elif fmt == "packed":
            bufs = (
                jnp.zeros(Tp, jnp.float16), jnp.zeros(Tp, jnp.int32),
                jnp.zeros(Tp, jnp.uint16), jnp.zeros(Tp, jnp.uint8),
                jnp.zeros(Tp, jnp.uint8), jnp.zeros(Tp, jnp.uint8),
            )
        else:
            bufs = (
                jnp.zeros(Tp, jnp.float16), jnp.zeros(Tp, jnp.int32),
                jnp.zeros(Tp, jnp.int32), jnp.zeros(Tp, jnp.float16),
                jnp.zeros(Tp, jnp.float16), jnp.zeros(Tp, jnp.float16),
            )

    boundaries = [(i0, min(i0 + C, T)) for i0 in range(0, T, C)]
    done = [threading.Event() for _ in boundaries]
    fill_err: list[BaseException] = []

    def filler():
        try:
            for (i0, i1), evt in zip(boundaries, done):
                lib.graphpack_fill(
                    i0, i1,
                    durations.ctypes.data_as(f32p),
                    out_bytes.ctypes.data_as(f32p),
                    perm.ctypes.data_as(i32p), inv.ctypes.data_as(i32p),
                    heavy.ctypes.data_as(i32p), heavy2.ctypes.data_as(i32p),
                    dep_total.ctypes.data_as(f32p),
                    indeg.ctypes.data_as(i32p),
                    1.0 / bandwidth, float(latency),
                    dur_s.ctypes.data_as(f32p),
                    heavy_s.ctypes.data_as(i32p),
                    heavy2_s.ctypes.data_as(i32p),
                    xp_s.ctypes.data_as(f32p), xp2_s.ctypes.data_as(f32p),
                    xa_s.ctypes.data_as(f32p),
                )
                evt.set()
        except BaseException as exc:  # pragma: no cover - defensive
            fill_err.append(exc)
            for evt in done:
                evt.set()

    th = threading.Thread(target=filler, name="graphpack-fill", daemon=True)
    th.start()

    with _Span("place.upload", timings):
        if mesh is not None:
            rs: _RunState = _ShardedRunState(
                mesh, packed, Tp, Lp, wide, uniform, thr_h, run_h, occ_h,
                fleet_dev=fleet_dev, stats=stats,
            )
            host_fill = (dur_s, heavy_s, heavy2_s, xp_s, xp2_s, xa_s)
        else:
            rs = _RunState(packed, Tp, Lp, wide, uniform,
                           jnp.asarray(thr_h), jnp.asarray(run_h),
                           jnp.asarray(occ_h), fmt=fmt)
    run_i = 0
    for (i0, i1), evt in zip(boundaries, done):
        with _Span("place.fill_wait", timings):
            evt.wait()
        if fill_err:
            raise RuntimeError("graph pack fill failed") from fill_err[0]
        if mesh is None:
            with _Span("place.upload", timings):
                # fixed-length window clamped into the buffers: the last
                # chunk re-sends a few already-final rows instead of
                # changing shape (one compiled _apply_chunk per length)
                start = min(i0, Tp - C)
                sl = slice(start, start + C)
                if fmt == "packed":
                    lo, hi = _enc_heavy_pair(heavy_s[sl], heavy2_s[sl])
                    host = (
                        dur_s[sl].astype(np.float16), lo, hi,
                        _enc_cost(xp_s[sl]), _enc_cost(xp2_s[sl]),
                        _enc_cost(xa_s[sl]),
                    )
                else:
                    host = (
                        dur_s[sl].astype(np.float16),
                        heavy_s[sl], heavy2_s[sl],
                        xp_s[sl].astype(np.float16),
                        xp2_s[sl].astype(np.float16),
                        xa_s[sl].astype(np.float16),
                    )
                bufs = _apply_chunk(bufs, jax.device_put(host),
                                    jnp.int32(start))
        # dispatch every fused run whose rows have fully landed; on one
        # device its windows may read a few rows past i1 — still the
        # zero fill, masked by the wave's validity lanes
        while (
            run_i < len(runs)
            and int(offsets[runs[run_i][1][-1] + 1]) <= i1
        ):
            last = run_i == len(runs) - 1
            if mesh is not None:
                # sharded: build each shard's [K, Fl] blocks from the
                # host fill arrays and ship each to its device — the
                # async per-shard H2D overlaps both the pack fill and
                # the earlier runs' compute
                Fl, waves = sharded_runs[run_i]
                with _Span("place.tiles", timings):
                    tiles = rs.tiles(host_fill, Fl, waves)
                with _Span("place.dispatch", timings):
                    rs.dispatch(tiles, Fl, waves, last=last)
            else:
                with _Span("place.dispatch", timings):
                    F, waves = runs[run_i]
                    rs.dispatch(bufs, F, waves, last=last)
            run_i += 1
    with _Span("place.finalize", timings):
        th.join()
        assert run_i == len(runs), "not all runs dispatched"
        if mesh is not None:
            rs.record_shard_ms()
        result = rs.finalize()
    if timings is not None:
        timings["tile_threads"] = rs.tile_threads if mesh is not None else 0
    return packed, result


def _finalize(packed, segments, spans, load, T: int, L: int) -> LeveledResult:
    """Assemble the host-side result from the downloaded segments."""
    packed_h = np.empty(max(T, 1), np.int32)
    for start, Lw, seg in segments:
        end = min(start + Lw, T)
        packed_h[start:end] = np.asarray(seg)[: end - start]
    packed_h = packed_h[:T]
    spans_h = np.asarray(spans)[:L]
    load_h = np.asarray(load)

    assignment = np.full(T, -1, np.int32)
    choice = np.full(T, 2, np.int8)
    from distributed_tpu import native

    lib = native.load_nowait() or native.load()
    if lib is not None and T:
        # one C sweep instead of four numpy passes + two fancy scatters
        i32p = ctypes.POINTER(ctypes.c_int32)
        lib.unpack_assignment(
            T,
            np.ascontiguousarray(packed_h).ctypes.data_as(i32p),
            np.ascontiguousarray(packed.perm).ctypes.data_as(i32p),
            assignment.ctypes.data_as(i32p),
            choice.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
        )
    elif T:
        assign_h = packed_h // 4 - 1
        choice_h = (packed_h % 4).astype(np.int8)
        assignment[packed.perm] = assign_h
        choice[packed.perm] = choice_h
    wave_start = np.concatenate([[0.0], np.cumsum(spans_h)[:-1]]).astype(np.float32)
    start_time = wave_start[np.maximum(packed.level, 0)] if L else np.zeros(T, np.float32)
    return LeveledResult(
        assignment=assignment,
        start_time=start_time,
        occupancy=load_h,
        n_waves=L,
        level=packed.level,
        choice=choice,
    )


def validate_leveled(
    packed: PackedGraph,
    result: LeveledResult,
    src: np.ndarray,
    dst: np.ndarray,
    running: np.ndarray,
) -> None:
    """Host oracle: every task placed on a running worker; every consumer
    in a strictly later level than each of its producers."""
    a = result.assignment
    assert (a >= 0).all(), "unplaced tasks"
    assert running[a].all(), "task on non-running worker"
    lv = result.level
    real = src != dst
    assert (lv[dst[real]] > lv[src[real]]).all(), "level order violated"


# ----------------------------------------------------- sharded engine
#
# The same level-synchronous placement, partitioned over a
# ``jax.sharding.Mesh`` (ops/partition.make_engine_mesh: 2-D
# ``(tasks, workers)``): every wave's task slice is split CONTIGUOUSLY
# over the flattened device order (device d of D owns window rows
# ``[d*Fl, (d+1)*Fl)``), the fleet SoA rows shard over the ``workers``
# axis (the mirror's slot->shard mapping, scheduler/mirror.py), and the
# per-wave combine is tiled ``all_gather``s: the assignment and choice
# slices (the next wave's locality gathers see the full picture) and
# the per-task load terms of both contention sums.  Every shard sums
# the gathered [F] terms in task order, exactly as ``_place_run`` does,
# so on ANY mesh the kernel computes the same floating-point
# expressions in the same order as the single-device engine and places
# identically (a psum of per-shard partial sums re-associated them:
# agreement 0.628 at 10M tasks / 4096 workers on a 2x2 v5e, PR 21).


def _mesh_shards(mesh):
    """(axis names, per-axis sizes, total shard count) of an engine mesh."""
    names = tuple(mesh.axis_names)
    sizes = tuple(int(mesh.shape[a]) for a in names)
    D = 1
    for s in sizes:
        D *= s
    return names, sizes, D


def _plan_runs_sharded(offsets: np.ndarray, n_shards: int):
    """Sharded analogue of :func:`_plan_runs` (the same grouping loop):
    fused runs ``[(Fl, [wave, ...])]`` where ``Fl`` is the PER-SHARD
    pow2 bucket of the wave size (ops/partition.shard_bucket) — one
    fused dispatch per group, every shard's slice a static
    ``Fl``-length window."""
    from distributed_tpu.ops.partition import shard_bucket

    return _plan_runs(
        offsets,
        bucket_fn=lambda f: shard_bucket(f, n_shards, floor=512),
        small=max(SMALL_WAVE // max(n_shards, 1), 2048),
    )


@functools.lru_cache(maxsize=None)
def _sharded_run_fn(mesh, Fl: int, K: int, W: int, uniform: bool,
                    fleet_sharded: bool):
    """Build (and cache) the jitted shard_map program for one fused run
    shape class.  Mirrors ``_place_run``'s per-wave body (both the
    uniform fast path and the general path) on per-shard slices; see the
    module-tail comment for the collective structure."""
    from jax.sharding import PartitionSpec as P

    names, sizes, D = _mesh_shards(mesh)

    # named as the device trace shows it: ``jit__place_run_sharded``
    def _place_run_sharded(dur_g, heavy_g, heavy2_g, xp_g, xp2_g, xa_g,
                           assign, choices, load, spans, offs, fs, widxs,
                           nthreads, running, occ0):
        # task arrays: [K, Fl] local tiles; assign/choices/load/spans
        # replicated; fleet arrays are "workers"-axis shards when the
        # mirror feeds the kernel, else full replicated [W]
        if fleet_sharded:
            nthreads = lax.all_gather(nthreads, "workers", tiled=True)
            running = lax.all_gather(running, "workers", tiled=True)
            occ0 = lax.all_gather(occ0, "workers", tiled=True)
        threads_f = jnp.maximum(nthreads, 1).astype(jnp.float32)
        inv_t = 1.0 / threads_f
        w_run = jnp.maximum(
            (running & (nthreads > 0)).sum(), 1
        ).astype(jnp.int32)
        INF = jnp.float32(np.inf)
        ovt0 = jnp.where(running, occ0 * inv_t, INF)
        ovt_c = occ0[0] * inv_t[0]  # uniform-path scalar
        inv_c = inv_t[0]
        # linear shard index in the flattened (row-major) device order —
        # the order NamedSharding splits the task dimension in
        shard = jnp.int32(0)
        stride = D
        for a, s in zip(names, sizes):
            stride //= s
            shard = shard + lax.axis_index(a).astype(jnp.int32) * stride
        rank = shard * Fl + jnp.arange(Fl, dtype=jnp.int32)

        def body(k, carry):
            offset = offs[k]
            f = fs[k]

            def run_wave(carry):
                assign, choices, load, spans = carry
                dur = lax.dynamic_index_in_dim(
                    dur_g, k, 0, keepdims=False
                ).astype(jnp.float32)
                heavy = lax.dynamic_index_in_dim(heavy_g, k, 0, keepdims=False)
                heavy2 = lax.dynamic_index_in_dim(
                    heavy2_g, k, 0, keepdims=False
                )
                xp = lax.dynamic_index_in_dim(
                    xp_g, k, 0, keepdims=False
                ).astype(jnp.float32)
                xp2 = lax.dynamic_index_in_dim(
                    xp2_g, k, 0, keepdims=False
                ).astype(jnp.float32)
                xa = lax.dynamic_index_in_dim(
                    xa_g, k, 0, keepdims=False
                ).astype(jnp.float32)
                valid = rank < f

                h = jnp.maximum(heavy, 0)
                pref = jnp.where((heavy >= 0) & valid, assign[h], -1)
                p = jnp.maximum(pref, 0)
                ok1 = pref >= 0
                h2 = jnp.maximum(heavy2, 0)
                pref2 = jnp.where((heavy2 >= 0) & valid, assign[h2], -1)
                p2 = jnp.maximum(pref2, 0)
                ok2 = (pref2 >= 0) & (pref2 != pref)

                order = jnp.argsort(
                    jnp.where(running, load * inv_t, jnp.inf)
                )
                block = jnp.maximum((f + w_run - 1) // w_run, 1)
                slot = jnp.clip(rank // block, 0, W - 1)
                spread = order[slot]

                if uniform:
                    c0 = jnp.where(ok1, xp + ovt_c, INF)
                    c1 = jnp.where(ok2, xp2 + ovt_c, INF)
                    c2 = xa + ovt_c
                else:
                    c0 = jnp.where(ok1, ovt0[p] + xp, INF)
                    c1 = jnp.where(ok2, ovt0[p2] + xp2, INF)
                    c2 = ovt0[spread] + xa
                choice = _argmin3(c0, c1, c2)
                tent = _sel3(choice, p, p2, spread)
                xfer_t = _sel3(choice, xp, xp2, xa)

                tw = jnp.where(valid, dur + xfer_t, 0.0)
                # the wave's per-task terms, gathered and summed in task
                # order on every shard: a psum of per-shard partials
                # re-associates the f32 sums and flips near-ties
                tl = jax.ops.segment_sum(
                    lax.all_gather(tw, names, tiled=True),
                    jnp.maximum(lax.all_gather(tent, names, tiled=True), 0),
                    num_segments=W,
                )
                if uniform:
                    tli = tl * inv_c
                    corr = tw * inv_c
                    d0 = jnp.where(
                        ok1,
                        tli[p] - jnp.where(p == tent, corr, 0.0)
                        + xp + ovt_c,
                        INF,
                    )
                    d1 = jnp.where(
                        ok2,
                        tli[p2] - jnp.where(p2 == tent, corr, 0.0)
                        + xp2 + ovt_c,
                        INF,
                    )
                    d2 = (
                        tli[spread]
                        - jnp.where(spread == tent, corr, 0.0)
                        + xa + ovt_c
                    )
                else:
                    s_tab = ovt0 + tl * inv_t
                    corr = tw * inv_t[tent]
                    d0 = jnp.where(
                        ok1,
                        s_tab[p] - jnp.where(p == tent, corr, 0.0) + xp,
                        INF,
                    )
                    d1 = jnp.where(
                        ok2,
                        s_tab[p2] - jnp.where(p2 == tent, corr, 0.0) + xp2,
                        INF,
                    )
                    d2 = (
                        s_tab[spread]
                        - jnp.where(spread == tent, corr, 0.0) + xa
                    )
                choice = _argmin3(d0, d1, d2)
                assign_w = _sel3(choice, p, p2, spread)
                xfer = _sel3(choice, xp, xp2, xa)
                assign_w = jnp.where(valid, assign_w, -1)

                work = jnp.where(assign_w >= 0, dur + xfer, 0.0)
                # republish this wave's slice to every shard: tiled
                # gather over the flattened device order reassembles the
                # CONTIGUOUS [F] window (shard d holds rows [d*Fl, ...))
                afull = lax.all_gather(assign_w, names, tiled=True)
                cfull = lax.all_gather(choice, names, tiled=True)
                wave_load = jax.ops.segment_sum(
                    lax.all_gather(work, names, tiled=True),
                    jnp.maximum(afull, 0),
                    num_segments=W,
                )
                load = load + wave_load
                span = jnp.where(running, wave_load * inv_t, 0.0).max()
                spans = spans.at[widxs[k]].set(span)
                assign = lax.dynamic_update_slice(assign, afull, (offset,))
                choices = lax.dynamic_update_slice(choices, cfull, (offset,))
                return assign, choices, load, spans

            if K == 1:
                return run_wave(carry)
            return lax.cond(f > 0, run_wave, lambda c: c, carry)

        if K == 1:
            out = body(0, (assign, choices, load, spans))
        else:
            out = lax.fori_loop(0, K, body, (assign, choices, load, spans))
        return out

    fleet_spec = P("workers") if fleet_sharded else P(None)
    fn = jax.shard_map(
        _place_run_sharded,
        mesh=mesh,
        in_specs=(
            P(None, names), P(None, names), P(None, names),
            P(None, names), P(None, names), P(None, names),
            P(None), P(None), P(None), P(None),
            P(None), P(None), P(None),
            fleet_spec, fleet_spec, fleet_spec,
        ),
        out_specs=(P(None), P(None), P(None), P(None)),
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=(6, 7, 8, 9))


#: a fused run's tile lanes (``K * F``) above which its per-shard blocks
#: are built on the tile pool.  On an 8-core host with four devices the
#: pool wins from 2^19 lanes (a [4, 131072] run's blocks and put: 5.4 ->
#: 2.8 ms; the 10M-task mesh graph's eleven runs: 228 -> 79 ms) and ties
#: or loses at 2^18 and below, where the hand-off and the GIL-bound row
#: loop outweigh the copies it spreads ([16, 16384]: 4.3 -> 6.1 ms)
_TILE_POOL_LANES = 1 << 18

_tile_pool_lock = threading.Lock()
_TILE_POOL: ThreadPoolExecutor | None = None


def _host_cores() -> int:
    """The cores this process may run on."""
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:  # pragma: no cover - no affinity API
        return os.cpu_count() or 1


def _tile_threads(n_jobs: int) -> int:
    """Threads for ``n_jobs`` block builds: at most one a block, the
    host's cores and 16."""
    return min(n_jobs, _host_cores(), 16)


def _tile_pool() -> ThreadPoolExecutor:
    """The process's tile pool, made on first use (numpy's copies and
    casts drop the GIL, so its threads copy in parallel)."""
    global _TILE_POOL
    with _tile_pool_lock:
        if _TILE_POOL is None:
            _TILE_POOL = ThreadPoolExecutor(
                min(_host_cores(), 16), thread_name_prefix="leveled-tiles"
            )
        return _TILE_POOL


def _shard_block(buf, dtype, K: int, Fl: int, lo: int, offs, fs):
    """One device's ``[K, Fl]`` block of a fused run's tile: row ``i``
    is lanes ``[lo, lo + Fl)`` of the wave at ``offs[i]`` with ``fs[i]``
    real rows, cast to ``dtype`` and zero past the wave's end; rows
    past the run's waves are zero."""
    block = np.empty((K, Fl), dtype)
    for i, (off, f) in enumerate(zip(offs, fs)):
        n = min(max(f - lo, 0), Fl)
        block[i, :n] = buf[off + lo: off + lo + n]
        block[i, n:] = 0
    block[len(offs):] = 0
    return block


class _ShardedRunState(_RunState):
    """Dispatch/download driver for the mesh-sharded engine.

    Differs from the single-device ``_RunState`` in the upload plane:
    instead of six persistent ``Tp``-sized device buffers written by
    chunk, each fused run ships a ``[K, F]`` tile set sharded with
    ``NamedSharding`` — every shard receives EXACTLY its ``[K, Fl]``
    block (per-shard H2D, :meth:`tiles`), and the async ``device_put``
    overlaps the transfer against earlier runs still computing.
    Segmented D2H is inherited unchanged.
    """

    def __init__(self, mesh, packed: PackedGraph, Tp: int, Lp: int,
                 wide: bool, uniform: bool, thr_h, run_h, occ_h,
                 fleet_dev=None, stats: dict | None = None):
        import jax as _jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.mesh = mesh
        self.names, self.axis_sizes, self.D = _mesh_shards(mesh)
        self.packed = packed
        self.Tp = Tp
        self.Lp = Lp
        self.wide = wide
        self.uniform = uniform
        self.sizes = np.diff(packed.offsets)
        rep = NamedSharding(mesh, P(None))
        self.assign = _jax.device_put(np.full(Tp, -1, np.int32), rep)
        self.choices = _jax.device_put(np.full(Tp, 2, np.int32), rep)
        self.load = _jax.device_put(np.asarray(occ_h, np.float32), rep)
        self.spans = _jax.device_put(np.zeros(Lp, np.float32), rep)
        if fleet_dev is not None:
            # mirror-resident fleet shards (scheduler/mirror.py
            # sharded_device_view): ZERO fleet H2D on this plan — the
            # kernel reads the rows each shard already holds
            self.fleet = (
                fleet_dev["nthreads"], fleet_dev["running"],
                fleet_dev["occupancy"],
            )
            self.fleet_sharded = True
        else:
            self.fleet = tuple(
                _jax.device_put(a, rep) for a in (thr_h, run_h, occ_h)
            )
            self.fleet_sharded = False
        self.task_sharding = NamedSharding(mesh, P(None, self.names))
        self.segments = []
        self.seg_from = 0
        self.SEG_MIN = max(packed.n // 4, 4096)
        self.stats = stats
        self.tile_threads = 0  # the most threads a run's tiles used
        if stats is not None:
            stats["n_shards"] = self.D
            stats["runs"] = 0
            stats["shards"] = [
                {"shard": d, "h2d_bytes": 0, "kernel_ms": 0.0}
                for d in range(self.D)
            ]

    _TASK_DTYPES = (np.float16, np.int32, np.int32,
                    np.float16, np.float16, np.float16)

    def tiles(self, host_bufs, Fl: int, waves: list[int]):
        """Ship one fused run's six ``[K, F]`` task tiles, sharded.

        Each device's ``[K, Fl]`` block is built on its own and written
        once: row ``i`` holds the device's lanes of wave ``i``, cast to
        the wire dtype, up to the wave's real rows, then zeros, and the
        padding rows (``i >= len(waves)``) are zeros; the kernel masks
        every such lane.  So no lane reads a host row outside its
        wave's ``[offset, offset + f)``.  Above ``_TILE_POOL_LANES``
        lanes the blocks are built on the tile pool.  One batched
        ``device_put`` ships every block to its device (async)."""
        import jax as _jax

        F = Fl * self.D
        K = _bucket(len(waves), floor=1)
        offs = [int(self.packed.offsets[w]) for w in waves]
        fs = [int(self.sizes[w]) for w in waves]
        index_map = self.task_sharding.addressable_devices_indices_map(
            (K, F)
        )
        devices = list(index_map)
        los = [idx[1].start or 0 for idx in index_map.values()]
        jobs = [(a, lo) for a in range(len(host_bufs)) for lo in los]

        def build(job):
            a, lo = job
            return _shard_block(host_bufs[a], self._TASK_DTYPES[a], K, Fl,
                                lo, offs, fs)

        threads = _tile_threads(len(jobs)) if K * F > _TILE_POOL_LANES else 1
        self.tile_threads = max(self.tile_threads, threads)
        if threads > 1:
            # the pool runs min(jobs, its threads) of them at once
            blocks = list(_tile_pool().map(build, jobs))
        else:
            blocks = [build(job) for job in jobs]
        if self.stats is not None:
            per_shard = sum(K * Fl * np.dtype(dt).itemsize
                            for dt in self._TASK_DTYPES)
            for row in self.stats["shards"]:
                row["h2d_bytes"] += per_shard
        shards = _jax.device_put(blocks, devices * len(host_bufs))
        D = self.D
        return tuple(
            _jax.make_array_from_single_device_arrays(
                (K, F), self.task_sharding, shards[a * D:(a + 1) * D]
            )
            for a in range(len(host_bufs))
        )

    def dispatch(self, tiles, Fl: int, waves: list[int],
                 last: bool) -> None:
        """Enqueue the kernel on one fused run's shipped tiles
        (:meth:`tiles`)."""
        packed = self.packed
        K = _bucket(len(waves), floor=1)
        offs = np.full(K, packed.n, np.int32)
        fs = np.zeros(K, np.int32)
        widxs = np.full(K, self.Lp - 1, np.int32)
        for i, w in enumerate(waves):
            offs[i] = packed.offsets[w]
            fs[i] = self.sizes[w]
            widxs[i] = w
        if self.stats is not None:
            self.stats["runs"] += 1
        W = int(self.fleet[0].shape[0])
        fn = _sharded_run_fn(
            self.mesh, Fl, K, W, self.uniform, self.fleet_sharded
        )
        self.assign, self.choices, self.load, self.spans = fn(
            *tiles,
            self.assign, self.choices, self.load, self.spans,
            jnp.asarray(offs), jnp.asarray(fs), jnp.asarray(widxs),
            *self.fleet,
        )
        self._maybe_segment(int(packed.offsets[waves[-1] + 1]), last)

    def record_shard_ms(self) -> None:
        """Per-shard completion wall, measured AFTER the last dispatch
        was enqueued and BEFORE the blocking host fetch: shard d's entry
        is the time until its copy of the final carry went ready.

        The probe blocks shard-by-shard IN ORDER, so the series is
        cumulative (monotone non-decreasing): a later shard can never
        read lower than an earlier one, and a straggler inflates every
        shard behind it — read the FIRST shard's value as the pipeline
        drain time and a large step between neighbours as "the earlier
        shard was the straggler".  Per-device completion timestamps
        would need device events jax does not expose portably."""
        if self.stats is None:
            return
        import time as _time

        order = {
            d.id: i for i, d in enumerate(self.mesh.devices.flatten())
        }
        t0 = _time.perf_counter()
        try:
            shards = sorted(
                self.assign.addressable_shards,
                key=lambda s: order.get(s.device.id, 0),
            )
            for s in shards:
                s.data.block_until_ready()
                i = order.get(s.device.id, 0)
                self.stats["shards"][i]["kernel_ms"] = round(
                    (_time.perf_counter() - t0) * 1e3, 3
                )
        except AttributeError:  # pragma: no cover - non-array backend
            pass


def place_graph_leveled_sharded(
    mesh,
    packed: PackedGraph,
    nthreads,
    occupancy0,
    running,
    *,
    fleet_dev=None,
    stats: dict | None = None,
) -> LeveledResult:
    """Place the whole graph as one partitioned program over ``mesh``.

    Semantics match :func:`place_graph_leveled`; on a 1x1 mesh the
    result is bit-identical.  ``fleet_dev`` takes the mirror's
    ``sharded_device_view`` arrays (capacity-sized, ``workers``-axis
    shards) so a fresh cycle ships zero fleet rows; the host
    ``nthreads``/``occupancy0``/``running`` are still required — they
    seed the replicated load carry and the uniform/wide host decisions —
    and must mirror the device rows (the mirror guarantees it).
    ``stats`` (optional dict) receives per-shard H2D bytes and kernel
    completion ms.
    """
    return _run_sharded(mesh, packed, nthreads, occupancy0, running,
                        fleet_dev, stats).finalize()


def _run_sharded(mesh, packed: PackedGraph, nthreads, occupancy0, running,
                 fleet_dev, stats) -> _ShardedRunState:
    """Dispatch every fused run of ``packed`` over ``mesh``; the
    returned state's ``finalize`` gives the result."""
    T = packed.n
    D = _mesh_shards(mesh)[2]
    runs = _plan_runs_sharded(packed.offsets, D)
    Tp = T + _compute_pad(
        T, [(Fl * D, ws) for Fl, ws in runs], packed.offsets
    )
    Lp = _bucket(packed.n_levels + 1, floor=64)
    # the tiles cast to the wire dtypes and read only their waves' rows
    host_bufs = (
        packed.duration_s, packed.heavy_s, packed.heavy2_s,
        packed.xfer_pref_s, packed.xfer_pref2_s, packed.xfer_all_s,
    )
    wide, uniform, thr_h, run_h, occ_h = _worker_params(
        nthreads, occupancy0, running
    )
    rs = _ShardedRunState(mesh, packed, Tp, Lp, wide, uniform,
                          thr_h, run_h, occ_h,
                          fleet_dev=fleet_dev, stats=stats)
    for run_i, (Fl, waves) in enumerate(runs):
        rs.dispatch(rs.tiles(host_bufs, Fl, waves), Fl, waves,
                    last=run_i == len(runs) - 1)
    rs.record_shard_ms()
    return rs

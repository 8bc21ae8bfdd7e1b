"""Whole-graph wavefront placement — the flagship device kernel.

Where `ops.placement.decide_workers` accelerates one batch of ready tasks,
this kernel schedules an **entire task graph** on device: a jit-compiled
``lax.while_loop`` peels off dependency wavefronts (all tasks whose deps are
placed) and assigns each wave in parallel, with the reference scheduler's two
placement behaviors reproduced as vectorized decisions:

- **locality** (reference decide_worker/worker_objective, scheduler.py:8550,
  3131): a task prefers the worker that produced its heaviest dependency;
  it stays there iff the transfer savings beat the load-balance alternative;
- **rootish spreading / co-assignment** (reference scheduler.py:2135-2236):
  tasks without a binding dependency are assigned in priority-contiguous
  blocks sized by worker capacity, least-loaded workers first — siblings end
  up contiguous on the same worker exactly like ``tg.last_worker`` batching.

Complexity per wave is O(T + E + W) — **no dense [T, W] cost matrix** — so a
1M-task / 512-worker graph fits comfortably on one chip and the loop runs
``depth(graph)`` device steps.  This is the engine behind the north-star
benchmark (place 1M tasks on 512 workers < 250 ms) and the unit that
``parallel.sharded_placement`` distributes across a device mesh.

Static shapes throughout: T tasks, E dependency edges, W workers, all padded
by the caller (`GraphArrays.from_graph` pads to compile buckets).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

INT32_MAX = np.int32(2**31 - 1)


class GraphArrays(NamedTuple):
    """CSR-ish SoA encoding of a task graph for device placement."""

    duration: jax.Array  # f32[T] estimated runtime
    out_bytes: jax.Array  # f32[T] estimated output size
    indegree: jax.Array  # i32[T] number of dependencies
    heavy_dep: jax.Array  # i32[T] index of largest-bytes dep, -1 if none
    dep_bytes_total: jax.Array  # f32[T] sum of dep output bytes
    edge_src: jax.Array  # i32[E] producer task per dependency edge
    edge_dst: jax.Array  # i32[E] consumer task per dependency edge
    valid: jax.Array  # bool[T] padding mask

    @property
    def n(self) -> int:
        return self.duration.shape[0]

    @classmethod
    def from_arrays(
        cls,
        durations: np.ndarray,
        out_bytes: np.ndarray,
        edges_src: np.ndarray,
        edges_dst: np.ndarray,
        pad_tasks: int | None = None,
        pad_edges: int | None = None,
    ) -> "GraphArrays":
        """Build from host numpy arrays.  ``edges_src[i] -> edges_dst[i]``
        means dst depends on src.  Padding keeps jit caches warm."""
        T = len(durations)
        E = len(edges_src)
        Tp = pad_tasks or T
        Ep = pad_edges or max(E, 1)
        assert Tp >= T and Ep >= E

        indeg = np.zeros(Tp, np.int32)
        np.add.at(indeg, edges_dst, 1)
        ob = np.zeros(Tp, np.float32)
        ob[:T] = out_bytes
        # heaviest dependency per consumer (host-side, one pass)
        heavy = np.full(Tp, -1, np.int64)
        dep_total = np.zeros(Tp, np.float32)
        src_bytes = ob[edges_src]
        np.add.at(dep_total, edges_dst, src_bytes)
        # argmax-by-bytes per consumer via sort by (dst, -bytes, src);
        # stable: ties -> lower src index wins
        if E:
            order = np.lexsort((edges_src, -src_bytes, edges_dst))
            dst_sorted = edges_dst[order]
            first = np.ones(E, bool)
            first[1:] = dst_sorted[1:] != dst_sorted[:-1]
            heavy[dst_sorted[first]] = edges_src[order][first]

        dur = np.zeros(Tp, np.float32)
        dur[:T] = durations
        valid = np.zeros(Tp, bool)
        valid[:T] = True
        # pad tasks: indegree INT32_MAX so they never become ready
        indeg[T:] = INT32_MAX
        es = np.zeros(Ep, np.int32)
        ed = np.zeros(Ep, np.int32)
        es[:E] = edges_src
        ed[:E] = edges_dst
        if Ep > E:
            # pad edges: self-loop on a pad slot (or task 0 if no padding);
            # masked out because decrements only fire for tasks placed in the
            # current wave and pad tasks are never placed
            pad_t = T if Tp > T else 0
            es[E:] = pad_t
            ed[E:] = pad_t
        return cls(
            duration=jnp.asarray(dur),
            out_bytes=jnp.asarray(ob),
            indegree=jnp.asarray(indeg),
            heavy_dep=jnp.asarray(heavy.astype(np.int32)),
            dep_bytes_total=jnp.asarray(dep_total),
            edge_src=jnp.asarray(es),
            edge_dst=jnp.asarray(ed),
            valid=jnp.asarray(valid),
        )


class PlacementResult(NamedTuple):
    assignment: jax.Array  # i32[T] worker per task (-1 = unplaced/pad)
    start_time: jax.Array  # f32[T] estimated start time
    occupancy: jax.Array  # f32[W] final modeled occupancy
    n_waves: jax.Array  # i32[] wavefront count (critical-path depth)
    wave_of: jax.Array  # i32[T] wave index each task was placed in (-1 = unplaced)


class _Carry(NamedTuple):
    assign: jax.Array  # i32[T]
    start: jax.Array  # f32[T]
    wave_of: jax.Array  # i32[T] wave index each task was placed in
    indeg: jax.Array  # i32[T]
    load: jax.Array  # f32[W] cumulative work over all waves (reporting/fairness)
    clock: jax.Array  # f32[]  modeled wall-clock at wave start
    wave: jax.Array  # i32[]  waves that actually placed something


@functools.partial(jax.jit, static_argnames=("chunk_waves",))
def _place_chunk(
    graph: GraphArrays,
    nthreads: jax.Array,  # i32[W]
    occupancy0: jax.Array,  # f32[W] ambient occupancy at request time
    running: jax.Array,  # bool[W]
    carry: _Carry,
    bandwidth: float = 100e6,
    chunk_waves: int = 32,
) -> _Carry:
    """Run ``chunk_waves`` wavefronts as ONE device dispatch.

    A ``lax.while_loop`` with a data-dependent cond would sync with the host
    every iteration, so the loop is a fixed-trip ``fori_loop``; once the graph is exhausted the body is a
    natural no-op (no ready tasks -> nothing changes) and the host checks
    progress between chunks.
    """
    T = graph.n
    W = nthreads.shape[0]
    threads_f = jnp.maximum(nthreads, 1).astype(jnp.float32)
    cap = jnp.where(running, jnp.maximum(nthreads, 1), 0).astype(jnp.int32)
    total_cap = jnp.maximum(cap.sum(), 1)
    inv_bw = jnp.float32(1.0 / bandwidth)

    def body(_, c: _Carry) -> _Carry:
        ready = (c.indeg == 0) & (c.assign < 0) & graph.valid  # bool[T]

        # Waves execute after their predecessors complete, so cross-wave
        # occupancy has drained (the reference's occupancy likewise drops on
        # task completion, scheduler.py:3264).  Contention is therefore
        # modeled *within* the wave: occ_wave accumulates as the wave's
        # tasks are (conceptually sequentially) assigned; the ambient
        # occupancy0 represents work already on the cluster at request time.

        # ---- locality choice: follow the heaviest dependency
        hd = jnp.maximum(graph.heavy_dep, 0)
        pref = jnp.where(graph.heavy_dep >= 0, c.assign[hd], -1)  # i32[T]
        pref_ok = ready & (pref >= 0) & running[jnp.maximum(pref, 0)]
        heavy_bytes = jnp.where(graph.heavy_dep >= 0, graph.out_bytes[hd], 0.0)
        # transfer cost if we stay with pref: everything but the heavy dep
        xfer_pref = (graph.dep_bytes_total - heavy_bytes) * inv_bw
        xfer_all = graph.dep_bytes_total * inv_bw

        # ---- spread choice: contiguous blocks over least-loaded workers.
        # Equal-size blocks over load-sorted running workers: slot is pure
        # arithmetic (searchsorted over [T] queries is catastrophically slow
        # inside device loops on TPU — measured 40-160 ms/wave at 1M tasks).
        # Capacity heterogeneity is honored across waves by the load-sorted
        # order; load normalizes by threads so big workers sort first.
        order = jnp.argsort(
            jnp.where(running, c.load / threads_f, jnp.inf)
        )
        w_run = jnp.maximum((running & (cap > 0)).sum(), 1).astype(jnp.float32)
        n_ready = jnp.maximum(ready.sum(), 1).astype(jnp.float32)
        # rank of each ready task within the wave (priority == array order);
        # f32 rounding shifts block edges by O(1) tasks at worst
        rank = (jnp.cumsum(ready.astype(jnp.int32)) - 1).astype(jnp.float32)
        spread_slot = (rank * (w_run / n_ready)).astype(jnp.int32)
        spread_slot = jnp.clip(spread_slot, 0, W - 1)
        spread = order[spread_slot]  # i32[T]

        cost_pref = occupancy0[jnp.maximum(pref, 0)] / threads_f[jnp.maximum(pref, 0)] + xfer_pref
        cost_spread = occupancy0[spread] / threads_f[spread] + xfer_all

        choose_pref = pref_ok & (cost_pref <= cost_spread)

        # one Jacobi contention round: re-evaluate the choice against the
        # *tentative* wave load, so dogpiles on a popular producer spill to
        # the spread slot — the vectorized stand-in for the reference's
        # per-assignment occupancy bump in its sequential loop.
        tent = jnp.where(choose_pref, pref, spread)
        tent_work = jnp.where(
            ready, graph.duration + jnp.where(choose_pref, xfer_pref, xfer_all), 0.0
        )
        tent_load = jax.ops.segment_sum(
            tent_work, jnp.maximum(tent, 0), num_segments=W
        )
        p = jnp.maximum(pref, 0)
        # contention from *other* tasks only — subtract own contribution
        load_pref_others = tent_load[p] - jnp.where(tent == p, tent_work, 0.0)
        load_spread_others = tent_load[spread] - jnp.where(
            tent == spread, tent_work, 0.0
        )
        cost_pref2 = (occupancy0[p] + load_pref_others) / threads_f[p] + xfer_pref
        cost_spread2 = (
            occupancy0[spread] + load_spread_others
        ) / threads_f[spread] + xfer_all
        choose_pref = pref_ok & (cost_pref2 <= cost_spread2)

        assign_wave = jnp.where(choose_pref, pref, spread)
        assign_wave = jnp.where(ready & running[assign_wave], assign_wave, -1)
        newly = assign_wave >= 0

        aw = jnp.maximum(assign_wave, 0)
        xfer = jnp.where(choose_pref, xfer_pref, xfer_all)
        work = jnp.where(newly, graph.duration + xfer, 0.0)
        wave_load = jax.ops.segment_sum(work, aw, num_segments=W)  # f32[W]
        load = c.load + wave_load
        est_start = jnp.where(newly, c.clock, 0.0)
        wave_span = jnp.where(running, wave_load / threads_f, 0.0).max()
        clock = c.clock + wave_span

        # release dependency edges of everything placed this wave
        fired = newly[graph.edge_src]
        dec = jax.ops.segment_sum(
            fired.astype(jnp.int32), graph.edge_dst, num_segments=T
        )
        indeg = c.indeg - dec

        assign = jnp.where(newly, assign_wave, c.assign)
        start = jnp.where(newly, est_start, c.start)
        wave_of = jnp.where(newly, c.wave, c.wave_of)
        progressed = newly.any()
        return _Carry(
            assign, start, wave_of, indeg, load, clock,
            c.wave + progressed.astype(jnp.int32),
        )

    return lax.fori_loop(0, chunk_waves, body, carry)


def place_graph(
    graph: GraphArrays,
    nthreads: jax.Array,  # i32[W]
    occupancy0: jax.Array,  # f32[W] initial occupancy
    running: jax.Array,  # bool[W]
    bandwidth: float = 100e6,
    max_waves: int = 0,
    chunk_waves: int = 32,
) -> PlacementResult:
    """Schedule the whole graph (module docstring has the algorithm).

    Dispatches ``chunk_waves``-deep fori chunks and checks progress on the
    host between chunks — one host<->device round trip per ``chunk_waves``
    graph levels instead of one per level.
    """
    T = graph.n
    max_waves = max_waves or T
    carry = _Carry(
        assign=jnp.full(T, -1, jnp.int32),
        start=jnp.zeros(T, jnp.float32),
        wave_of=jnp.full(T, -1, jnp.int32),
        indeg=graph.indegree,
        load=occupancy0,
        clock=jnp.float32(0.0),
        wave=jnp.int32(0),
    )
    waves_prev = 0
    while True:
        carry = _place_chunk(
            graph, nthreads, occupancy0, running, carry,
            bandwidth=bandwidth, chunk_waves=chunk_waves,
        )
        waves = int(carry.wave)
        unplaced = bool(((carry.indeg == 0) & (carry.assign < 0) & graph.valid).any())
        if not unplaced:
            break
        if waves == waves_prev or waves >= max_waves:
            break  # blocked graph (cycle/stopped workers) or wave budget hit
        waves_prev = waves
    return PlacementResult(
        assignment=carry.assign,
        start_time=carry.start,
        occupancy=carry.load,
        n_waves=carry.wave,
        wave_of=carry.wave_of,
    )


def validate_placement(
    graph: GraphArrays, result: PlacementResult, running: np.ndarray
) -> None:
    """Host-side oracle: every valid task placed on a running worker, and
    every consumer placed in a strictly later wave than its producers."""
    assign = np.asarray(result.assignment)
    valid = np.asarray(graph.valid)
    assert (assign[valid] >= 0).all(), "unplaced valid tasks"
    assert running[assign[valid]].all(), "task placed on non-running worker"
    src = np.asarray(graph.edge_src)
    dst = np.asarray(graph.edge_dst)
    wave_of = np.asarray(result.wave_of)
    real = valid[src] & valid[dst] & (src != dst)
    assert (wave_of[src[real]] >= 0).all(), "producer never placed"
    assert (
        wave_of[dst[real]] > wave_of[src[real]]
    ).all(), "consumer placed no later than its producer"

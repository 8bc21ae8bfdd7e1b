"""Device graph partitioner: priority-order blocks + capped label refinement.

The leveled placer (`ops.leveled`) places wave by wave following each
task's heaviest dependency — ideal for the million-task throughput
problem, but it cannot express TILED placements: a reduction tree whose
eight inputs followed eight different "heavy" parents is scattered no
matter what.  Communication-minimal placement of a blockwise graph
(rechunk + tensordot, shuffles, stencils) is a graph PARTITIONING
problem.  The reference has no equivalent — its decide_worker is a
per-task greedy min over dependency holders (reference
scheduler.py:2247, 8550); this module is the TPU-native answer: the
whole batch partitioned in one jitted dispatch, consumed by
``scheduler.jax_placement`` as absolute home hints with park/pull
semantics.

Algorithm (measured on a G=12 blockwise-tensordot proxy; comm volume
= unique (producer, consumer-worker) cross-worker pairs — the number of
peer fetches after replica caching, which is what the cluster pays):

1. **Init: contiguous equal-LOAD blocks of the priority order.**
   Scheduler priorities are depth-first graph order (graph/order.py,
   the dask.order role), so adjacent indices are related tasks — a
   1-D space-filling-curve partition.  This alone beat a hand-computed
   square tiling (comm volume 271 vs 288) with balance by construction.
2. **Refine: label propagation with a HARD admission cap.**  Per
   iteration each task scores every worker by the edge weight of its
   neighbours living there; workers at/above ``cap``·average load are
   masked out as attractors (they keep what they have, they cannot
   pull more).  Half of the tasks update per iteration (synchronous
   all-task moves herd onto whatever the shared load snapshot showed
   underloaded, then oscillate); a stickiness bonus on the current
   label stops bipartite flip-flop.  Refinement took the proxy to
   comm volume 111 and stayed stable from 4 to 16 iterations.  Soft
   load penalties (signed or clamped) measured strictly worse: they
   either herd (signed, volume 0.5·|E|) or starve workers (clamped).

Everything is scatter-adds over the edge arrays plus an argmax over a
dense [T, W] score matrix — the shapes XLA vectorizes well.  Dense
scores bound the method to T·W ≤ DENSE_LIMIT; beyond that callers fall
back to the leveled engine (the two compose: partition quality where it
fits, leveled throughput where it doesn't).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# scores matrix cap: T * W above this would blow device memory; the
# caller falls back to the leveled engine
DENSE_LIMIT = 32_000_000
DEFAULT_ITERS = 8
DEFAULT_CAP = 1.2       # hard admission: load >= cap*avg cannot attract
DEFAULT_STICKY = 2.0    # current-label bonus, in units of mean edge weight


#: mesh axis names of the scheduler engine mesh, in order: "tasks" is the
#: data-parallel wave axis, "workers" shards the fleet SoA rows
ENGINE_AXES = ("tasks", "workers")


def make_engine_mesh(n_devices: int | None = None, layout: str = "auto",
                     devices=None):
    """The scheduler co-processor mesh: 2-D ``(tasks, workers)``.

    The leveled engine splits every wave's task slice over BOTH axes
    (the flattened device order), while the fleet mirror's SoA rows
    shard over ``"workers"`` only (replicated along ``"tasks"``) — see
    ``scheduler/mirror.py.sharded_device_view`` and
    ``ops/leveled.place_graph_leveled_sharded``.

    ``layout`` is ``"auto"`` (factor ``n`` as close to square as
    possible, workers axis the smaller factor) or an explicit ``"TxW"``
    string, e.g. ``"4x2"``.  ``n_devices`` of ``None``/``0`` means all
    visible devices.
    """
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    if n_devices:
        # truncate to what exists (the historical make_mesh semantics):
        # asking for 8 on a 2-device host yields the 2-device mesh; an
        # EXPLICIT "TxW" layout below still raises when unsatisfiable
        devices = devices[: min(n_devices, len(devices))]
    n = len(devices)
    if layout and layout != "auto":
        dt, dw = (int(p) for p in str(layout).lower().split("x"))
        if dt * dw > n:
            raise ValueError(f"layout {layout} needs {dt*dw} devices, have {n}")
        devices = devices[: dt * dw]
    else:
        dw = 1
        for f in range(int(np.sqrt(n)), 0, -1):
            if n % f == 0:
                dw = f
                break
        dt = n // dw
    dev_array = np.asarray(devices).reshape(dt, dw)
    return Mesh(dev_array, axis_names=ENGINE_AXES)


def shard_bucket(n: int, n_shards: int, floor: int = 2048) -> int:
    """Per-shard power-of-two bucket for a wave of ``n`` tasks split
    over ``n_shards`` devices — the sharded engine's analogue of
    ``ops.leveled._bucket``: bounds distinct jit shapes while keeping
    every shard's slice the same (static) length."""
    need = max(-(-n // max(n_shards, 1)), 1)
    b = floor
    while b < need:
        b *= 2
    return b


def block_init(durations: np.ndarray, n_workers: int) -> np.ndarray:
    """Equal-load contiguous blocks over the (priority-sorted) task
    axis: label[i] = which of the W cumulative-duration buckets the
    midpoint of task i falls in."""
    T = len(durations)
    W = int(n_workers)
    if T == 0:
        return np.zeros(0, np.int64)
    d = np.asarray(durations, np.float64)
    cum = np.cumsum(d) - d / 2.0
    total = float(d.sum())
    if total <= 0:
        return (np.arange(T, dtype=np.int64) * W) // max(T, 1)
    return np.minimum((cum / total * W).astype(np.int64), W - 1)


def partition_numpy(
    durations: np.ndarray,    # f32[T] in PRIORITY order
    weights: np.ndarray,      # f32[E] cost of cutting edge e
    src: np.ndarray,          # i32[E] edge producer (task index)
    dst: np.ndarray,          # i32[E] edge consumer (task index)
    n_workers: int,
    iters: int = DEFAULT_ITERS,
    cap: float = DEFAULT_CAP,
    sticky: float = DEFAULT_STICKY,
    init: np.ndarray | None = None,
) -> np.ndarray:
    """Reference implementation (and the ``partitioner: numpy`` engine);
    returns i32[T] worker index per task."""
    T = len(durations)
    W = int(n_workers)
    if T == 0 or W <= 1:
        return np.zeros(T, np.int32)
    labels = (
        init.astype(np.int64).copy() if init is not None
        else block_init(durations, W)
    )
    mean_w = float(weights.mean()) if len(weights) else 1.0
    avg_load = float(durations.sum()) / W or 1.0
    idx = np.arange(T)
    for it in range(iters):
        scores = np.zeros((T, W), np.float32)
        np.add.at(scores, (dst, labels[src]), weights)
        np.add.at(scores, (src, labels[dst]), weights)
        load = np.zeros(W, np.float32)
        np.add.at(load, labels, durations)
        blocked = load >= cap * avg_load
        scores = np.where(blocked[None, :], -np.inf, scores)
        own = np.maximum(scores[idx, labels], 0.0) + sticky * mean_w
        scores[idx, labels] = own
        new = np.argmax(scores, axis=1)
        labels = np.where((idx + it) % 2 == 0, new, labels)
    return labels.astype(np.int32)


@functools.partial(
    jax.jit, static_argnames=("n_workers", "iters", "cap", "sticky")
)
def partition_kernel(durations, weights, src, dst, labels, *, n_workers,
                     iters=DEFAULT_ITERS, cap=DEFAULT_CAP,
                     sticky=DEFAULT_STICKY):
    """The jitted refinement loop: f32[T] durations, f32[E] weights,
    i32[E] src/dst, i32[T] initial labels -> i32[T] labels.  One compile
    per (T, E) shape and static ``n_workers``/``iters``/``cap``/``sticky``."""
    T = durations.shape[0]
    W = n_workers
    mean_w = jnp.where(weights.size > 0, weights.mean(), 1.0)
    avg_load = jnp.maximum(durations.sum() / W, 1e-9)
    idx = jnp.arange(T)

    def body(it, labels):
        scores = jnp.zeros((T, W), jnp.float32)
        scores = scores.at[dst, labels[src]].add(weights)
        scores = scores.at[src, labels[dst]].add(weights)
        load = jnp.zeros(W, jnp.float32).at[labels].add(durations)
        blocked = load >= cap * avg_load
        scores = jnp.where(blocked[None, :], -jnp.inf, scores)
        own = jnp.maximum(scores[idx, labels], 0.0) + sticky * mean_w
        scores = scores.at[idx, labels].set(own)
        new = jnp.argmax(scores, axis=1)
        return jnp.where((idx + it) % 2 == 0, new, labels)

    return jax.lax.fori_loop(0, iters, body, labels)


def partition_jax(
    durations,
    weights,
    src,
    dst,
    n_workers: int,
    iters: int = DEFAULT_ITERS,
    cap: float = DEFAULT_CAP,
    sticky: float = DEFAULT_STICKY,
    init=None,
):
    """Device variant of :func:`partition_numpy`, same contract.

    One compile per (T, E, W) shape class — callers should pad T/E to
    power-of-two buckets when graph sizes vary (see
    :func:`partition_padded`)."""
    T = int(durations.shape[0])
    W = int(n_workers)
    if T == 0 or W <= 1:
        return np.zeros(T, np.int32)
    if init is None:
        init = block_init(np.asarray(durations), W)
    labels = partition_kernel(
        jnp.asarray(durations, jnp.float32),
        jnp.asarray(weights, jnp.float32),
        jnp.asarray(src, jnp.int32),
        jnp.asarray(dst, jnp.int32),
        jnp.asarray(init, jnp.int32),
        n_workers=W, iters=int(iters), cap=float(cap), sticky=float(sticky),
    )
    return np.asarray(labels, np.int32)


def _bucket(n: int, floor: int = 1024) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def partition_padded(
    durations: np.ndarray,
    weights: np.ndarray,
    src: np.ndarray,
    dst: np.ndarray,
    n_workers: int,
    iters: int = DEFAULT_ITERS,
) -> np.ndarray:
    """Pad T and E to power-of-two buckets so repeated graphs of similar
    size reuse one jit compile.  Padding tasks have zero duration and
    zero-weight self-edges, so they cannot influence real labels."""
    T = len(durations)
    if T == 0:
        return np.zeros(0, np.int32)
    TB = _bucket(T)
    EB = _bucket(max(len(src), 1))
    d = np.zeros(TB, np.float32)
    d[:T] = durations
    w = np.zeros(EB, np.float32)
    w[: len(weights)] = weights
    s = np.zeros(EB, np.int32)
    s[: len(src)] = src
    t = np.zeros(EB, np.int32)
    t[: len(dst)] = dst
    init = np.empty(TB, np.int64)
    init[:T] = block_init(durations, n_workers)
    init[T:] = np.arange(TB - T, dtype=np.int64) % max(n_workers, 1)
    labels = partition_jax(d, w, s, t, n_workers, iters=iters, init=init)
    return labels[:T]

"""Batched Active-Memory-Manager replica-drop selection on device.

The python ``ReduceReplicas`` policy (scheduler/amm.py, mirroring
reference active_memory_manager.py:527) yields one drop suggestion at a
time; ``_find_dropper`` then picks the holder with the highest projected
memory, updating projections per suggestion.  This kernel batches the
whole round: given the (task x worker) replica matrix it peels excess
replicas in K Jacobi rounds — each round every over-replicated task
drops from its currently highest-projected-memory eligible holder and
the per-worker projections are updated with a segment-sum — i.e. a
vectorized bin-unpacking of replicas off the fullest bins.

Parity contract (tested by sequential re-validation): replaying the
emitted drops in round order reproduces the python policy's invariants —
never the last replica, never an excluded holder, each drop taken from
the max-projected-memory holder among the task's eligible holders at its
application point (ties broken toward the lowest worker index).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tpu.ops.leveled import _bucket


class DropBatch(NamedTuple):
    """SoA view of one AMM round over replicated tasks."""

    holders: np.ndarray   # bool[R, W] replica matrix
    excluded: np.ndarray  # bool[R, W] holders that must not drop (active use)
    nbytes: np.ndarray    # f32[R] replica size
    ndrop: np.ndarray     # i32[R] replicas to shed per task
    mem: np.ndarray       # f32[W] projected managed memory per worker


#: most replicated-task rows in one device call; a larger round runs as
#: consecutive calls, each starting from the memory projection the
#: previous one left
MAX_ROWS = 4096
#: most drop rounds per call (the output's static width); the rounds
#: actually run are a traced bound, so they never enter the program shape
MAX_ROUNDS = 64
#: smallest row bucket
MIN_ROWS = 64


@jax.jit
def _drop_rounds(holders, excluded, nbytes, ndrop, mem, K):
    R, W = holders.shape
    NEG = jnp.float32(-np.inf)

    def round_body(k, carry):
        holders, ndrop, mem, drops = carry
        # eligible holders per task; keep >= 1 replica always
        nrep = holders.sum(axis=1)
        can = holders & ~excluded & (ndrop > 0)[:, None] & (nrep > 1)[:, None]
        # drop from the fullest holder; ties toward the LOWEST worker
        # index (argmax picks the first maximum)
        score = jnp.where(can, mem[None, :], NEG)
        w = jnp.argmax(score, axis=1)
        ok = jnp.take_along_axis(can, w[:, None], 1)[:, 0]
        # apply: clear the replica bit, count down, shrink projections
        holders = holders & ~(
            ok[:, None] & (jnp.arange(W)[None, :] == w[:, None])
        )
        ndrop = ndrop - ok.astype(jnp.int32)
        shed = jax.ops.segment_sum(
            jnp.where(ok, nbytes, 0.0), jnp.where(ok, w, W),
            num_segments=W + 1,
        )[:W]
        mem = jnp.maximum(mem - shed, 0.0)
        drops = drops.at[:, k].set(jnp.where(ok, w, -1).astype(jnp.int32))
        return holders, ndrop, mem, drops

    drops0 = jnp.full((R, MAX_ROUNDS), -1, jnp.int32)
    _, _, mem, drops = jax.lax.fori_loop(
        0, K, round_body, (holders, ndrop, mem, drops0)
    )
    return drops, mem


def _row_buckets():
    b = MIN_ROWS
    while b <= MAX_ROWS:
        yield b
        b <<= 1


def lower_all(W: int):
    """Yield ``_drop_rounds`` lowered for every row bucket a round can
    use at fleet width ``W`` (the mirror's capacity): compiled ahead,
    a live AMM cycle — which runs on the event loop — never compiles."""
    for R in _row_buckets():
        yield _drop_rounds.lower(
            jax.ShapeDtypeStruct((R, W), jnp.bool_),
            jax.ShapeDtypeStruct((R, W), jnp.bool_),
            jax.ShapeDtypeStruct((R,), jnp.float32),
            jax.ShapeDtypeStruct((R,), jnp.int32),
            jax.ShapeDtypeStruct((W,), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.int32),
        )


def plan_drop_rounds(
    batch: DropBatch, rounds: int | None = None
) -> list[list[tuple[int, int]]]:
    """Select replica drops on device; returns rounds of
    [(task_row, worker_idx)].  Drops within one round were selected
    against the same (round-start) memory projection — Jacobi, where the
    python policy is Gauss-Seidel.  Rows beyond ``MAX_ROWS`` go in later
    calls, whose rounds follow the earlier calls' rounds."""
    R = len(batch.nbytes)
    if R == 0:
        return []
    K = rounds if rounds is not None else int(max(batch.ndrop.max(), 1))
    K = min(K, MAX_ROUNDS)
    W = batch.holders.shape[1]
    mem = jnp.asarray(batch.mem, jnp.float32)
    out: list[list[tuple[int, int]]] = []
    for lo in range(0, R, MAX_ROWS):
        hi = min(lo + MAX_ROWS, R)
        # pad rows to a pow2 bucket: repeated AMM cycles vary in
        # replicated-task count every 2 s, and lower_all() covers
        # exactly these buckets
        Rp = _bucket(hi - lo, floor=MIN_ROWS)

        def pad2(arr):
            buf = np.zeros((Rp, W), bool)
            buf[: hi - lo] = arr[lo:hi]
            return jnp.asarray(buf)

        def pad1(arr, dtype):
            buf = np.zeros(Rp, dtype)
            buf[: hi - lo] = arr[lo:hi]
            return jnp.asarray(buf)

        drops, mem = _drop_rounds(
            pad2(batch.holders),
            pad2(batch.excluded),
            pad1(batch.nbytes, np.float32),
            pad1(batch.ndrop, np.int32),
            mem,
            np.int32(K),
        )
        drops = np.asarray(drops)[: hi - lo, :K]
        for k in range(K):
            col = drops[:, k]
            rnd = [(lo + int(r), int(col[r]))
                   for r in np.nonzero(col >= 0)[0]]
            if rnd:
                out.append(rnd)
    return out


def plan_drops(batch: DropBatch, rounds: int | None = None) -> list[tuple[int, int]]:
    """Flat [(task_row, worker_idx)] in application (round) order."""
    return [d for rnd in plan_drop_rounds(batch, rounds) for d in rnd]

"""Batched work-stealing decisions on device (the WorkStealing
co-processor).

The python ``WorkStealing.balance`` (scheduler/stealing.py, mirroring
reference stealing.py:402-465) walks victims x levels x tasks
sequentially, re-evaluating occupancy after every move.  This kernel
batches one balance cycle into K Jacobi rounds of a single jitted
program over SoA arrays:

per round
  1. unstolen stealable tasks are ordered busiest-victim-first, then by
     (level, arrival-rank) — the python scan order within a victim;
  2. ONE TASK PER IDLE THIEF: rank r task goes to rank r least-loaded
     thief.  Per-victim nomination (the old scheme) drained a single
     overloaded worker one task per round — a 320-task pile on one
     victim took 40 rounds; per-thief pairing lets it feed the whole
     fleet in one round;
  3. each pair applies the reference steal criterion
     ``occ_thief/nthreads + cost + compute <= occ_victim/nthreads -
     compute/2`` (reference stealing.py:462-465).  When one victim
     donates several tasks in a round, each criterion conservatively
     assumes every OTHER same-victim candidate was already applied;
     accepted moves update occupancy (scatter-add over repeated
     victims), mark tasks stolen, and refresh the idle set
     (``occ/nthreads > LATENCY`` retires a thief, reference
     stealing.py:447).

Thieves are pairwise-distinct within a round and same-victim criteria
are evaluated against the full other-candidate load, so replaying the
accepted moves sequentially in ANY order satisfies the python criterion
at each application point (tested in tests/test_ops_stealing_amm.py by
sequential re-validation against the python oracle).

The decisions feed the existing async confirm protocol
(``move_task_request``) unchanged: the device only batches the
*selection*, exactly like the placement co-processor batches
``decide_worker``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tpu.ops.leveled import _bucket

LATENCY = 0.1  # assumed steal round-trip (reference stealing.py:33-37)

_RANK_BITS = 27  # key = level << 27 | rank; level < 16, rank < 2^27


class StealBatch(NamedTuple):
    """SoA view of one balance cycle's stealable tasks + worker fleet."""

    task_victim: np.ndarray   # i32[T] worker index currently holding the task
    task_key: np.ndarray      # i32[T] (level << 27) | arrival-rank
    task_cost: np.ndarray     # f32[T] transfer seconds to a thief
    task_compute: np.ndarray  # f32[T] estimated compute seconds
    occ: np.ndarray           # f32[W] occupancy
    nthreads: np.ndarray      # i32[W]
    idle: np.ndarray          # bool[W] potential thieves
    running: np.ndarray       # bool[W]


def make_key(level: np.ndarray, rank: np.ndarray) -> np.ndarray:
    return (
        (level.astype(np.int32) << _RANK_BITS)
        | np.minimum(rank, (1 << _RANK_BITS) - 1).astype(np.int32)
    )


@functools.partial(jax.jit, static_argnames=("K",))
def _steal_rounds(
    task_victim,   # i32[T]
    task_key,      # i32[T]
    task_cost,     # f32[T]
    task_compute,  # f32[T]
    occ,           # f32[W]
    nthreads,      # i32[W]
    idle,          # bool[W]
    running,       # bool[W]
    K: int,
):
    T = task_victim.shape[0]
    W = occ.shape[0]
    threads = jnp.maximum(nthreads, 1).astype(jnp.float32)
    idx = jnp.arange(T, dtype=jnp.int32)
    r = jnp.arange(W, dtype=jnp.int32)
    IMAX = jnp.int32(2**31 - 1)

    def round_body(_, carry):
        taken, thief_of, occ, idle = carry
        # 1. order unstolen tasks: busiest victim first, then steal key.
        #    ONE TASK PER THIEF per round (not per victim): a single
        #    overloaded worker must be able to donate to every idle
        #    thief at once — per-victim nomination drained config 3's
        #    320-task pile 8 tasks per cycle (balance_efficiency 0.5).
        key = jnp.where(taken[:T], IMAX, task_key)
        vload = occ / threads
        # victims are NOT masked on running: a paused worker keeps its
        # pile and the scheduler re-marks its homed tasks stealable so
        # the balancer can drain it (the python path includes paused
        # victims too); a victim REMOVED after the snapshot costs
        # nothing — the apply step re-validates ``processing_on``.
        # ``running`` gates only thief eligibility below.
        usable = key != IMAX
        order = jnp.lexsort(
            (key, jnp.where(usable, -vload[task_victim], jnp.inf))
        )
        thief_order = jnp.argsort(jnp.where(idle & running, vload, jnp.inf))
        n_th = (idle & running).sum()
        n_usable = usable.sum()
        t = order[jnp.minimum(r, T - 1)]
        # r < n_usable: without it, fleets with more idle thieves than
        # stealable tasks would clamp several slots onto the LAST task
        # and double-steal it (corrupting occupancy for later rounds)
        cand_ok = (r < n_th) & (r < n_usable) & usable[t]
        th = thief_order[r]

        # 2. same-victim load adjustment: when one victim donates
        #    several tasks this round, each criterion assumes EVERY
        #    other same-victim candidate was already applied — a
        #    superset of the accepted ones, so the accepted moves
        #    satisfy the sequential python criterion replayed in ANY
        #    order (over-conservative rejections cost a round, later
        #    rounds pick them back up)
        vic = task_victim[t]
        tc = jnp.where(cand_ok, task_cost[t], 0.0)
        cp = jnp.where(cand_ok, task_compute[t], 0.0)
        same = (vic[None, :] == vic[:, None]) & cand_ok[None, :] & cand_ok[:, None]
        others_cp = (same * cp[None, :]).sum(axis=1) - cp

        # 3. the reference criterion per (task, thief) pair
        crit = (
            vload[th] + tc + cp
            <= vload[vic] - others_cp / threads[vic] - cp / 2
        )
        acc = cand_ok & crit & (vic != th)

        # apply accepted moves (thieves distinct by construction;
        # repeated victims accumulate via scatter-add)
        occ = occ.at[jnp.where(acc, vic, W)].add(-cp, mode="drop")
        occ = occ.at[jnp.where(acc, th, W)].add(cp + tc, mode="drop")
        taken = taken.at[jnp.where(acc, t, T)].set(True)
        thief_of = thief_of.at[jnp.where(acc, t, T)].set(
            jnp.where(acc, th, -1).astype(jnp.int32)
        )
        # a thief that got loaded past LATENCY stops being idle
        idle = idle & ~((occ / threads) > LATENCY)
        return taken, thief_of, occ, idle

    taken0 = jnp.zeros(T + 1, bool)
    thief0 = jnp.full(T + 1, -1, jnp.int32)
    taken, thief_of, occ, idle = jax.lax.fori_loop(
        0, K, round_body, (taken0, thief0, occ, idle)
    )
    return thief_of[:T], occ


#: smallest task bucket
MIN_TASKS = 64


def lower_all(W: int, max_tasks: int, rounds: int = 8):
    """Yield ``_steal_rounds`` lowered for every task bucket a balance
    cycle of at most ``max_tasks`` tasks can use at fleet width ``W``
    (the mirror's capacity): compiled ahead, a live cycle never
    compiles."""
    fleet = [jax.ShapeDtypeStruct((W,), d)
             for d in (jnp.float32, jnp.int32, jnp.bool_, jnp.bool_)]
    T = MIN_TASKS
    while T <= _bucket(max_tasks, floor=MIN_TASKS):
        task = [jax.ShapeDtypeStruct((T,), d)
                for d in (jnp.int32, jnp.int32, jnp.float32, jnp.float32)]
        yield _steal_rounds.lower(*task, *fleet, K=rounds)
        T <<= 1


def plan_steals(batch: StealBatch, rounds: int = 8) -> np.ndarray:
    """One balance cycle on device; returns thief worker index per task
    (-1 = not stolen).

    Task arrays are padded to a power-of-two bucket so repeated cycles
    (whose stealable count varies every 100 ms) reuse the jit cache
    instead of recompiling per call.  Padding rows carry the sentinel
    key INT32_MAX, which ``_steal_rounds`` never nominates."""
    T = len(batch.task_victim)
    if T == 0:
        return np.zeros(0, np.int32)
    Tp = _bucket(T, floor=MIN_TASKS)

    def pad(arr, fill, dtype):
        buf = np.full(Tp, fill, dtype)
        buf[:T] = arr
        return jnp.asarray(buf)

    thief_of, _ = _steal_rounds(
        pad(batch.task_victim, 0, np.int32),
        pad(batch.task_key, 2**31 - 1, np.int32),
        pad(batch.task_cost, 0, np.float32),
        pad(batch.task_compute, 0, np.float32),
        jnp.asarray(batch.occ),
        jnp.asarray(batch.nthreads),
        jnp.asarray(batch.idle),
        jnp.asarray(batch.running),
        K=rounds,
    )
    return np.asarray(thief_of)[:T]

"""Device kernels for work stealing and AMM replica drops
(ops/stealing.py, ops/amm.py): oracle-parity by sequential re-validation
against the python criterion, plus live-cluster tests where the device
path makes real decisions."""

from __future__ import annotations

import asyncio

import numpy as np
import pytest

from distributed_tpu.ops.amm import DropBatch, plan_drop_rounds, plan_drops
from distributed_tpu.ops.stealing import (
    LATENCY,
    StealBatch,
    make_key,
    plan_steals,
)

from conftest import gen_test


# ----------------------------------------------------------- ops.stealing


def random_steal_batch(rng, T=200, W=16, idle_frac=0.5):
    victim_workers = rng.integers(0, W, T)
    level = rng.integers(0, 15, T)
    rank = np.arange(T)
    occ = np.zeros(W, np.float32)
    compute = rng.uniform(0.05, 0.5, T).astype(np.float32)
    cost = rng.uniform(0.0, 0.05, T).astype(np.float32) + LATENCY
    for t in range(T):
        occ[victim_workers[t]] += compute[t]
    idle = occ < np.quantile(occ, idle_frac)
    return StealBatch(
        task_victim=victim_workers.astype(np.int32),
        task_key=make_key(level, rank),
        task_cost=cost,
        task_compute=compute,
        occ=occ,
        nthreads=np.full(W, 2, np.int32),
        idle=idle,
        running=np.ones(W, bool),
    )


def test_steals_satisfy_python_criterion_sequentially():
    """Every emitted move must satisfy the reference steal criterion when
    the moves are replayed sequentially (the python oracle's contract,
    reference stealing.py:462-465)."""
    rng = np.random.default_rng(0)
    batch = random_steal_batch(rng)
    thief_of = plan_steals(batch)
    assert (thief_of >= 0).sum() > 0, "kernel made no steals on an imbalance"

    occ = batch.occ.astype(np.float64).copy()
    threads = np.maximum(batch.nthreads, 1)
    for t in np.nonzero(thief_of >= 0)[0]:
        v = batch.task_victim[t]
        th = thief_of[t]
        assert v != th
        cp = batch.task_compute[t]
        tc = batch.task_cost[t]
        # tolerance: the kernel evaluates the criterion at round-local
        # occupancy; replay order within a round is arbitrary but rounds
        # touch distinct victim/thief pairs, so the inequality holds up
        # to float32 rounding
        assert occ[th] / threads[th] + tc + cp <= occ[v] / threads[v] - cp / 2 + 1e-4, (
            t, v, th,
        )
        occ[v] -= cp
        occ[th] += cp + tc
    # no task stolen twice, no thief == victim
    stolen = thief_of[thief_of >= 0]
    assert len(stolen) == (thief_of >= 0).sum()


def test_steal_prefers_low_levels():
    """Within one victim, the lowest (level, rank) task moves first —
    the python scan order (reference stealing.py:420)."""
    W = 4
    T = 8
    victim = np.zeros(T, np.int32)  # all on worker 0
    level = np.asarray([9, 1, 5, 1, 14, 0, 7, 3])
    batch = StealBatch(
        task_victim=victim,
        task_key=make_key(level, np.arange(T)),
        task_cost=np.full(T, LATENCY, np.float32),
        task_compute=np.full(T, 1.0, np.float32),
        occ=np.asarray([8.0, 0, 0, 0], np.float32),
        nthreads=np.ones(W, np.int32),
        idle=np.asarray([False, True, True, True]),
        running=np.ones(W, bool),
    )
    thief_of = plan_steals(batch, rounds=1)
    # one round, one task per idle THIEF (a single overloaded victim can
    # feed the whole fleet at once); steal order follows (level, rank)
    stolen = set(np.flatnonzero(thief_of >= 0).tolist())
    assert 1 <= len(stolen) <= 3  # 3 idle thieves
    # the stolen tasks must be exactly the lowest-(level, rank) ones:
    # levels [9,1,5,1,14,0,7,3] -> 0 (idx 5), then 1 (idx 1), 1 (idx 3)
    expected_order = [5, 1, 3]
    assert stolen == set(expected_order[: len(stolen)]), (stolen, thief_of)


def test_no_steals_when_balanced():
    rng = np.random.default_rng(1)
    W, T = 8, 64
    batch = StealBatch(
        task_victim=rng.integers(0, W, T).astype(np.int32),
        task_key=make_key(np.zeros(T, np.int64), np.arange(T)),
        task_cost=np.full(T, LATENCY, np.float32),
        task_compute=np.full(T, 0.1, np.float32),
        occ=np.full(W, 0.8, np.float32),  # perfectly balanced
        nthreads=np.ones(W, np.int32),
        idle=np.zeros(W, bool),  # nobody idle
        running=np.ones(W, bool),
    )
    assert (plan_steals(batch) >= 0).sum() == 0


def test_empty_steal_batch():
    batch = StealBatch(
        task_victim=np.zeros(0, np.int32),
        task_key=np.zeros(0, np.int32),
        task_cost=np.zeros(0, np.float32),
        task_compute=np.zeros(0, np.float32),
        occ=np.zeros(4, np.float32),
        nthreads=np.ones(4, np.int32),
        idle=np.ones(4, bool),
        running=np.ones(4, bool),
    )
    assert len(plan_steals(batch)) == 0


# ---------------------------------------------------------------- ops.amm


@pytest.mark.parametrize("max_rows", [None, 64])
def test_drops_match_python_policy_invariants(monkeypatch, max_rows):
    """Replaying device drops sequentially must satisfy the python
    oracle: never the last replica, never an excluded holder, always the
    max-projected-memory eligible holder at application time
    (reference active_memory_manager.py:290,527).  ``max_rows`` splits
    the round into device calls of that many rows."""
    from distributed_tpu.ops import amm as ops_amm

    rng = np.random.default_rng(2)
    R, W = 150, 12
    if max_rows is not None:
        monkeypatch.setattr(ops_amm, "MAX_ROWS", max_rows)
    holders = rng.random((R, W)) < 0.4
    holders[:, 0] |= ~holders.any(axis=1)  # at least one replica each
    excluded = (rng.random((R, W)) < 0.1) & holders
    nbytes = rng.uniform(1e3, 1e6, R).astype(np.float32)
    desired = np.maximum(1, rng.integers(1, 3, R))
    ndrop = np.maximum(holders.sum(1) - desired, 0).astype(np.int32)
    mem = (holders * nbytes[:, None]).sum(0).astype(np.float32)

    rounds = plan_drop_rounds(DropBatch(holders, excluded, nbytes, ndrop, mem))
    assert rounds, "no drops planned on an over-replicated state"

    h = holders.copy()
    m = mem.astype(np.float64).copy()
    left = ndrop.copy()
    for rnd in rounds:
        m0 = m.copy()  # drops in one round see the round-start projection
        seen_rows = set()
        for r, w in rnd:
            assert r not in seen_rows, "two drops for one task in a round"
            seen_rows.add(r)
            assert h[r, w], "dropped a replica that does not exist"
            assert not excluded[r, w], "dropped from an excluded holder"
            assert h[r].sum() >= 2, "dropped the last replica"
            assert left[r] > 0, "dropped more than requested"
            # max-projected-memory among this task's eligible holders at
            # round start (f32 kernel: allow rounding slack)
            elig = h[r] & ~excluded[r]
            assert m0[w] >= m0[elig].max() - max(1e-5 * m0[elig].max(), 1e-3), (r, w)
            h[r, w] = False
            left[r] -= 1
            m[w] = max(m[w] - nbytes[r], 0.0)
    # every satisfiable requested drop got planned
    planned_by_row = np.zeros(R, int)
    for rnd in rounds:
        for r, _ in rnd:
            planned_by_row[r] += 1
    for r in range(R):
        # bounded by the request, by eligible (non-excluded) holders, and
        # by the never-drop-the-last-replica floor over ALL holders
        satisfiable = max(0, min(
            int(ndrop[r]),
            int((holders[r] & ~excluded[r]).sum()),
            int(holders[r].sum()) - 1,
        ))
        assert planned_by_row[r] == satisfiable, (r, planned_by_row[r], satisfiable)


def test_drop_never_last_replica():
    holders = np.asarray([[True, True, False]])
    excluded = np.zeros((1, 3), bool)
    drops = plan_drops(DropBatch(
        holders, excluded,
        np.asarray([100.0], np.float32),
        np.asarray([5], np.int32),  # asks for more than possible
        np.asarray([100.0, 100.0, 0.0], np.float32),
    ))
    assert len(drops) == 1  # only one can go


def test_empty_drop_batch():
    assert plan_drops(DropBatch(
        np.zeros((0, 4), bool), np.zeros((0, 4), bool),
        np.zeros(0, np.float32), np.zeros(0, np.int32),
        np.zeros(4, np.float32),
    )) == []


# ------------------------------------------------------------- live paths


def _slow(i, delay=0.1):
    import time

    time.sleep(delay)
    return i


@gen_test(timeout=120)
async def test_device_stealing_live():
    """With the fleet gates lowered, a pinned-imbalance workload must be
    rebalanced by the DEVICE balance path (>= 1 device-planned steal)."""
    from distributed_tpu import config
    from distributed_tpu.client.client import Client
    from distributed_tpu.deploy.local import LocalCluster

    with config.set(
        {
            "scheduler.jax.enabled": True,
            "scheduler.jax.min-workers": 0,
            "scheduler.work-stealing-interval": "50ms",
        }
    ):
        async with LocalCluster(n_workers=4, threads_per_worker=1) as cluster:
            steal = cluster.scheduler.extensions["stealing"]
            steal.DEVICE_MIN_TASKS = 1  # tiny cluster: always use device
            async with Client(cluster.scheduler_address) as c:
                await c.submit(_slow, -1, delay=0.1).result()
                w0 = cluster.workers[0].address
                futs = c.map(
                    _slow, range(24), delay=0.1,
                    workers=[w0], allow_other_workers=True,
                )
                assert await asyncio.wait_for(c.gather(futs), 60) == list(
                    range(24)
                )
                assert steal.count >= 1, steal.log
                counts = {
                    w.address: len(w.data) for w in cluster.workers
                }
                assert sum(1 for v in counts.values() if v) >= 2, counts


@gen_test(timeout=120)
async def test_device_amm_drop_live():
    """Broadcast-replicated data beyond demand must be trimmed by the
    DEVICE ReduceReplicas path (>= 1 device-planned drop)."""
    from distributed_tpu import config
    from distributed_tpu.client.client import Client
    from distributed_tpu.deploy.local import LocalCluster
    from distributed_tpu.scheduler.amm import ReduceReplicas

    with config.set(
        {
            "scheduler.jax.enabled": True,
            "scheduler.jax.min-workers": 0,
        }
    ):
        async with LocalCluster(n_workers=4, threads_per_worker=1) as cluster:
            amm = cluster.scheduler.extensions["amm"]
            policy = next(
                p for p in amm.policies if isinstance(p, ReduceReplicas)
            )
            policy.DEVICE_MIN_TASKS = 1
            async with Client(cluster.scheduler_address) as c:
                futs = await c.scatter(list(range(6)), broadcast=True)
                state = cluster.scheduler.state
                # broadcast replication is async (acquire-replicas round
                # trips): wait for the replicas to land
                for _ in range(100):
                    if len(state.replicated_tasks) >= 6:
                        break
                    await asyncio.sleep(0.05)
                assert state.replicated_tasks
                n_before = sum(
                    len(state.tasks[f.key].who_has) for f in futs
                )
                amm.run_once()
                # drops are async worker round-trips; poll for the trim
                for _ in range(100):
                    await asyncio.sleep(0.05)
                    n_now = sum(
                        len(state.tasks[f.key].who_has) for f in futs
                    )
                    if n_now < n_before:
                        break
                else:
                    pytest.fail("device AMM round dropped nothing")
                # data still gatherable after the trim
                assert await c.gather(futs) == list(range(6))


# ---------------------------------------------------------------- rebalance


def _rebalance_setup(seed=0, N=400, W=16):
    rng = np.random.default_rng(seed)
    owner = rng.integers(0, W, N).astype(np.int32)
    nbytes = rng.uniform(1e3, 1e7, N).astype(np.float32)
    eligible = rng.random(N) < 0.9
    # worker memory includes ineligible keys too (ws.nbytes does)
    mem = np.zeros(W, np.float32)
    np.add.at(mem, owner, nbytes)
    # skew: first worker hoards extra
    mem[0] += mem.sum()
    return owner, nbytes, eligible, mem


def test_rebalance_kernel_invariants_and_band():
    from distributed_tpu.ops.rebalance import RebalanceBatch, plan_rebalance

    owner, nbytes, eligible, mem = _rebalance_setup()
    W = len(mem)
    mean = mem.sum() / W
    moves = plan_rebalance(
        RebalanceBatch(owner, nbytes, eligible, mem.copy()), rounds=32
    )
    assert moves, "skewed memory must produce moves"
    proj = mem.copy()
    seen = set()
    imbalance0 = proj.max() - proj.min()
    for key, src, dst in moves:
        assert key not in seen, "key moved twice"
        seen.add(key)
        assert eligible[key]
        assert owner[key] == src
        # python-policy invariants at application point
        assert proj[src] > mean, "sender was not above the mean"
        assert proj[dst] + nbytes[key] <= mean * 1.05 + 1, (
            "recipient pushed past the 1.05 band"
        )
        proj[src] -= nbytes[key]
        proj[dst] += nbytes[key]
    assert proj.max() - proj.min() <= imbalance0, "imbalance grew"
    # the hoarder actually drained toward the band
    assert proj[0] < mem[0]


def test_rebalance_kernel_noop_when_balanced():
    from distributed_tpu.ops.rebalance import RebalanceBatch, plan_rebalance

    rng = np.random.default_rng(1)
    W, N = 8, 160
    owner = np.repeat(np.arange(W), N // W).astype(np.int32)
    nbytes = np.full(N, 1e5, np.float32)
    mem = np.full(W, N // W * 1e5, np.float32)
    moves = plan_rebalance(
        RebalanceBatch(owner, nbytes, np.ones(N, bool), mem), rounds=8
    )
    assert moves == []

"""Graph partitioner kernel (ops/partition.py): quality, balance,
numpy/jax parity, and the live planner path."""

from __future__ import annotations

import asyncio

import numpy as np
import pytest

from distributed_tpu.ops.partition import (
    block_init,
    partition_jax,
    partition_numpy,
    partition_padded,
)


def _blockwise_graph(G: int):
    """mul grid + per-(i,j) reduction — the tensordot proxy."""
    keys: dict[str, int] = {}
    src, dst = [], []

    def add(k):
        keys[k] = len(keys)
        return keys[k]

    for i in range(G):
        for k in range(G):
            add(f"A-{i}-{k}")
    for i in range(G):
        for j in range(G):
            for k in range(G):
                m = add(f"m-{i}-{j}-{k}")
                src.append(keys[f"A-{i}-{k}"])
                dst.append(m)
            r = add(f"r-{i}-{j}")
            for k in range(G):
                src.append(keys[f"m-{i}-{j}-{k}"])
                dst.append(r)
    T = len(keys)
    return (
        keys,
        np.ones(T, np.float32),
        np.ones(len(src), np.float32),
        np.asarray(src, np.int32),
        np.asarray(dst, np.int32),
    )


def _comm_volume(labels, src, dst) -> int:
    """Unique (producer, consumer-worker) cross pairs — peer fetches
    after replica caching, which is what the cluster actually pays."""
    return len(
        {
            (s, labels[d])
            for s, d in zip(src.tolist(), dst.tolist())
            if labels[s] != labels[d]
        }
    )


def test_block_init_equal_load():
    d = np.ones(100, np.float32)
    lab = block_init(d, 10)
    counts = np.bincount(lab, minlength=10)
    assert (counts == 10).all()
    # heavier tasks shrink their block
    d2 = np.ones(100, np.float32)
    d2[:10] = 9.0
    lab2 = block_init(d2, 10)
    assert np.bincount(lab2, minlength=10)[0] < 10


def test_partition_beats_random_and_balances():
    keys, dur, wts, src, dst = _blockwise_graph(10)
    W = 8
    labels = partition_numpy(dur, wts, src, dst, W)
    assert labels.min() >= 0 and labels.max() < W
    vol = _comm_volume(labels, src, dst)
    rng = np.random.default_rng(0)
    vol_rand = _comm_volume(rng.integers(0, W, len(dur)), src, dst)
    vol_blocks = _comm_volume(block_init(dur, W), src, dst)
    # refinement beats both a random partition and its own init
    assert vol < 0.4 * vol_rand
    assert vol < vol_blocks
    # hard admission cap keeps load within ~cap of the average
    load = np.bincount(labels, minlength=W).astype(float)
    assert load.max() <= 1.5 * (len(dur) / W)


def test_partition_trivial_cases():
    assert len(partition_numpy(np.ones(0, np.float32), np.ones(0, np.float32),
                               np.zeros(0, np.int32), np.zeros(0, np.int32), 4)) == 0
    one = partition_numpy(np.ones(5, np.float32), np.ones(0, np.float32),
                          np.zeros(0, np.int32), np.zeros(0, np.int32), 1)
    assert (one == 0).all()


def test_partition_jax_matches_numpy():
    keys, dur, wts, src, dst = _blockwise_graph(8)
    W = 6
    init = block_init(dur, W)
    a = partition_numpy(dur, wts, src, dst, W, init=init)
    b = partition_jax(dur, wts, src, dst, W, init=init)
    # identical algorithm, identical deterministic updates
    assert (a == b).all()


def test_partition_padded_second_call_compiles_nothing():
    """The kernel is jitted once at module level: a second call with the
    same shapes and worker count reuses the compiled program, so a live
    plan under the dense cap pays no backend compile after the first."""
    import jax.monitoring

    keys, dur, wts, src, dst = _blockwise_graph(8)
    first = partition_padded(dur, wts, src, dst, 6)
    compiles: list[str] = []

    def listener(event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        again = partition_padded(dur, wts, src, dst, 6)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert compiles == []
    assert (first == again).all()


def test_live_planner_partitions_and_wins_locality():
    """Product path: LocalCluster with the partitioner planner (numpy
    engine for determinism), a blockwise graph, and plan consumption via
    deep home stacks.  Transfers must come in well under the no-plan
    run's."""
    from distributed_tpu import config
    from distributed_tpu.client.client import Client
    from distributed_tpu.deploy.local import LocalCluster

    def mul(a, b):
        return a * b

    def red(*xs):
        return sum(xs)

    async def run(jax_on: bool):
        from distributed_tpu.graph.spec import Graph, TaskRef, TaskSpec

        with config.set({
            "scheduler.jax.enabled": jax_on,
            "scheduler.jax.min-workers": 0,
            "scheduler.jax.min-batch": 64,
            "scheduler.jax.min-transfer-ratio": 0,
            "scheduler.jax.partitioner": "numpy",
            "scheduler.jax.sync-plan": True,
        }):
            async with LocalCluster(n_workers=8, threads_per_worker=1) as cluster:
                async with Client(cluster.scheduler_address) as c:
                    G = 8
                    g = Graph()
                    outs = []
                    for i in range(G):
                        for k in range(G):
                            g.tasks[f"s-{i}-{k}"] = TaskSpec(mul, (i, k))
                    for i in range(G):
                        for j in range(G):
                            for k in range(G):
                                g.tasks[f"m-{i}-{j}-{k}"] = TaskSpec(
                                    mul,
                                    (TaskRef(f"s-{i}-{k}"), TaskRef(f"s-{j}-{k}")),
                                )
                            g.tasks[f"r-{i}-{j}"] = TaskSpec(
                                red,
                                tuple(TaskRef(f"m-{i}-{j}-{k}") for k in range(G)),
                            )
                            outs.append(f"r-{i}-{j}")
                    futs = c.compute_graph(g, outs)
                    res = await asyncio.wait_for(
                        c.gather([futs[k] for k in outs]), 120
                    )
                    # correctness oracle
                    assert res[0] == sum((0 * k) * (0 * k) for k in range(G))
                    assert res[-1] == sum(
                        (7 * k) * (7 * k) for k in range(G)
                    )
                    served = sum(
                        getattr(w, "get_data_keys_served", 0)
                        for w in cluster.workers
                    )
                    pl = cluster.scheduler.state.placement
                    stats = (
                        (pl.plans_computed, pl.plan_hits) if pl else (0, 0)
                    )
                    return served, stats

    async def main():
        # bounded retries: the margin is normally huge (plan runs cut
        # transfers ~10x), but a CPU-starved CI box can stall the
        # no-plan run's stealing into an unusually LOW served_off —
        # both measurements are re-taken together so the comparison
        # stays within one load regime.  Attempts print their numbers
        # so an eventual failure is diagnosable from the CI log.
        import sys

        history = []
        for attempt in range(3):
            served_off, _ = await run(False)
            served_on, (plans, hits) = await run(True)
            history.append(
                (attempt, served_on, served_off, plans, hits)
            )
            print(
                f"# locality attempt {attempt}: served_on={served_on} "
                f"served_off={served_off} plans={plans} hits={hits}",
                file=sys.stderr,
            )
            assert plans >= 1
            assert hits > 0
            # the whole point: the plan must cut peer transfers hard
            if served_on < 0.75 * served_off:
                return
        raise AssertionError(history)

    asyncio.run(main())

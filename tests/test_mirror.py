"""Persistent SchedulerState fleet mirror (scheduler/mirror.py).

Contracts under test:

- **Oracle parity.**  Replaying random transition + worker-churn traces
  (add/remove/resize, status flips, replica add/drop, finishes/errors),
  the incrementally-maintained mirror equals the from-scratch snapshot
  bit-for-bit at every step (``SchedulerMirror.verify`` raises
  otherwise — the same contract the ``DTPU_MIRROR_CHECK`` runtime mode
  enforces).
- **Slot stability.**  Worker slots survive unrelated churn; tombstoned
  slots are reused; capacity doubles and never invalidates live rows.
- **O(dirty) cycles.**  With the mirror fresh, a kernel cycle performs
  no O(W) Python-loop fleet pack (``oracle_packs`` stays 0) and no
  fleet H2D upload (``rows_uploaded``/``full_uploads`` deltas are 0).
- **Steal comm-cost fidelity.**  The device balance prices a task at
  the best idle thief's TRUE cost (thief-resident dependency bytes
  subtracted), so a profitable steal toward data is no longer rejected
  by the old every-thief-pays-everything estimate; moves re-check the
  criterion with the per-thief oracle cost at apply time.
"""

from __future__ import annotations

import random

import pytest

from distributed_tpu.graph.spec import TaskRef, TaskSpec
from distributed_tpu.scheduler.mirror import (
    MirrorParityError,
    SchedulerMirror,
    oracle_fleet,
)
from distributed_tpu.scheduler.state import SchedulerState
from distributed_tpu.scheduler.stealing import WorkStealing
from distributed_tpu.utils.test import StubScheduler


def _noop(*args):
    return 0


def _state(n_workers=0, nthreads=1, **kwargs) -> SchedulerState:
    state = SchedulerState(
        validate=True, transition_counter_max=500_000, **kwargs
    )
    for i in range(n_workers):
        state.add_worker_state(
            f"tcp://127.0.0.1:{10000 + i}",
            nthreads=nthreads,
            memory_limit=2**30,
            name=f"w{i}",
        )
    return state


def _submit(state, rng, n_tasks, tag):
    keys: list[str] = []
    tasks: dict = {}
    deps: dict = {}
    for i in range(n_tasks):
        key = f"{tag}-{i}"
        n_deps = rng.randint(0, min(2, len(keys)))
        dep_keys = rng.sample(keys, n_deps) if n_deps else []
        tasks[key] = TaskSpec(_noop, tuple(TaskRef(d) for d in dep_keys))
        deps[key] = set(dep_keys)
        keys.append(key)
    state.update_graph_core(
        tasks, deps, keys[-max(3, n_tasks // 3):], client="client-1",
        stimulus_id=f"graph-{tag}",
    )
    return keys


def _flip_status(state, ws, status):
    """Mimic server.handle_worker_status_change's state side effects."""
    state.set_worker_status(ws, status)
    if status == "paused":
        state.running.discard(ws)
        state.idle.pop(ws.address, None)
        state.idle_task_count.discard(ws)
        state.splice_parked(ws.address)
    else:
        state.running.add(ws)
        state.check_idle_saturated(ws)
        recs = state.bulk_schedule_unrunnable_after_adding_worker(ws)
        recs.update(state.stimulus_queue_slots_maybe_opened("flip"))
        state.transitions(recs, "flip")


# ------------------------------------------------------- oracle parity


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_mirror_parity_random_trace(seed):
    """The incremental mirror is bit-identical to the from-scratch
    snapshot after EVERY step of a random transition + churn trace."""
    rng = random.Random(seed)
    state = _state(n_workers=3, nthreads=rng.choice([1, 2]))
    m = state.mirror
    assert isinstance(m, SchedulerMirror)
    m.verify()
    graph_n = 0
    for step in range(250):
        op = rng.random()
        workers = list(state.workers.values())
        if op < 0.06 and len(workers) < 12:
            state.add_worker_state(
                f"tcp://127.0.0.1:{20000 + step}",
                nthreads=rng.choice([1, 2, 4]),
                memory_limit=2**30,
            )
        elif op < 0.10 and len(workers) > 1:
            ws = rng.choice(workers)
            state.remove_worker_state(
                ws.address, stimulus_id=f"rm-{step}", safe=True
            )
        elif op < 0.13 and workers:
            state.set_worker_nthreads(
                rng.choice(workers), rng.choice([1, 2, 3, 4])
            )
        elif op < 0.18 and workers:
            ws = rng.choice(workers)
            _flip_status(
                state, ws,
                "paused" if ws in state.running else "running",
            )
        elif op < 0.28:
            graph_n += 1
            _submit(state, rng, rng.randint(4, 12), f"g{graph_n}")
        elif op < 0.34:
            # replica churn on memory tasks (the AMM's delta source)
            mem = [
                ts for ts in state.tasks.values() if ts.state == "memory"
            ]
            if mem and workers:
                ts = rng.choice(mem)
                ws = rng.choice(workers)
                if ws in ts.who_has:
                    if len(ts.who_has) > 1:
                        state.remove_replica(ts, ws)
                else:
                    state.add_replica(ts, ws)
        else:
            processing = [
                ts
                for ts in state.tasks.values()
                if ts.state == "processing"
            ]
            if processing:
                ts = rng.choice(processing)
                if rng.random() < 0.85:
                    state.stimulus_task_finished(
                        ts.key,
                        worker=ts.processing_on.address,
                        stimulus_id=f"fin-{step}",
                        nbytes=rng.randint(1, 10_000),
                        typename="int",
                    )
                else:
                    state.stimulus_task_erred(
                        ts.key,
                        worker=ts.processing_on.address,
                        stimulus_id=f"err-{step}",
                        exception_text="boom",
                    )
        state.validate_state()
        m.verify()  # raises MirrorParityError on any divergence
    assert m.oracle_failures == 0
    assert m.deltas_applied > 0


def test_mirror_check_mode_catches_unmarked_mutation():
    """DTPU_MIRROR_CHECK semantics: a mirrored-field mutation that
    bypasses the delta paths (exactly what the mirror-parity lint rule
    exists to prevent) is caught by the oracle check."""
    state = _state(n_workers=3)
    m = state.mirror
    m.check = True
    m.fleet_view()
    ws = next(iter(state.workers.values()))
    ws.occupancy += 1.0  # graft-lint: allow[mirror-parity] deliberately unmarked to prove the check fires
    with pytest.raises(MirrorParityError):
        m.fleet_view()
    assert m.oracle_failures == 1
    # marking the row heals the mirror
    m.mark(ws)
    m.fleet_view()


def test_oracle_fleet_matches_disabled_mirror_state():
    """A mirror=False state runs with no mirror at all (consumers use
    the from-scratch pack), and the oracle pack sees the same fleet."""
    state = _state(n_workers=3, mirror=False)
    assert state.mirror is None
    rows = oracle_fleet(state)
    assert set(rows) == set(state.workers)


# ------------------------------------------------------- slot stability


def test_slot_stability_tombstones_and_growth():
    state = _state(n_workers=6)
    m = state.mirror
    slots = {addr: ws.idx for addr, ws in state.workers.items()}
    assert sorted(slots.values()) == list(range(6))
    victims = list(state.workers)[1:4:2]
    for addr in victims:
        state.remove_worker_state(addr, stimulus_id="t", safe=True)
    survivors = {addr: ws.idx for addr, ws in state.workers.items()}
    # unrelated churn never moves a live worker's slot
    assert all(slots[a] == i for a, i in survivors.items())
    freed = sorted(slots[a] for a in victims)
    w_new = state.add_worker_state("tcp://fresh:1", nthreads=2)
    assert w_new.idx in freed  # tombstone reused, no growth
    cap0 = m.cap
    for i in range(cap0 + 1):
        state.add_worker_state(f"tcp://grow:{i}", nthreads=1)
    assert m.cap > cap0  # capacity doubled
    assert {ws.idx for ws in state.workers.values()} == {
        ws.idx for ws in state.workers.values()
    }
    m.verify()
    fv = m.fleet_view()
    assert fv.n_live == len(state.workers)
    # live_pos inverts slots for every live worker
    for ws in state.workers.values():
        assert fv.live_list[fv.live_pos[ws.idx]] is ws


# --------------------------------------------- O(dirty) cycle contracts


def test_fresh_mirror_cycle_no_pack_no_upload():
    state = _state(n_workers=8, nthreads=2)
    m = state.mirror
    m.fleet_view()
    dv = m.device_view()
    if dv is None:
        pytest.skip("jax unavailable")
    base = m.stats()
    # an untouched fleet: views are free — no refresh, no upload
    fv = m.fleet_view()
    dv = m.device_view()
    after = m.stats()
    assert after["rows_refreshed"] == base["rows_refreshed"]
    assert after["rows_uploaded"] == base["rows_uploaded"]
    assert after["full_uploads"] == base["full_uploads"]
    assert after["oracle_packs"] == 0
    # one worker's occupancy changes -> exactly one row refreshes and
    # uploads; never a full rebuild
    ws = next(iter(state.workers.values()))
    state._adjust_occupancy(ws, 1.5)
    m.fleet_view()
    m.device_view()
    after2 = m.stats()
    assert after2["rows_refreshed"] == after["rows_refreshed"] + 1
    assert after2["rows_uploaded"] == after["rows_uploaded"] + 1
    assert after2["full_uploads"] == after["full_uploads"]
    import numpy as np

    assert float(m.occupancy[ws.idx]) == np.float32(ws.occupancy)


def test_sharded_device_view_per_shard_scatter_and_growth():
    """Mesh-sharded fleet arrays (sharded_device_view): per-shard
    dirty-row accounting, zero rows on fresh cycles, values in lockstep
    with the host SoA, full per-shard re-pack only on capacity growth
    (growth remaps slot->shard, so nothing cheaper is sound)."""
    import numpy as np

    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    from distributed_tpu.ops.partition import make_engine_mesh

    mesh = make_engine_mesh(layout="4x2")  # workers axis: 2 shards
    state = _state(n_workers=16, nthreads=2)
    m = state.mirror
    v = m.sharded_device_view(mesh)
    assert v is not None
    ss = m.sharded_stats()
    assert ss["n_shards"] == 2
    assert ss["full_packs"] == [1, 1]
    assert ss["rows_uploaded"] == [0, 0]
    # fresh second view: nothing moves on any shard
    m.sharded_device_view(mesh)
    assert m.sharded_stats()["rows_uploaded"] == [0, 0]
    # dirty one worker per shard half; only the owning shard scatters
    rows_per_shard = m.cap // 2
    ws_lo = next(ws for ws in state.workers.values()
                 if ws.idx < rows_per_shard)
    state._adjust_occupancy(ws_lo, 2.5)
    v = m.sharded_device_view(mesh)
    ss = m.sharded_stats()
    assert ss["rows_uploaded"] == [1, 0], ss
    assert float(np.asarray(v["occupancy"])[ws_lo.idx]) == np.float32(
        ws_lo.occupancy
    )
    ws_hi = next(ws for ws in state.workers.values()
                 if ws.idx >= rows_per_shard)
    state.set_worker_nthreads(ws_hi, 4)
    v = m.sharded_device_view(mesh)
    ss = m.sharded_stats()
    assert ss["rows_uploaded"] == [1, 1], ss
    assert int(np.asarray(v["nthreads"])[ws_hi.idx]) == 4
    # growth: capacity doubles, slot->shard remaps, shards re-pack once
    for i in range(m.cap):  # force at least one _grow
        state.add_worker_state(
            f"tcp://127.0.0.1:{20000 + i}", nthreads=1,
            memory_limit=2**30, name=f"g{i}",
        )
    v = m.sharded_device_view(mesh)
    ss = m.sharded_stats()
    assert ss["full_packs"] == [2, 2], ss
    # ...and values still match the host SoA everywhere
    for name in ("nthreads", "occupancy", "running"):
        np.testing.assert_array_equal(
            np.asarray(v[name]), getattr(m, name)
        )
    m.verify()


def test_sharded_device_view_indivisible_mesh_returns_none():
    """A mesh whose workers axis cannot divide the slot capacity gets
    the replicated fallback (None), never a crash."""
    import jax

    if len(jax.devices()) < 3:
        pytest.skip("needs >= 3 devices")
    import numpy as np
    from jax.sharding import Mesh

    state = _state(n_workers=4)
    mesh = Mesh(
        np.asarray(jax.devices()[:3]).reshape(1, 3),
        axis_names=("tasks", "workers"),
    )
    assert state.mirror.sharded_device_view(mesh) is None


def test_shared_fleet_view_feeds_steal_and_amm_without_repack():
    """One dirty flush serves a whole cycle: steal + AMM both consume
    the mirror with zero additional refreshes and zero Python packs."""
    from distributed_tpu.scheduler.amm import (
        ActiveMemoryManagerExtension,
        ReduceReplicas,
    )

    state = _state(n_workers=6, nthreads=1)
    sched = StubScheduler(state)
    stealing = WorkStealing(sched)
    amm = ActiveMemoryManagerExtension(
        sched, policies=[ReduceReplicas()], register=False, start=False
    )
    m = state.mirror
    # a few replicated memory tasks for the AMM half
    for i in range(4):
        key = f"mem-{i}"
        state.new_task(key, None).priority = (0,)
        state._transition(
            key, "memory", "seed", nbytes=1000,
            worker=list(state.workers)[0],
        )
        for ws in list(state.workers.values())[1:3]:
            state.add_replica(state.tasks[key], ws)
    m.fleet_view()
    base = m.stats()
    fv1 = m.fleet_view()
    amm.run_once()
    fv2 = m.fleet_view()
    after = m.stats()
    assert after["oracle_packs"] == 0
    assert after["oracle_failures"] == 0
    assert after["rows_refreshed"] == base["rows_refreshed"]
    # device residency: at most the one initial whole-fleet upload
    assert after["full_uploads"] <= 1
    assert after["rows_uploaded"] == base["rows_uploaded"] == 0, after
    assert fv1.slots is fv2.slots  # membership untouched, view reused
    # the AMM round dropped replicas of the over-replicated keys
    assert any(
        msg.get("op") == "remove-replicas" and msg.get("keys")
        for _, wmsgs in sched.sent
        for msgs in wmsgs.values()
        for msg in msgs
    )
    m.verify()


# --------------------------------------- device steal comm-cost fidelity


def _steal_state(dep_on_thief: bool):
    """w0: 4 stealable 0.1 s tasks + the dep replica; w1 idle.  The dep
    is big enough that pricing the steal at full transfer cost fails the
    criterion, while the true cost to a thief already holding the dep
    passes it."""
    state = _state(n_workers=2, nthreads=1)
    sched = StubScheduler(state)
    ext = WorkStealing(sched)
    w0, w1 = state.workers.values()
    state.new_task_prefix("sl").add_duration(0.1)
    dep = state.new_task("data", None)
    dep.priority = (0,)
    state._transition("data", "memory", "seed", nbytes=40_000_000,
                      worker=w0.address)
    if dep_on_thief:
        state.add_replica(dep, w1)
    tasks = {
        f"sl-{i}": TaskSpec(_noop, (TaskRef("data"),)) for i in range(4)
    }
    state.update_graph_core(
        tasks, {k: {"data"} for k in tasks}, list(tasks),
        client="client-1",
        annotations_by_key={
            k: {"workers": [w0.address], "allow_other_workers": True}
            for k in tasks
        },
        stimulus_id="graph-steal",
    )
    assert all(
        state.tasks[k].processing_on is w0 for k in tasks
    ), {k: state.tasks[k].state for k in tasks}
    return state, sched, ext, w0, w1


def test_device_steal_accounts_thief_resident_bytes():
    """Regression (over-estimate wrongly rejected a profitable steal):
    the idle thief already holds the 40 MB dependency, so the move is
    nearly free for it — the old full-cost estimate priced it at 0.5 s
    and refused."""
    state, sched, ext, w0, w1 = _steal_state(dep_on_thief=True)
    idle = [ws for ws in state.idle.values() if ws in state.running]
    assert w1 in idle
    ext._balance_device(idle)  # no loop -> plans inline
    thieves = {info.thief for info in ext.in_flight.values()}
    assert thieves == {w1}, (ext.in_flight, sched.sent)
    assert state.mirror.oracle_packs == 0


def test_device_steal_still_rejects_when_no_thief_holds_data():
    """Control: same shape, dep only on the victim — every thief truly
    pays the full transfer, so the criterion correctly refuses."""
    state, sched, ext, w0, w1 = _steal_state(dep_on_thief=False)
    idle = [ws for ws in state.idle.values() if ws in state.running]
    ext._balance_device(idle)
    assert not ext.in_flight, ext.in_flight


def test_device_steal_drains_paused_victim():
    """A paused worker keeps its pile, and the pause handler re-marks
    its homed tasks stealable precisely so the balancer drains them:
    the device victim selection must include non-running workers (it
    briefly filtered on the mirror's running bit and orphaned them)."""
    state, sched, ext, w0, w1 = _steal_state(dep_on_thief=True)
    _flip_status(state, w0, "paused")
    # not via the saturated shortcut — force the array-mask victim scan
    state.saturated.discard(w0)
    state.mirror.mark(w0)
    idle = [ws for ws in state.idle.values() if ws in state.running]
    assert w1 in idle and w0 not in state.running
    ext._balance_device(idle)
    assert {info.thief for info in ext.in_flight.values()} == {w1}, (
        ext.in_flight
    )


def test_device_steal_mirror_and_oracle_paths_agree():
    """The no-mirror from-scratch pack (the oracle path) plans the same
    moves as the mirror-fed pack on identical fleets."""
    results = []
    for use_mirror in (True, False):
        state = _state(n_workers=2, nthreads=1, mirror=use_mirror)
        sched = StubScheduler(state)
        ext = WorkStealing(sched)
        w0, w1 = state.workers.values()
        state.new_task_prefix("sl").add_duration(0.1)
        dep = state.new_task("data", None)
        dep.priority = (0,)
        state._transition("data", "memory", "seed", nbytes=40_000_000,
                          worker=w0.address)
        state.add_replica(dep, w1)
        tasks = {
            f"sl-{i}": TaskSpec(_noop, (TaskRef("data"),))
            for i in range(4)
        }
        state.update_graph_core(
            tasks, {k: {"data"} for k in tasks}, list(tasks),
            client="client-1",
            annotations_by_key={
                k: {"workers": [w0.address], "allow_other_workers": True}
                for k in tasks
            },
            stimulus_id="graph-steal",
        )
        idle = [ws for ws in state.idle.values() if ws in state.running]
        ext._balance_device(idle)
        results.append(
            sorted(
                (key, info.thief.name) for key, info in ext.in_flight.items()
            )
        )
    assert results[0] == results[1], results

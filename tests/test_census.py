"""State census + retention sentinel tests (diagnostics/census.py;
docs/observability.md "State census & retention"): registration
completeness, walk-vs-counter audits, quiesce-clean gates, the
deliberately re-introduced unknown_durations leak, and the leak fixes
this instrument drove (worker forget cascade, stealing overlays,
misrouted-completion free-keys, memtrace refcounting)."""

from __future__ import annotations

import asyncio
from collections import defaultdict, deque

import pytest

from distributed_tpu import config
from distributed_tpu.diagnostics.census import (
    CensusParityError,
    CensusResidueError,
    RetentionSentinel,
    StateCensus,
    build_scheduler_census,
    build_worker_census,
)
from distributed_tpu.scheduler.state import SchedulerState
from distributed_tpu.utils import HeapSet, OrderedSet
from distributed_tpu.worker.state_machine import (
    ComputeTaskEvent,
    FreeKeysEvent,
    GatherDepNetworkFailureEvent,
    WorkerState,
)

from conftest import gen_test

CONTAINER_TYPES = (dict, set, frozenset, list, deque, defaultdict,
                   HeapSet, OrderedSet)


def _container_attrs(obj) -> list[str]:
    return [
        name for name, value in vars(obj).items()
        if isinstance(value, CONTAINER_TYPES)
    ]


# ------------------------------------------------- registration completeness


def test_registration_completeness_scheduler():
    """Every dict/set/deque/list attribute SchedulerState.__init__
    assigns must be census-registered (a family's ``attrs``) or
    allowlisted with a mandatory reason — new state cannot silently
    dodge the census."""
    state = SchedulerState()
    covered = state.census.covered_attrs()
    missing = [a for a in _container_attrs(state) if a not in covered]
    assert not missing, (
        f"SchedulerState container attrs not covered by the census: "
        f"{missing} — register them in "
        f"diagnostics.census.build_scheduler_census (attrs=...) or "
        f"allowlist them there with a reason (allow_attr)"
    )


def test_registration_completeness_worker():
    state = WorkerState(nthreads=1)
    covered = state.census.covered_attrs()
    missing = [a for a in _container_attrs(state) if a not in covered]
    assert not missing, (
        f"WorkerState container attrs not covered by the census: "
        f"{missing} — register them in "
        f"diagnostics.census.build_worker_census (attrs=...) or "
        f"allowlist them there with a reason (allow_attr)"
    )


def test_attr_allowlist_requires_reason():
    c = StateCensus("x")
    with pytest.raises(AssertionError):
        c.allow_attr("foo", "")
    with pytest.raises(AssertionError):
        c.register("bar", lambda: 0, allow=True, reason="")


# --------------------------------------------------------- audits must fire


def test_audit_catches_maintained_counter_drift():
    """Mirror-parity style: corrupting a maintained counter makes the
    walk audit raise (the check that the engines' bookkeeping cannot
    silently drift from container truth)."""
    state = SchedulerState()
    state.new_task("drift-k", None)
    state.census.audit()  # clean
    next(iter(state.task_groups.values())).states["memory"] += 1
    with pytest.raises(CensusParityError, match="tasks.counted"):
        state.census.audit()


def test_audit_catches_ledger_open_row_drift():
    state = SchedulerState()
    h = state.ledger.file(
        "placement", "k", "p", "tcp://w:1", "stim", 0.1, 0.1, False,
    )
    state.census.audit()
    # tamper the derived-counter inputs without closing the ring row
    state.ledger._memory_joins += 1
    with pytest.raises(CensusParityError, match="ledger.open"):
        state.census.audit()
    state.ledger._memory_joins -= 1
    state.ledger.join_row(h, "memory")
    state.census.audit()


def test_census_check_env_parsing(monkeypatch):
    from distributed_tpu.diagnostics.census import census_check_enabled

    for off in ("", "0", "false", "off", "no", "False", "OFF"):
        monkeypatch.setenv("DTPU_CENSUS_CHECK", off)
        assert not census_check_enabled()
    monkeypatch.setenv("DTPU_CENSUS_CHECK", "1")
    assert census_check_enabled()


# ------------------------------------------------------ quiesce + findings


def test_residue_finding_names_holding_container():
    """A retained TaskState in unknown_durations produces a finding
    whose gc.get_referrers holder chain names the registered family."""
    state = SchedulerState()
    ts = state.new_task("leak-k", None)
    state.unknown_durations.setdefault("leak", set()).add(ts)
    del state.tasks["leak-k"]  # simulate the forget that missed the set
    findings = state.census.residue()
    fams = {f["family"] for f in findings}
    assert "tasks.unknown-durations" in fams
    assert "tasks.unknown-durations.members" in fams
    state.census.enrich_findings(findings)
    member = next(
        f for f in findings
        if f["family"] == "tasks.unknown-durations.members"
    )
    assert member["sample"], member
    assert "leak-k" in member["sample"][0]
    assert any(
        h.startswith("tasks.unknown-durations") for h in member["holders"]
    ), member


def test_quiesced_and_snapshot_shape():
    state = SchedulerState()
    assert state.census.quiesced()
    recs = state.census.snapshot(deep=True)
    head = recs[0]
    assert head["type"] == "census-head"
    assert head["quiesced"] is True
    fams = [r for r in recs if r["type"] == "census"]
    assert len(fams) == len(state.census.families)
    allow = {r["family"]: r.get("allow") for r in fams}
    # allowlisted families carry their reason in the snapshot
    assert allow["trace.ring"]
    assert allow["tasks"] is None
    state.new_task("q-k", None)
    assert not state.census.quiesced()


# --------------------------------------- the acceptance demonstration test


class _PoplessDict(dict):
    """Re-introduces the PR 10 ``unknown_durations`` leak: the pop on
    first completed duration becomes a no-op, so every pre-first-
    duration TaskState is pinned forever (append-only dict again)."""

    def pop(self, *a, **k):  # noqa: ARG002 - deliberately inert
        return None


def test_deliberate_unknown_durations_leak_is_caught():
    """The quiesce gate catches the deliberately re-introduced
    unknown_durations leak, with a referrer sample naming the holding
    container — the acceptance demonstration (ISSUE 15)."""
    from distributed_tpu.sim.chaos import _base_sim, _base_trace
    from distributed_tpu.sim.validate import check_census_clean

    sim = _base_sim(8, 11)
    sim.state.unknown_durations = _PoplessDict()
    _base_trace(11).start(sim)
    sim.run()
    with pytest.raises(CensusResidueError) as ei:
        check_census_clean(sim)
    msg = str(ei.value)
    assert "tasks.unknown-durations.members" in msg
    member = next(
        f for f in sim.state.census.findings
        if f["family"] == "tasks.unknown-durations.members"
    )
    assert member["count"] > 0
    assert member["holders"], member
    assert any(
        h.startswith("tasks.unknown-durations") for h in member["holders"]
    ), member


def test_sim_quiesce_gate_clean_on_healthy_run():
    from distributed_tpu.sim.chaos import _base_sim, _base_trace
    from distributed_tpu.sim.validate import check_census_clean

    sim = _base_sim(8, 12)
    _base_trace(12).start(sim)
    sim.run()
    out = check_census_clean(sim)
    assert out["census_clean"] is True
    assert out["censuses"] == 9  # scheduler + 8 workers
    # post-gate: literally zero TaskStates resident anywhere
    assert not sim.state.tasks
    assert all(not w.state.tasks for w in sim.workers.values())
    assert all(not w.state.data for w in sim.workers.values())


# ------------------------------------------------------- sentinel behavior


def test_sentinel_flags_growing_family_once_and_rearms():
    clock = [0.0]
    c = StateCensus("t", clock=lambda: clock[0])
    n = [0]
    c.register("grow", lambda: n[0], sample=lambda: iter(()))
    c.motion = ()
    from distributed_tpu.tracing import FlightRecorder

    tr = FlightRecorder(enabled=True, ring_size=64)
    s = RetentionSentinel(
        c, trace=tr, slope_threshold=10.0, min_count=100,
    )
    # grows 1000 members/second, above the floor: flags exactly once
    for _ in range(6):
        clock[0] += 1.0
        n[0] += 1000
        s.tick()
    assert s.leaks_flagged == 1
    leaks = [e for e in tr.tail() if e["cat"] == "leak"]
    assert len(leaks) == 1
    assert leaks[0]["name"] == "grow"
    assert leaks[0]["n"] >= 100
    # growth stops -> slope EWMA decays below half threshold -> re-arms
    for _ in range(20):
        clock[0] += 1.0
        s.tick()
    fam = c.families["grow"]
    assert not fam.flagged
    # a second episode flags again
    for _ in range(6):
        clock[0] += 1.0
        n[0] += 1000
        s.tick()
    assert s.leaks_flagged == 2


def test_sentinel_quiesce_edge_runs_residue_once():
    clock = [0.0]
    c = StateCensus("t", clock=lambda: clock[0])
    busy = [1]
    resid = [0]
    c.register("work", lambda: busy[0])
    c.register("junk", lambda: resid[0], sample=lambda: iter(()))
    c.motion = ("work",)
    s = RetentionSentinel(c, slope_threshold=1e9, min_count=10**9)
    clock[0] += 1.0
    assert s.tick() == []          # busy: no quiesce check
    resid[0] = 3
    busy[0] = 0
    clock[0] += 1.0
    fresh = s.tick()               # quiesce edge: diff runs
    assert [f["family"] for f in fresh] == ["junk"]
    clock[0] += 1.0
    assert s.tick() == []          # still quiesced: no re-fire
    busy[0] = 1
    clock[0] += 1.0
    s.tick()
    busy[0] = 0
    resid[0] = 0
    clock[0] += 1.0
    assert s.tick() == []          # clean quiesce: no findings


def test_census_check_mode_audits_throughout_sim(monkeypatch):
    """DTPU_CENSUS_CHECK=1 arms periodic walk-vs-counter audits on the
    sim's virtual clock — every census, throughout the run, not only at
    the quiesce gate."""
    monkeypatch.setenv("DTPU_CENSUS_CHECK", "1")
    from distributed_tpu.sim.chaos import scenario_worker_death

    # the scenario's curated default seed: chaos seeds are chosen to
    # converge (an unconvergeable workload loops its periodic ticks on
    # the virtual clock forever, by design)
    sim, report = scenario_worker_death()
    assert sim.counters["census_audits"] > 0
    assert sim.state.census.audits > sim.counters["census_audits"]  # + gate
    assert sim.state.census.audit_failures == 0
    assert report["census"]["census_clean"] is True


# ------------------------------------------------- leak fixes (regressions)


def test_worker_forget_cascades_to_orphaned_released_deps():
    """The released->forgotten arm recommends forgetting orphaned
    released dependencies — the census-found retention that pinned
    ~14% of WTaskStates (the old code had a no-op `pass` there)."""
    ws = WorkerState(nthreads=1)
    ws.handle_stimulus(
        ComputeTaskEvent(
            stimulus_id="s1", key="b", run_spec=None, priority=(1,),
            who_has={"a": ["tcp://peer:1"]}, nbytes={"a": 8},
            duration=0.1, resource_restrictions={}, actor=False,
            annotations={}, span_id=None,
        )
    )
    assert set(ws.tasks) == {"a", "b"}
    # free the dependent, then fail the in-flight fetch of the dep:
    # BOTH must forget (a becomes a released orphan the moment its
    # parked fetch resolves; has_what/who_has rows must go with them)
    ws.handle_stimulus(FreeKeysEvent(stimulus_id="s2", keys=("b",)))
    ws.handle_stimulus(
        GatherDepNetworkFailureEvent(
            stimulus_id="s3", worker="tcp://peer:1", keys=("a",),
        )
    )
    assert not ws.tasks, dict(ws.tasks)
    assert not ws.has_what, dict(ws.has_what)
    deep = ws.census.counts(deep=True)
    assert not any(
        v for k, v in deep.items() if not ws.census.families[k].allow
    ), deep


def test_worker_compute_task_severs_stale_dependency_edges():
    """A re-targeted compute-task whose who_has no longer names a
    previously-wired dependency severs the stale edge (the scheduler's
    dep list is authoritative) instead of wedging waiting->ready."""
    ws = WorkerState(nthreads=1)
    ws.handle_stimulus(
        ComputeTaskEvent(
            stimulus_id="s1", key="t", run_spec=None, priority=(1,),
            who_has={"old": ["tcp://peer:1"]}, nbytes={"old": 8},
            duration=0.1, resource_restrictions={}, actor=False,
            annotations={}, span_id=None,
        )
    )
    ws.handle_stimulus(FreeKeysEvent(stimulus_id="s2", keys=("t",)))
    ws.handle_stimulus(
        GatherDepNetworkFailureEvent(
            stimulus_id="s2b", worker="tcp://peer:1", keys=("old",),
        )
    )
    # re-submission with a different dep set; 'old' must not survive
    ws.handle_stimulus(
        ComputeTaskEvent(
            stimulus_id="s3", key="t", run_spec=None, priority=(1,),
            who_has={"new": ["tcp://peer:2"]}, nbytes={"new": 8},
            duration=0.1, resource_restrictions={}, actor=False,
            annotations={}, span_id=None,
        )
    )
    ts = ws.tasks["t"]
    assert {d.key for d in ts.dependencies} == {"new"}
    assert "old" not in ws.tasks


def test_stealing_overlays_deleted_and_pruned():
    """in_flight_tasks rows delete at zero, occupancy rows for a
    removed worker are purged, and a stimulus-mismatched confirm still
    reverts its window's overlays (census-found residue family
    steal.in-flight-*)."""
    from distributed_tpu.scheduler.stealing import WorkStealing
    from distributed_tpu.utils.test import StubScheduler

    state = SchedulerState()
    sched = StubScheduler(state)
    steal = WorkStealing(sched)
    v = state.add_worker_state("tcp://v:1", nthreads=1)
    t = state.add_worker_state("tcp://t:1", nthreads=1)
    ts = state.new_task("sk", object())
    ts.state = "processing"
    ts.processing_on = v
    v.processing[ts] = 1.0

    steal.seed_in_flight(ts, v, t, 1.0, 0.5, "stim-1")
    assert steal.in_flight_tasks[v] == 1
    # mismatched (forged/stale) confirm consumes the window AND reverts
    asyncio.run(
        steal.move_task_confirm(key="sk", state="ready",
                                stimulus_id="forged")
    )
    assert "sk" not in steal.in_flight
    assert not steal.in_flight_tasks     # zero rows deleted
    assert not steal.in_flight_occupancy  # bulk clear ran

    # overlay rows for a removed worker are purged even while other
    # windows stay open
    ts2 = state.new_task("sk2", object())
    ts2.state = "processing"
    ts2.processing_on = v
    v.processing[ts2] = 1.0
    steal.seed_in_flight(ts2, v, t, 1.0, 0.5, "stim-2")
    steal.remove_worker(sched, "tcp://t:1")
    assert t not in steal.in_flight_occupancy
    assert t not in steal.in_flight_tasks


def test_combined_occupancy_read_does_not_materialize_rows():
    from distributed_tpu.scheduler.stealing import WorkStealing
    from distributed_tpu.utils.test import StubScheduler

    state = SchedulerState()
    steal = WorkStealing(StubScheduler(state))
    ws = state.add_worker_state("tcp://w:1", nthreads=1)
    assert steal._combined_occupancy(ws) == 0.0
    assert not steal.in_flight_occupancy


def test_misrouted_completion_answers_free_keys():
    """A completion from a worker that is not processing_on gets a
    free-keys answer: the reporter's unaccounted copy must drop instead
    of outliving the task (census-found via the partition scenario)."""
    state = SchedulerState()
    w0 = state.add_worker_state("tcp://w:0", nthreads=1)
    state.add_worker_state("tcp://w:1", nthreads=1)
    state.client_desires_keys(["mk"], "c")
    cm, wm = state.update_graph_core(
        {"mk": object()}, {"mk": set()}, ["mk"], client="c",
        priorities={"mk": (0,)}, stimulus_id="g",
    )
    ts = state.tasks["mk"]
    assert ts.state == "processing"
    other = "tcp://w:1" if ts.processing_on is w0 else "tcp://w:0"
    cm, wm = state.stimulus_task_finished(
        "mk", other, "misroute-stim", nbytes=8,
    )
    assert wm == {other: [{
        "op": "free-keys", "keys": ["mk"], "stimulus_id": "misroute-stim",
    }]}
    assert ts.state == "processing"  # still awaiting the real worker


def test_unreachable_submission_is_culled_at_ingest():
    """A submitted task no requested key needs, nothing depends on and
    no client wants is forgotten at ingest instead of sitting released
    forever (buggy/hostile clients at production scale)."""
    state = SchedulerState()
    state.add_worker_state("tcp://w:0", nthreads=1)
    state.client_desires_keys(["want"], "c")
    state.update_graph_core(
        {"want": object(), "junk": object()},
        {"want": set(), "junk": set()},
        ["want"], client="c",
        priorities={"want": (0,), "junk": (1,)}, stimulus_id="g",
    )
    assert "junk" not in state.tasks
    assert "want" in state.tasks
    # the cull is a real released->forgotten story row, not a silent drop
    assert [(r[1], r[2]) for r in state.story("junk")] == [
        ("released", "forgotten"),
    ]


def test_groups_stale_last_worker_cleared_on_removal():
    state = SchedulerState()
    ws = state.add_worker_state("tcp://w:0", nthreads=1)
    state.new_task("gk", object())
    tg = next(iter(state.task_groups.values()))
    tg.last_worker = ws
    tg.last_worker_tasks_left = 3
    assert state.census.families["groups.stale-last-worker"].probe() == 0
    state.remove_worker_state("tcp://w:0", stimulus_id="rm")
    assert tg.last_worker is None
    assert state.census.families["groups.stale-last-worker"].probe() == 0


def test_telemetry_stale_links_walk():
    state = SchedulerState()
    state.add_worker_state("tcp://w:0", nthreads=1)
    tel = state.telemetry
    tel.fold_rows([["tcp://gone:1", "tcp://gone:2", 1000, 0.01, 1]],
                  reporter="tcp://gone:2")
    assert state.census.families["telemetry.links.stale"].probe() == 1
    # EITHER endpoint dead counts — the dominant leak shape is a LIVE
    # reporter re-creating a link to a removed peer
    tel.fold_rows([["tcp://w:0", "tcp://gone:3", 1000, 0.01, 1]],
                  reporter="tcp://w:0")
    assert state.census.families["telemetry.links.stale"].probe() == 2
    tel.forget_worker("tcp://gone:1")
    tel.forget_worker("tcp://gone:2")
    tel.forget_worker("tcp://gone:3")
    assert state.census.families["telemetry.links.stale"].probe() == 0


# ------------------------------------------------------------ memtrace fix


def test_memtrace_refcounted_per_owner():
    """With in-process workers one worker's stop must not kill the
    process-global trace for every other server (ISSUE 15 satellite)."""
    import tracemalloc

    from distributed_tpu.diagnostics import memtrace

    was_tracing = tracemalloc.is_tracing()
    try:
        memtrace.start_trace(owner="w-a")
        memtrace.start_trace(owner="w-b")
        assert tracemalloc.is_tracing()
        out = memtrace.stop_trace(owner="w-a")
        assert out["tracing"] is True, "other owner still tracing"
        assert tracemalloc.is_tracing()
        out = memtrace.stop_trace(owner="w-b")
        assert out["tracing"] is False
        assert not tracemalloc.is_tracing()
        # stale double-stop stays a no-op
        memtrace.stop_trace(owner="w-b")
        # an EXTERNALLY-armed trace is never memtrace's to stop: a
        # worker closing (its close path releases its hold defensively)
        # must not kill the user's own tracemalloc session
        tracemalloc.start()
        memtrace.stop_trace(owner="closing-worker")
        assert tracemalloc.is_tracing()
        tracemalloc.stop()
    finally:
        memtrace._owners.clear()
        memtrace._started_here = False
        if was_tracing and not tracemalloc.is_tracing():
            tracemalloc.start()
        elif not was_tracing and tracemalloc.is_tracing():
            tracemalloc.stop()


# --------------------------------------------------------------- live wiring


@gen_test(timeout=30)
async def test_heartbeat_fold_ignores_unregistered_link_endpoints():
    """Link rows naming a peer that already left (or never completed
    registration) do not re-create pruned LinkStats entries — the
    census's telemetry.links.stale family stays zero."""
    from distributed_tpu.scheduler.server import Scheduler

    async with Scheduler(listen_addr="inproc://") as s:
        s.state.add_worker_state("tcp://w:1", nthreads=1)
        s.state.add_worker_state("tcp://w:2", nthreads=1)
        await s.heartbeat_worker(
            address="tcp://w:1",
            link_telemetry=[
                ["tcp://w:2", "tcp://w:1", 1000, 0.01, 1],   # live pair
                ["tcp://ghost:9", "tcp://w:1", 1000, 0.01, 1],  # stale
            ],
        )
        tel = s.state.telemetry
        assert ("tcp://w:2", "tcp://w:1") in tel.links
        assert ("tcp://ghost:9", "tcp://w:1") not in tel.links
        assert s.state.census.families["telemetry.links.stale"].probe() == 0


@gen_test(timeout=60)
async def test_census_route_rpc_and_dump():
    """/census JSONL on both roles, the get_census RPC, the
    dtpu_census_* metric families, and the cluster-dump census artifact
    (DumpArtefact.census_counts/census_findings)."""
    import json as _json

    from distributed_tpu.diagnostics.cluster_dump import DumpArtefact
    from test_observability import http_get, new_cluster

    from distributed_tpu.client.client import Client

    async with await new_cluster(
        n_workers=1,
        scheduler_kwargs={"http_port": 0},
        worker_kwargs={"http_port": 0},
    ) as cluster:
        async with Client(cluster.scheduler_address) as c:
            futs = c.map(lambda x: x + 1, range(4))
            await c.gather(futs)

            # scheduler route
            port = cluster.scheduler.http_server.port
            status, body = await http_get(port, "/census")
            assert status == 200
            recs = [_json.loads(ln) for ln in body.splitlines() if ln]
            assert recs[0]["type"] == "census-head"
            assert recs[0]["role"] == "scheduler"
            fams = {r["family"] for r in recs if r["type"] == "census"}
            assert "tasks" in fams and "ledger.open" in fams

            # worker route
            w = cluster.workers[0]
            wport = w.http_server.port
            status, body = await http_get(wport, "/census")
            assert status == 200
            wrecs = [_json.loads(ln) for ln in body.splitlines() if ln]
            assert wrecs[0]["role"] == "worker"

            # metrics families
            status, body = await http_get(port, "/metrics")
            assert b"dtpu_census_count{" in body
            assert b"dtpu_census_quiesced" in body

            # RPC twin, deep (edge walks included)
            deep = await c.scheduler.get_census(deep=True)
            fams = {r["family"] for r in deep if r.get("type") == "census"}
            assert "edges.dependencies" in fams

            # cluster dump artifact
            dump = DumpArtefact(await c.dump_cluster_state())
            counts = dump.census_counts()
            assert counts.get("tasks", -1) >= 0
            assert "edges.dependencies" in counts  # dump census is deep
            assert dump.worker_census  # every worker shipped its census
            addr = next(iter(dump.worker_census))
            assert "wtasks" in dump.census_counts(addr)
            assert dump.census_findings() == []


@gen_test(timeout=60)
async def test_local_cluster_teardown_census_clean():
    """A LocalCluster that computed and released everything quiesces
    census-clean on both roles — the live half of the quiesce contract
    (durability dirty sets exempt by snapshot cadence; none here)."""
    from test_observability import new_cluster

    from distributed_tpu.client.client import Client

    async with await new_cluster(n_workers=2) as cluster:
        async with Client(cluster.scheduler_address) as c:
            futs = c.map(lambda x: x * 2, range(10))
            await c.gather(futs)
            for f in futs:
                f.release()
            del futs
            s = cluster.scheduler.state
            for _ in range(100):
                if not s.tasks and s.census.quiesced():
                    break
                await asyncio.sleep(0.05)
            assert s.census.quiesced(), {
                m: s.census.families[m].probe() for m in s.census.motion
            }
            s.census.audit()
            assert s.census.residue() == []
            n_families = len(s.census.families)
            for w in cluster.workers:
                for _ in range(100):
                    if not w.state.tasks:
                        break
                    await asyncio.sleep(0.05)
                w.state.census.audit()
                assert w.state.census.residue() == [], w.address
                n_families += len(w.state.census.families)
            # three roles' censuses, each of its whole state
            assert n_families > 100, n_families
            # the RPC twin serves the same truth
            head = (await c.scheduler.get_census(deep=True))[0]
            assert head["quiesced"] is True, head

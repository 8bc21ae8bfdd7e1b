"""Deterministic sans-io cluster simulator (distributed_tpu/sim;
docs/simulator.md): determinism contract, chaos scenarios against the
drift-gated state-machine model, sim<->live journal replay parity, the
policy A/B driver, and the virtual-clock seams.
"""

from __future__ import annotations

import json
import os

import pytest

from distributed_tpu.diagnostics.flight_recorder import (
    replay_stimulus_trace,
    transition_stream,
    verify_journal,
)
from distributed_tpu.sim import (
    ClusterSim,
    JournalTrace,
    LinkProfile,
    SyntheticDag,
    VirtualClock,
    run_ab,
)
from distributed_tpu.sim.chaos import (
    scenario_partition,
    scenario_poison_flood,
    scenario_straggler,
    scenario_worker_death,
)
from distributed_tpu.sim.validate import check_no_lost_keys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_model() -> dict:
    out = {}
    for role in ("scheduler", "worker"):
        path = os.path.join(REPO_ROOT, "docs", "state_machine", f"{role}.json")
        with open(path) as f:
            out[role] = json.load(f)
    return out


MODEL = load_model()


def small_sim(seed=0, n_workers=8, **kwargs) -> ClusterSim:
    sim = ClusterSim(n_workers, seed=seed, validate=True, **kwargs)
    sim.install_digest()
    return sim


def small_trace(seed=0, **kwargs) -> SyntheticDag:
    kwargs.setdefault("n_layers", 6)
    kwargs.setdefault("layer_width", 16)
    kwargs.setdefault("fanin", 2)
    return SyntheticDag(seed=seed, **kwargs)


# ------------------------------------------------------------- primitives


def test_virtual_clock_monotone():
    clock = VirtualClock()
    assert clock() == 0.0
    clock.advance_to(1.5)
    assert clock() == 1.5
    with pytest.raises(ValueError):
        clock.advance_to(1.0)


def test_link_profile_deterministic_and_seeded():
    a = LinkProfile(jitter=0.3, seed=1)
    b = LinkProfile(jitter=0.3, seed=1)
    c = LinkProfile(jitter=0.3, seed=2)
    e = ("sim://w1", "sim://w2")
    assert a.transfer_seconds(*e, 10**6) == b.transfer_seconds(*e, 10**6)
    assert a.transfer_seconds(*e, 10**6) != c.transfer_seconds(*e, 10**6)
    # jitter is per-edge, independent of use order
    assert a.transfer_seconds("sim://w3", "sim://w4", 1) == b.transfer_seconds(
        "sim://w3", "sim://w4", 1
    )


def test_link_profile_from_measured_records():
    """Telemetry's link-profile export seeds the sim's network model
    (the measured-truth loop: live cluster -> LinkStats -> sim)."""
    from distributed_tpu.telemetry import LinkTelemetry

    tel = LinkTelemetry(alpha=0.5, enabled=True)
    for _ in range(4):
        tel.record("sim://w0", "sim://w1", 10**6, 0.01)  # 100 MB/s
    records = tel.link_profile()
    assert records and records[0]["src"] == "sim://w0"
    prof = LinkProfile.from_records(records, bandwidth=1e9, latency=1e-4)
    measured = prof.transfer_seconds("sim://w0", "sim://w1", 10**6)
    # ~ 1 MB over ~100 MB/s => ~10ms, nothing like the 1 GB/s default
    assert 0.005 < measured < 0.05
    # unmeasured edges keep the synthetic default
    assert prof.transfer_seconds("sim://w1", "sim://w0", 10**6) < 0.005


def test_partition_windows():
    prof = LinkProfile()
    prof.add_partition(["a"], ["b"], 1.0, 2.0)
    assert prof.reachable("a", "b", 0.5)
    assert not prof.reachable("a", "b", 1.5)
    assert not prof.reachable("b", "a", 1.5)
    assert prof.reachable("a", "b", 2.0)


# ------------------------------------------------------------ determinism


def test_same_seed_bit_identical():
    """The acceptance gate: same seed => bit-identical digest and
    virtual makespan; different seed => different digest."""
    reports, digests = [], []
    for seed in (0, 0, 3):
        sim = small_sim(seed=seed)
        small_trace(seed=seed).start(sim)
        reports.append(sim.run())
        check_no_lost_keys(sim)
        digests.append(sim.digest())
    assert digests[0] == digests[1]
    assert reports[0]["virtual_makespan_s"] == reports[1]["virtual_makespan_s"]
    assert reports[0]["scheduler_transitions"] == reports[1]["scheduler_transitions"]
    assert digests[0] != digests[2]


def test_makespan_is_virtual_not_wall():
    """The makespan must be virtual seconds derived from the task
    profile, not anything wall-adjacent: 10x the task durations ~10x
    the makespan, irrespective of how fast the host ran the sim."""
    outs = []
    for scale in (1.0, 10.0):
        sim = small_sim()
        small_trace(
            duration_range=(0.002 * scale, 0.004 * scale)
        ).start(sim)
        outs.append(sim.run()["virtual_makespan_s"])
    assert 5.0 < outs[1] / outs[0] < 15.0


# ------------------------------------------------------------------ chaos


def test_chaos_worker_death():
    sim, rep = scenario_worker_death(model=MODEL)
    assert rep["counters"]["workers_killed"] == 2
    assert rep["n_alive"] == rep["n_workers"] - 2
    # the lost lineage recomputed: every wanted key done, none lost
    assert rep["keys_done"] >= rep["keys_wanted"]
    assert rep["keys_lost"] == 0
    # deterministic: the same scenario digests identically
    _sim2, rep2 = scenario_worker_death(model=MODEL)
    assert rep["digest"] == rep2["digest"]


def test_chaos_partition():
    sim, rep = scenario_partition(model=MODEL)
    assert rep["counters"].get("gather_failures", 0) > 0, (
        "partition never failed a fetch — the scenario tested nothing"
    )
    _sim2, rep2 = scenario_partition(model=MODEL)
    assert rep["digest"] == rep2["digest"]


def test_chaos_straggler_steal_pays():
    sim, rep = scenario_straggler(model=MODEL)
    assert rep["steals"] > 0
    assert rep["virtual_makespan_s"] < rep["nosteal_makespan_s"]


def test_chaos_poison_flood():
    sim, rep = scenario_poison_flood(model=MODEL)
    assert rep["faults"]["scheduler-unknown-op"] >= 1
    _sim2, rep2 = scenario_poison_flood(model=MODEL)
    assert rep["digest"] == rep2["digest"]


# -------------------------------------------------------- journal replay


def replay_build(seed=5):
    """Single-chunk workload with periodics off: the journal records
    ENGINE stimuli, so record/replay states must be structurally
    identical up front and free of outside-the-journal mutations
    (steal confirms bypass the stimulus plane by design)."""
    sim = ClusterSim(
        6, seed=seed, validate=True,
        steal_interval=0, amm_interval=0, find_missing_interval=0,
    )
    SyntheticDag(
        n_layers=4, layer_width=10, fanin=2, seed=seed, layers_per_chunk=4
    ).start(sim)
    return sim


def test_sim_journal_replays_through_live_engine():
    """A sim-recorded journal re-fed through the batched engine on an
    identically-prepared state reproduces the identical transition
    stream — the sim half of the replay-format contract."""
    rec = replay_build()
    mark = len(rec.state.transition_log)
    rec.journal_start()
    rec.run()
    records = rec.journal()
    verify_journal(records)
    # dependency graphs exercise the add-keys journal op (replica truth
    # outside the engine); without it placements drift on replay
    assert any(r["op"] == "add-keys" for r in records)

    rep = replay_build()
    mark_b = len(rep.state.transition_log)
    replay_stimulus_trace(rep.state, records)
    assert transition_stream(rec.state, mark) == transition_stream(
        rep.state, mark_b
    )


def test_live_journal_replays_through_sim():
    """The other direction: a journal recorded off one engine replays
    through a fresh simulator's engine (JournalTrace), digests
    verified, bit-identical stream."""
    live = replay_build()
    mark_l = len(live.state.transition_log)
    live.journal_start()
    live.run()
    records = live.journal()

    sim = replay_build()
    mark_s = len(sim.state.transition_log)
    JournalTrace(records).replay(sim)
    assert transition_stream(live.state, mark_l) == transition_stream(
        sim.state, mark_s
    )


def test_journal_file_roundtrip(tmp_path):
    """dump_journal/load_journal + JournalTrace.from_file: the on-disk
    JSONL format survives a round trip with digests intact."""
    from distributed_tpu.tracing import dump_journal, load_journal

    rec = replay_build()
    rec.journal_start()
    rec.run()
    records = rec.journal()
    path = str(tmp_path / "journal.jsonl")
    n = dump_journal(records, path)
    assert n == len(records)
    loaded = load_journal(path)
    verify_journal(loaded)
    sim = replay_build()
    mark = len(sim.state.transition_log)
    JournalTrace.from_file(path).replay(sim)
    assert len(transition_stream(sim.state, mark)) > 0


def test_self_journaled_stimuli_do_not_double_journal():
    """stimulus_reschedule / stimulus_missing_data journal their own op
    AND drive an engine round internally — that round must NOT also
    journal as a "transitions" record, or replay runs it twice (the
    release-worker-data rule).  Captured here: fire both during a
    journal capture and require bit-identical replay."""
    rec = replay_build()
    mark = len(rec.state.transition_log)
    rec.journal_start()
    rec.run(max_events=120)  # mid-flight: processing tasks exist
    state = rec.state
    proc = sorted(
        (ts for ts in state.tasks.values() if ts.state == "processing"),
        key=lambda ts: ts.key,
    )
    assert proc, "no processing task mid-flight"
    state.stimulus_reschedule(
        proc[0].key, proc[0].processing_on.address, "resched-poke"
    )
    mem = sorted(
        (ts for ts in state.tasks.values()
         if ts.state == "memory" and len(ts.who_has) == 1),
        key=lambda ts: ts.key,
    )
    if mem:
        state.stimulus_missing_data(
            mem[0].key, next(iter(mem[0].who_has)).address, "md-poke"
        )
    records = rec.journal()
    ops = [r["op"] for r in records]
    assert "reschedule" in ops
    # exactly one journal record per self-journaled stimulus: no
    # trailing "transitions" twin carrying the same round
    for op in ("reschedule", "missing-data"):
        for i, r in enumerate(records):
            if r["op"] == op and i + 1 < len(records):
                nxt = records[i + 1]
                assert not (
                    nxt["op"] == "transitions"
                    and nxt["stim"] == r["stim"]
                ), f"{op} double-journaled its engine round"

    rep = replay_build()
    mark2 = len(rep.state.transition_log)
    replay_stimulus_trace(rep.state, records)
    assert transition_stream(rec.state, mark) == transition_stream(
        rep.state, mark2
    )


def test_tampered_journal_refused(tmp_path):
    rec = replay_build()
    rec.journal_start()
    rec.run()
    records = rec.journal()
    records[1]["payload"] = {"forged": True}
    sim = replay_build()
    with pytest.raises(ValueError, match="digest"):
        JournalTrace(records).replay(sim)


# ------------------------------------------------------------- A/B driver


def test_ab_driver_steal_cadence():
    """The same trace under two steal cadences: identical overrides
    give identical digests; a policy change moves the virtual-time
    outcome and the diff reports it."""
    def trace_factory():
        # fanin=1 chains cluster hard onto their input holders: real
        # imbalance, so stealing measurably matters
        return SyntheticDag(
            n_layers=8, layer_width=40, fanin=1, n_roots=4, seed=9,
        )

    out = run_ab(
        10, trace_factory,
        {"scheduler.work-stealing-interval": "50ms"},
        {"scheduler.work-stealing-interval": "50ms"},
        seed=9,
    )
    assert out["a"]["digest"] == out["b"]["digest"]
    assert out["diff"]["virtual_makespan_s"] == 0.0
    assert out["a"]["steals"] > 0

    out2 = run_ab(
        10, trace_factory,
        {"scheduler.work-stealing-interval": "50ms"},
        {"scheduler.work-stealing": False},
        seed=9,
    )
    assert out2["a"]["digest"] != out2["b"]["digest"]
    assert out2["b"]["steals"] == 0 < out2["a"]["steals"]
    assert out2["diff"]["makespan_ratio"] is not None


# ----------------------------------------------------- virtual-clock seams


def test_telemetry_ewmas_fed_from_simulated_transfers():
    """PR 7's telemetry plane under the virtual clock: simulated
    gathers file per-link samples whose EWMA bandwidth reproduces the
    link profile, and the snapshot timestamp is VIRTUAL time (the
    injected-clock satellite: no residual real-clock stamp)."""
    profile_bw = 200e6
    sim = small_sim(links=LinkProfile(bandwidth=profile_bw, latency=1e-4))
    small_trace(nbytes_range=(200_000, 400_000)).start(sim)
    sim.run()
    tel = sim.state.telemetry
    assert tel.links, "no simulated transfers filed telemetry"
    bws = [
        link.bandwidth.value for link in tel.links.values()
        if link.bandwidth.count
    ]
    assert bws
    mean_bw = sum(bws) / len(bws)
    # per-sample bandwidth = nbytes / (latency + nbytes/bw) < profile bw;
    # with >=200 KB payloads the latency term is small
    assert profile_bw / 3 < mean_bw <= profile_bw * 1.01, mean_bw
    snap = tel.snapshot()
    assert snap
    vnow = sim.clock()
    assert all(rec["ts"] <= vnow + 1e-9 for rec in snap), (
        "telemetry snapshot stamped off the virtual clock"
    )
    # the trace ring's journal clock is virtual too
    assert sim.state.trace.clock is sim.clock


def test_transition_log_stamps_are_virtual():
    sim = small_sim()
    small_trace().start(sim)
    sim.run()
    stamps = [row[5] for row in sim.state.transition_log]
    assert stamps and max(stamps) <= sim.clock() + 1e-9


# -------------------------------------------------- engine fixes (found
# by the simulator; regression-pinned here)


def test_scatter_release_pure_data_with_live_dependents():
    """Scatter -> consume -> client-release under validate: forgetting
    pure data while (released) dependents remain is legal (reference
    parity); the old assert crashed the engine."""
    from distributed_tpu.scheduler.state import SchedulerState

    state = SchedulerState(validate=True, mirror=False)
    state.add_worker_state("tcp://sc:1", nthreads=1, memory_limit=2**30)
    state.client_desires_keys(["datum"], "c")  # creates the TaskState
    recs, cm, wm = state._transition(
        "datum", "memory", "scatter", nbytes=8, worker="tcp://sc:1"
    )
    state._transitions(recs, cm, wm, "scatter")
    from distributed_tpu.sim.core import SIM_SPEC

    state.update_graph_core(
        {"use": SIM_SPEC}, {"use": {"datum"}}, ["use"], client="c",
        priorities={"use": (0,)}, stimulus_id="graph",
    )
    cm, wm = state.stimulus_task_finished(
        "use", "tcp://sc:1", "fin", nbytes=8
    )
    # consumer done; client drops both — must not trip the forgotten
    # validate assert even though "use" is released-not-forgotten while
    # "datum" forgets
    state.client_releases_keys(["use", "datum"], "c", "rel")
    assert "datum" not in state.tasks


def test_worker_compute_task_on_missing_task_waits_for_data():
    """A compute-task landing on a task in 'missing' (or fetch) state
    must keep the freshly-wired waiting_for_data — the released
    fallback wiped it and raced the task to ready without inputs
    (found by the partition chaos scenario)."""
    from distributed_tpu.worker.state_machine import (
        ComputeTaskEvent,
        Execute,
        GatherDep,
        GatherDepSuccessEvent,
        WorkerState,
    )

    ws = WorkerState(nthreads=1, address="sim://me", validate=True)
    spec = object()
    # dep lands 'missing': wanted as a dependency with NO known holders
    # (no gather can even start)
    ws.handle_stimulus(ComputeTaskEvent(
        stimulus_id="s1", key="consumer", run_spec=spec,
        priority=(1,), who_has={"dep": []}, nbytes={"dep": 8},
    ))
    assert ws.tasks["dep"].state == "missing"
    # the scheduler re-assigns the MISSING task as a compute with its
    # own absent dependency
    instrs = ws.handle_stimulus(ComputeTaskEvent(
        stimulus_id="s3", key="dep", run_spec=spec, priority=(0,),
        who_has={"base": ["sim://peer"]}, nbytes={"base": 8},
    ))
    dep = ws.tasks["dep"]
    assert dep.state == "waiting"
    assert {d.key for d in dep.waiting_for_data} == {"base"}
    assert not [i for i in instrs if isinstance(i, Execute) and i.key == "dep"]
    gathers = [i for i in instrs if isinstance(i, GatherDep)]
    assert gathers and "base" in gathers[0].to_gather
    # data arrives -> NOW it executes
    instrs = ws.handle_stimulus(GatherDepSuccessEvent(
        stimulus_id="s4", worker="sim://peer", data={"base": 1},
        total_nbytes=8,
    ))
    assert [i for i in instrs if isinstance(i, Execute) and i.key == "dep"]
    ws.validate_state()


# ---------------------------------------------------------- housekeeping


def test_sim_package_is_sans_io_scoped():
    """The lint scoping satellite: graft-lint's sans-io and
    monotonic-time rules must cover distributed_tpu/sim/."""
    from distributed_tpu.analysis.rules.monotonic_time import (
        MonotonicTimeRule,
    )
    from distributed_tpu.analysis.rules.sans_io import SansIORule

    assert any("sim" in pat for pat in SansIORule.scope)
    assert any("sim" in pat for pat in MonotonicTimeRule.scope)
    with open(os.path.join(REPO_ROOT, "graft-lint.toml")) as f:
        toml = f.read()
    assert "distributed_tpu/sim/*.py" in toml

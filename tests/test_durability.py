"""Scheduler durability (distributed_tpu/scheduler/durability.py;
docs/durability.md): snapshot/restore round trips, the journal
head-eviction regression, typed rejection of corrupt/mismatched
images, torn-write tolerance, worker re-registration idempotence,
restart-during-in-flight-steal reconciliation, and the deterministic
scheduler-bounce chaos proof across both transition engines."""

from __future__ import annotations

import asyncio
import json
import os

import pytest

from distributed_tpu import config
from distributed_tpu.diagnostics.flight_recorder import (
    replay_stimulus_trace,
    verify_journal,
)
from distributed_tpu.graph.spec import TaskSpec
from distributed_tpu.scheduler.durability import (
    DurabilityManager,
    FileSink,
    JournalCorruptError,
    MemorySink,
    SnapshotCorruptError,
    SnapshotVersionError,
    decode_run_spec,
    encode_run_spec,
    reconcile_worker,
    restore_state,
    restore_stealing,
    state_digest,
)
from distributed_tpu.scheduler.state import SchedulerState
from distributed_tpu.scheduler.stealing import WorkStealing
from distributed_tpu.utils.test import StubScheduler

from utils_cluster import gen_cluster

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _inc(x):
    return x + 1


def load_model() -> dict:
    out = {}
    for role in ("scheduler", "worker"):
        path = os.path.join(
            REPO_ROOT, "docs", "state_machine", f"{role}.json"
        )
        with open(path) as f:
            out[role] = json.load(f)
    return out


def _flood_state(n_workers=8, n_tasks=200, **overrides):
    with config.set({"scheduler.jax.enabled": False, **overrides}):
        state = SchedulerState(validate=True)
        for i in range(n_workers):
            state.add_worker_state(
                f"tcp://dur:{i}", nthreads=2, memory_limit=2**30,
                name=f"d{i}",
            )
        tasks = {f"dur-{i}": TaskSpec(_inc, (i,)) for i in range(n_tasks)}
        state.update_graph_core(
            tasks, {k: set() for k in tasks}, list(tasks),
            client="dur-client", stimulus_id="dur-graph",
        )
    return state


def _run_flood(state, mgr=None, cadence=0) -> int:
    rounds = 0
    while True:
        batch = [
            (ts.key, ws.address, f"dur-fin-{ts.key}", {"nbytes": 8})
            for ws in state.workers.values()
            for ts in list(ws.processing)
        ]
        if not batch:
            return rounds
        state.stimulus_tasks_finished_batch(batch)
        rounds += 1
        if mgr is not None and cadence and rounds % cadence == 0:
            mgr.snapshot()
        assert rounds < 10_000, "flood did not converge"


# ----------------------------------------------------------- round trips


def test_snapshot_restore_roundtrip_with_deltas():
    """Base + delta snapshots + journal tail fold back into a state
    whose structural digest matches the original bit-exactly."""
    state = _flood_state()
    mgr = DurabilityManager(
        state, MemorySink(), full_every=10**6, state_digests=True
    )
    mgr.attach()
    _run_flood(state, mgr, cadence=3)
    mgr.flush_journal()
    assert mgr.stats.epochs >= 2, "flood produced no delta epochs"

    fresh = SchedulerState(validate=True)
    info = DurabilityManager.restore_into(fresh, mgr.sink)
    assert state_digest(fresh) == state_digest(state)
    assert info["deltas"] >= 1
    assert info["torn_records"] == 0
    # interest survived: the client's keys are still wanted, so a
    # restored scheduler will not GC completed work
    cs = fresh.clients.get("dur-client")
    assert cs is not None and len(cs.wants_what) == 200


def test_rto_curve_restores_digest_identical():
    """The measured-RTO curve: one 2,000-task flood captured at three
    snapshot cadences (rounds per epoch: many deltas, few, base only)
    restores digest-identical at each; finer cadence means more epochs
    and a shorter journal tail, the deltas stay O(changed rows), and at
    the default ``snapshot-interval`` one delta's encode costs under 5 %
    of the interval."""
    n_tasks = 2000
    curve = []
    for cadence in (2, 8, 10**9):
        state = _flood_state(n_workers=16, n_tasks=n_tasks)
        mgr = DurabilityManager(state, MemorySink(), full_every=10**6,
                                state_digests=True)
        mgr.attach()
        _run_flood(state, mgr, cadence)
        mgr.flush_journal()
        fresh = SchedulerState(validate=False)
        info = DurabilityManager.restore_into(fresh, mgr.sink)
        assert state_digest(fresh) == state_digest(state), cadence
        assert info["restore_seconds"] > 0, cadence
        curve.append((mgr.stats, info["tail_records"]))
    (fine, fine_tail), (mid, _), (base, base_tail) = curve
    assert fine.epochs > base.epochs
    assert base_tail > fine_tail
    assert fine.snapshot_rows < 6 * n_tasks
    interval = config.parse_timedelta(
        config.get("scheduler.durability.snapshot-interval"))
    per_epoch = mid.snapshot_seconds / max(mid.epochs, 1)
    assert per_epoch < 0.05 * interval, (per_epoch, interval)


def test_journal_eviction_race_regression():
    """The head-truncation durability gap: with a tiny journal deque a
    long flood evicts its head, so the in-memory journal alone FAILS
    verification — but the sink capture (armed atomically with the
    base snapshot at attach) stays complete and restores exactly."""
    state = _flood_state(**{"scheduler.trace.journal-size": 8})
    assert state.trace.journal.maxlen == 8
    mgr = DurabilityManager(
        state, MemorySink(), full_every=10**6, state_digests=True
    )
    mgr.attach()
    _run_flood(state, mgr, cadence=5)
    mgr.flush_journal()
    assert mgr.stats.journal_records > 8
    # the deque lost its head: a capture that relied on it would replay
    # from a hole.  verify_journal is the detector...
    with pytest.raises(ValueError, match="complete capture"):
        verify_journal(list(state.trace.journal))
    # ...and the segment writer is the fix: restore is digest-exact
    fresh = SchedulerState(validate=True)
    DurabilityManager.restore_into(fresh, mgr.sink)
    assert state_digest(fresh) == state_digest(state)


def test_run_spec_codec_roundtrip():
    from distributed_tpu.protocol.serialize import Serialized

    spec = Serialized({"kind": "task"}, [b"frame-a", b"frame-b"])
    out = decode_run_spec(encode_run_spec(spec))
    assert isinstance(out, Serialized)
    assert out.header == {"kind": "task"}
    assert out.frames == [b"frame-a", b"frame-b"]
    # non-picklable degrades to a schedulable opaque marker
    opaque = decode_run_spec(encode_run_spec(lambda x: x))
    assert opaque  # truthy: the scheduler still schedules the task
    assert decode_run_spec(encode_run_spec(None)) is None
    assert decode_run_spec(encode_run_spec(7)) == 7


# ------------------------------------------------------ typed rejection


def _captured_sink() -> tuple:
    # floods journal ONE tasks-finished-batch record per engine batch:
    # enough tasks for a multi-record TAIL segment (the torn/gap tests
    # corrupt mid-span, so every record must be past the watermark —
    # no mid-flood snapshots)
    state = _flood_state(n_tasks=200)
    mgr = DurabilityManager(
        state, MemorySink(), full_every=10**6, state_digests=True
    )
    mgr.attach()
    _run_flood(state, mgr)
    mgr.flush_journal()
    return state, mgr.sink


def test_snapshot_version_mismatch_rejected():
    _state, sink = _captured_sink()
    blob = sink.snapshots[0]
    outer = json.loads(blob)
    outer["body"]["v"] = 999
    # re-stamp the digest so ONLY the version mismatches
    import hashlib

    check = json.dumps(
        outer["body"], default=repr, sort_keys=True,
        separators=(",", ":"),
    ).encode()
    outer["d"] = hashlib.blake2b(check, digest_size=16).hexdigest()
    sink.snapshots[0] = json.dumps(outer).encode()
    fresh = SchedulerState(validate=True)
    with pytest.raises(SnapshotVersionError, match="schema v999"):
        DurabilityManager.restore_into(fresh, sink)


def test_snapshot_digest_corruption_rejected():
    _state, sink = _captured_sink()
    blob = sink.snapshots[0]
    outer = json.loads(blob)
    outer["body"]["journal_seq"] = 12345  # bit rot, digest not re-stamped
    sink.snapshots[0] = json.dumps(outer).encode()
    fresh = SchedulerState(validate=True)
    with pytest.raises(SnapshotCorruptError, match="digest"):
        DurabilityManager.restore_into(fresh, sink)


def test_snapshot_unparseable_rejected():
    _state, sink = _captured_sink()
    sink.snapshots[0] = b"\x00not json"
    with pytest.raises(SnapshotCorruptError, match="parse"):
        DurabilityManager.restore_into(SchedulerState(validate=True), sink)


def test_torn_final_record_tolerated(tmp_path):
    """A crash mid-append leaves a torn FINAL line in the FINAL
    segment: that record was never durable — dropped and counted, and
    the restore still lands on the last durable prefix."""
    state, mem = _captured_sink()
    sink = FileSink(str(tmp_path))
    for e in mem.snapshot_epochs():
        sink.write_snapshot(e, mem.read_snapshot(e))
    for e in mem.journal_epochs():
        with open(sink._journal_path(e), "wb") as f:
            f.write(mem.read_journal(e))
    last = max(sink.journal_epochs())
    path = sink._journal_path(last)
    blob = open(path, "rb").read()
    if not blob.strip():
        pytest.skip("flood left an empty final segment")
    torn = blob.rstrip(b"\n")
    torn = torn[: len(torn) - len(torn.rsplit(b"\n", 1)[-1]) // 2 - 1]
    with open(path, "wb") as f:
        f.write(torn)
    fresh = SchedulerState(validate=True)
    info = DurabilityManager.restore_into(fresh, sink)
    assert info["torn_records"] == 1


def test_torn_middle_record_rejected(tmp_path):
    state, mem = _captured_sink()
    sink = FileSink(str(tmp_path))
    for e in mem.snapshot_epochs():
        sink.write_snapshot(e, mem.read_snapshot(e))
    for e in mem.journal_epochs():
        with open(sink._journal_path(e), "wb") as f:
            f.write(mem.read_journal(e))
    seg = next(
        e for e in sink.journal_epochs()
        if len(sink.read_journal(e).splitlines()) >= 3
    )
    path = sink._journal_path(seg)
    lines = open(path, "rb").read().splitlines()
    lines[1] = lines[1][: len(lines[1]) // 2]  # torn MID-segment
    with open(path, "wb") as f:
        f.write(b"\n".join(lines) + b"\n")
    with pytest.raises(JournalCorruptError, match="refusing to replay"):
        DurabilityManager.restore_into(SchedulerState(validate=True), sink)


def test_torn_penultimate_line_no_trailing_newline_rejected(tmp_path):
    """The torn-write allowance is exactly the LAST non-empty line.  A
    segment without a trailing newline whose PENULTIMATE line is
    corrupt must raise — not count the corruption as the crash artifact
    and silently drop the valid final record."""
    state, mem = _captured_sink()
    sink = FileSink(str(tmp_path))
    for e in mem.snapshot_epochs():
        sink.write_snapshot(e, mem.read_snapshot(e))
    for e in mem.journal_epochs():
        with open(sink._journal_path(e), "wb") as f:
            f.write(mem.read_journal(e))
    seg = max(sink.journal_epochs())
    lines = [
        ln for ln in sink.read_journal(seg).splitlines() if ln.strip()
    ]
    if len(lines) < 3:
        pytest.skip("flood left too few records in the final segment")
    lines[-2] = lines[-2][: len(lines[-2]) // 2]  # corrupt penultimate
    with open(sink._journal_path(seg), "wb") as f:
        f.write(b"\n".join(lines))  # NO trailing newline
    with pytest.raises(JournalCorruptError, match="refusing to replay"):
        DurabilityManager.restore_into(SchedulerState(validate=True), sink)


def test_reconcile_empty_held_keys_strips_stale_replicas():
    """A worker that re-registers holding NOTHING still reconciles: a
    restored who_has full of replicas it no longer has must be stripped
    through the engine (the server gate is `held_keys is not None`, not
    truthiness — an empty list is a meaningful report)."""
    state = _flood_state(n_workers=2, n_tasks=8)
    _run_flood(state)
    addr = next(iter(state.workers))
    ws = state.workers[addr]
    stale = [ts.key for ts in ws.has_what]
    assert stale, "flood left this worker no replicas to strip"
    _msgs, counts = reconcile_worker(state, addr, [], "reconcile-empty")
    assert counts["stripped"] == len(stale)
    assert not ws.has_what


def test_native_delta_snapshot_marks_workers_dirty():
    """Native tape appliers mutate ws.processing/has_what inline; they
    must mark the WORKER dirty too, or a delta snapshot taken after a
    native flood carries stale order lists and the restore fails its
    state-digest check (a quiescing workload whose last flood only
    completed tasks)."""
    from distributed_tpu import native

    if native.load() is None:
        pytest.skip("native toolchain unavailable")
    with config.set({"scheduler.jax.enabled": False,
                     "scheduler.work-stealing": False}):
        state = SchedulerState(validate=False)
        if not state.attach_native(build=True):
            pytest.skip("native engine did not attach")
        addrs = []
        for i in range(4):
            state.add_worker_state(
                f"tcp://nat:{i}", nthreads=2, memory_limit=2**30,
                name=f"n{i}",
            )
            addrs.append(f"tcp://nat:{i}")
        # scattered roots + a fanin layer: non-rootish tasks stay on the
        # compiled placement arm instead of escaping to the oracle
        roots = []
        for i in range(8):
            k = f"natroot-{i}"
            state.client_desires_keys([k], "nat-client")
            recs, cm, wm = state._transition(
                k, "memory", "nat-scatter", nbytes=65536,
                worker=addrs[i % 4],
            )
            state._transitions(recs, cm, wm, "nat-scatter")
            roots.append(k)
        tasks = {f"nat-{i}": TaskSpec(_inc, (i,)) for i in range(40)}
        deps = {k: {roots[i % 8]} for i, k in enumerate(tasks)}
        state.update_graph_core(
            tasks, deps, list(tasks), client="nat-client",
            priorities={k: (i,) for i, k in enumerate(tasks)},
            stimulus_id="nat-graph",
        )
        mgr = DurabilityManager(
            state, MemorySink(), full_every=10**6, state_digests=True
        )
        mgr.attach()
        # complete every processing task in REVERSED order so the
        # per-worker mirror orders change relative to the base
        # snapshot, then snapshot the quiesced state — a delta whose
        # only mutations came through the native tape appliers
        while True:
            batch = [
                (ts.key, ws.address, f"nat-fin-{ts.key}", {"nbytes": 8})
                for ws in state.workers.values()
                for ts in reversed(list(ws.processing))
            ]
            if not batch:
                break
            state.stimulus_tasks_finished_batch(batch)
        assert state.native is not None and (
            state.native.counters()["transitions"] > 40
        ), f"flood did not run natively: {state.native.counters()}"
        mgr.snapshot()
        mgr.flush_journal()
        fresh = SchedulerState(validate=False)
        DurabilityManager.restore_into(fresh, mgr.sink)
        assert state_digest(fresh) == state_digest(state)


def test_delta_snapshot_while_native_flood_is_deferred():
    """Deferred materialization meets durability: a delta snapshot
    taken while the last purely-native flood is still parked (no read
    has hydrated its rows) must force the replay from inside
    ``DurabilityTracker.drain`` — its dirty marks only exist after the
    tape appliers run — or the delta captures an empty dirty set and
    the restore's state digest diverges."""
    from distributed_tpu import native

    if native.load() is None:
        pytest.skip("native toolchain unavailable")
    with config.set({"scheduler.jax.enabled": False,
                     "scheduler.work-stealing": False,
                     "scheduler.native-engine.min-flood": 0}):
        state = SchedulerState(validate=False)
        if not state.attach_native(build=True):
            pytest.skip("native engine did not attach")
        addrs = []
        for i in range(4):
            state.add_worker_state(
                f"tcp://defer:{i}", nthreads=2, memory_limit=2**30,
                name=f"d{i}",
            )
            addrs.append(f"tcp://defer:{i}")
        roots = []
        for i in range(8):
            k = f"defroot-{i}"
            state.client_desires_keys([k], "def-client")
            recs, cm, wm = state._transition(
                k, "memory", "def-scatter", nbytes=65536,
                worker=addrs[i % 4],
            )
            state._transitions(recs, cm, wm, "def-scatter")
            roots.append(k)
        tasks = {f"def-{i}": TaskSpec(_inc, (i,)) for i in range(40)}
        deps = {k: {roots[i % 8]} for i, k in enumerate(tasks)}
        state.update_graph_core(
            tasks, deps, list(tasks), client="def-client",
            priorities={k: (i,) for i, k in enumerate(tasks)},
            stimulus_id="def-graph",
        )
        mgr = DurabilityManager(
            state, MemorySink(), full_every=10**6, state_digests=True
        )
        mgr.attach()
        ne = state.native
        # one purely-native flood, nothing reading python truth after:
        # the segments stay parked with their rows un-hydrated
        batch = [
            (ts.key, ws.address, f"def-fin-{ts.key}", {"nbytes": 8})
            for ws in state.workers.values()
            for ts in list(ws.processing)
        ]
        assert batch
        state.stimulus_tasks_finished_batch(batch)
        assert ne._pending, "flood did not defer (premise)"
        mgr.snapshot()  # delta over un-hydrated rows: drain must sync
        assert not ne._pending, "drain() did not materialize first"
        # finish the workload and round-trip the full image
        while True:
            batch = [
                (ts.key, ws.address, f"def-fin2-{ts.key}", {"nbytes": 8})
                for ws in state.workers.values()
                for ts in list(ws.processing)
            ]
            if not batch:
                break
            state.stimulus_tasks_finished_batch(batch)
        mgr.snapshot()
        mgr.flush_journal()
        assert ne.counters()["transitions"] > 0
        fresh = SchedulerState(validate=False)
        DurabilityManager.restore_into(fresh, mgr.sink)
        assert state_digest(fresh) == state_digest(state)


def test_snapshot_epoch_gap_rejected():
    """A delta snapshot lost to a swallowed off-loop sink write (the
    live threaded sink logs-and-drops failures) must fail the load
    loudly: folding around the hole would silently drop every row that
    was dirty only in the missing epoch's window."""
    state = _flood_state()
    mgr = DurabilityManager(
        state, MemorySink(), full_every=10**6, state_digests=True
    )
    mgr.attach()
    _run_flood(state, mgr, cadence=2)
    mgr.flush_journal()
    assert mgr.stats.epochs >= 4, "flood produced too few delta epochs"
    missing = mgr.sink.snapshot_epochs()[2]
    del mgr.sink.snapshots[missing]
    with pytest.raises(SnapshotCorruptError, match="epoch gap"):
        DurabilityManager.load(mgr.sink)


def test_journal_seq_gap_rejected():
    _state, sink = _captured_sink()
    seg = next(
        e for e in sink.journal_epochs()
        if len(sink.read_journal(e).splitlines()) >= 3
    )
    lines = sink.read_journal(seg).splitlines()
    del lines[1]  # a record vanished mid-span
    sink.journals[seg] = bytearray(b"\n".join(lines) + b"\n")
    with pytest.raises(JournalCorruptError, match="contiguity"):
        DurabilityManager.restore_into(SchedulerState(validate=True), sink)


def test_journal_payload_digest_rejected():
    _state, sink = _captured_sink()
    seg = sink.journal_epochs()[0]
    lines = sink.read_journal(seg).splitlines()
    rec = json.loads(lines[0])
    rec["payload"] = {"forged": True}
    lines[0] = json.dumps(rec).encode()
    sink.journals[seg] = bytearray(b"\n".join(lines) + b"\n")
    with pytest.raises(JournalCorruptError, match="payload digest"):
        DurabilityManager.restore_into(SchedulerState(validate=True), sink)


# ----------------------------------------------- worker re-registration


@gen_cluster(client=True)
async def test_reregistration_idempotent(c, s, a, b):
    """A register-worker retry (same server_id) after the reply was
    lost must not double-count replicas, occupancy, or worker rows —
    the scheduler reuses the state row and only replaces the stream."""
    from distributed_tpu.comm.core import connect

    futs = c.map(_inc, range(6))
    await c.gather(futs)
    ws = s.state.workers[a.address]
    occ0 = ws.occupancy
    nbytes0 = ws.nbytes
    has0 = [ts.key for ts in ws.has_what]
    n_workers0 = len(s.state.workers)
    held = [[ts.key, ts.nbytes or 0] for ts in ws.has_what]

    comm = await connect(s.address, **s.connection_args)
    await comm.write({
        "op": "register-worker", "address": a.address,
        "nthreads": a.nthreads, "name": a.name,
        "memory_limit": a.memory_limit, "resources": {},
        "server_id": a.id, "held_keys": held, "reply": False,
    })
    resp = await comm.read()
    assert resp["status"] == "OK"
    assert s.state.workers[a.address] is ws, "state row was rebuilt"
    assert len(s.state.workers) == n_workers0
    assert ws.occupancy == occ0
    assert ws.nbytes == nbytes0, "replicas were double-counted"
    assert [ts.key for ts in ws.has_what] == has0
    # a DIFFERENT process claiming the address while the stream lives
    # is still rejected (no silent takeover)
    comm2 = await connect(s.address, **s.connection_args)
    await comm2.write({
        "op": "register-worker", "address": a.address,
        "nthreads": 1, "name": "imposter", "memory_limit": 0,
        "resources": {}, "server_id": "not-the-same-worker",
        "reply": False,
    })
    resp2 = await comm2.read()
    assert resp2["status"] == "error"
    await comm2.close()
    await comm.close()


async def _hard_kill(s) -> None:
    """Crash, not close: abort every stream, comm and callback without
    the graceful protocol (no close-worker ops, no final snapshot), so
    the durable image is whatever already hit the sink.  The in-process
    stand-in for kill -9 of the scheduler."""
    from distributed_tpu.rpc.core import Status

    s.status = Status.closing  # stops the comm loops mid-read
    for pc in s.periodic_callbacks.values():
        pc.stop()
    s.periodic_callbacks.clear()
    if s.watchdog is not None:
        s.watchdog.stop()
    if s.cp_profiler is not None:
        s.cp_profiler.stop()
    for listener in s.listeners:
        listener.stop()
    for comms in (s.stream_comms, s.client_comms):
        for bs in list(comms.values()):
            bs.abort()
        comms.clear()
    for comm in list(s._comms):
        try:
            comm.abort()
        except Exception:
            pass
    await s._ongoing_background_tasks.stop()
    await s.rpc.close()
    if s.http_server is not None:
        await s.http_server.stop()
    s.status = Status.closed
    s._event_finished.set()


def test_live_tcp_hard_bounce_loses_no_completed_key(tmp_path):
    """A TCP cluster computes 50 keys, 10 of them after an explicit
    snapshot so the crash leaves a journal tail; the scheduler is hard
    killed and a fresh one restarts on the same port from snapshot +
    tail.  The two workers reconnect on their own with their held keys:
    no completed key is lost, recovery takes under 30 s, and new work
    computes after it."""
    from distributed_tpu.client.client import Client
    from distributed_tpu.scheduler.server import Scheduler
    from distributed_tpu.worker.server import Worker

    overrides = {
        "scheduler.jax.enabled": False,
        "scheduler.durability.directory": str(tmp_path),
        "scheduler.durability.snapshot-interval": "500ms",
        "scheduler.durability.flush-interval": "50ms",
        "scheduler.durability.grace": "15s",
        "worker.reconnect-attempts": 40,
        "worker.register.base-delay": "50ms",
        "worker.register.max-delay": "250ms",
    }

    async def run():
        workers: list = []
        s2 = c = None
        try:
            s1 = Scheduler(listen_addr="tcp://127.0.0.1:0", validate=True)
            await s1.start()
            addr = s1.address
            for i in range(2):
                w = Worker(addr, name=f"rw{i}", nthreads=1, validate=True,
                           listen_addr="tcp://127.0.0.1:0")
                await w.start()
                workers.append(w)
            c = Client(addr)
            await c.__aenter__()
            # the futures stay referenced: their keys stay wanted
            futs = c.map(_inc, range(40))
            assert await c.gather(futs) == list(range(1, 41))
            s1.durability.snapshot()
            futs2 = c.map(_inc, range(40, 50))
            assert await c.gather(futs2) == list(range(41, 51))
            pre_keys = sorted(k for k, ts in s1.state.tasks.items()
                              if ts.state == "memory")
            assert len(pre_keys) >= 50
            s1.durability.flush_journal()
            t_kill = asyncio.get_running_loop().time()
            await _hard_kill(s1)
            s1.durability.sink.drain()  # the queued writes hit disk first

            # the workers' reconnect loop is already probing the port
            s2 = Scheduler(listen_addr=addr, validate=True)
            await s2.start()
            assert s2.durability.stats.restore_seconds > 0
            # snapshot + tail, not the snapshot alone
            assert s2.durability.stats.replay_records > 0
            addrs = {w.address for w in workers}

            def lost():
                return [k for k in pre_keys
                        if (ts := s2.state.tasks.get(k)) is None
                        or ts.state != "memory" or not ts.who_has]

            while lost() or not addrs <= set(s2.stream_comms):
                rto = asyncio.get_running_loop().time() - t_kill
                assert rto < 30, (lost()[:5], sorted(s2.stream_comms))
                await asyncio.sleep(0.05)
            async with Client(addr) as c2:
                assert await c2.gather(c2.map(_inc, range(100, 110))) == \
                    list(range(101, 111))
        finally:
            if c is not None:
                try:
                    await asyncio.wait_for(c.close(), 5)
                except Exception:
                    pass
            for w in workers:
                try:
                    await w.close(report=False)
                except Exception:
                    pass
            if s2 is not None:
                await s2.close()

    with config.set(overrides):
        asyncio.run(asyncio.wait_for(run(), 90))


def test_reconcile_worker_idempotent_and_corrective():
    """held_keys reconciliation routes every correction through the
    engine and converges: a second identical pass finds nothing."""
    state = _flood_state(n_tasks=20)
    _run_flood(state)
    ws = next(iter(state.workers.values()))
    held = [[ts.key, ts.nbytes or 0] for ts in ws.has_what]
    assert held
    # strip one replica behind the scheduler's back (the worker still
    # reports it) and forge one stale scheduler-side replica (the
    # worker lost it)
    missing_key = held[0][0]
    ts_missing = state.tasks[missing_key]
    state.remove_replica(ts_missing, ws)
    stale = next(
        ts for ts in ws.has_what if ts.key != missing_key
    )
    reported = [
        [k, nb] for k, nb in held if k != stale.key
    ] + [["totally-unknown-key", 5]]

    (cm, wm), counts = reconcile_worker(
        state, ws.address, reported, "reconcile-1"
    )
    assert counts["added"] == 1
    assert counts["stripped"] == 1
    assert counts["unknown"] == 1
    assert ws in ts_missing.who_has
    assert stale not in ws.has_what
    # idempotence: the same report again corrects nothing
    (_cm2, _wm2), counts2 = reconcile_worker(
        state, ws.address, reported, "reconcile-2"
    )
    assert counts2["added"] == 0
    assert counts2["stripped"] == 0


# ------------------------------------------- restart during in-flight steal


def _steal_setup():
    with config.set({
        "scheduler.jax.enabled": False,
        "scheduler.work-stealing": False,  # no periodic cb registration
    }):
        state = SchedulerState(validate=True)
        sched = StubScheduler(state)
        for i in range(2):
            state.add_worker_state(
                f"tcp://steal:{i}", nthreads=1, memory_limit=2**30,
                name=f"s{i}",
            )
        # a duration prior so steal pricing has something to read
        state.new_task_prefix("stl").add_duration(0.1)
        tasks = {f"stl-{i}": TaskSpec(_inc, (i,)) for i in range(4)}
        state.update_graph_core(
            tasks, {k: set() for k in tasks}, list(tasks),
            client="steal-client", stimulus_id="steal-graph",
        )
        steal = WorkStealing(sched)
        state.extensions["stealing"] = steal
    return state, sched, steal


def _restore_with_stealing(sink):
    with config.set({
        "scheduler.jax.enabled": False,
        "scheduler.work-stealing": False,
    }):
        state2 = SchedulerState(validate=True)
        sched2 = StubScheduler(state2)
        folded, tail, info = DurabilityManager.load(sink)
        restore_state(state2, folded)
        want = info.get("state_digest")
        if want:
            assert state_digest(state2) == want
        steal2 = WorkStealing(sched2)
        state2.extensions["stealing"] = steal2
        restore_stealing(steal2, folded.get("ext") or None)
        replay_stimulus_trace(state2, tail, verify_digests=False)
    return state2, steal2, info


def test_restart_during_in_flight_steal_confirm_in_tail():
    """A steal requested before the snapshot and CONFIRMED after it
    (but before the crash) reconciles from the journal tail: the
    restored task runs on the thief, the confirm window is closed with
    its occupancy overlays reverted, and no ledger row leaks open."""
    state, sched, steal = _steal_setup()
    mgr = DurabilityManager(
        state, MemorySink(), full_every=10**6, state_digests=True
    )
    mgr.attach()
    ts = next(
        t for t in state.tasks.values() if t.state == "processing"
    )
    victim = ts.processing_on
    thief = next(
        w for w in state.workers.values() if w is not victim
    )
    steal.move_task_request(ts, victim, thief)
    stim = steal.in_flight[ts.key].stimulus_id
    mgr.snapshot()  # the open confirm window is snapshot truth
    asyncio.run(steal.move_task_confirm(
        key=ts.key, state="ready", stimulus_id=stim
    ))
    assert ts.processing_on is thief
    mgr.flush_journal()

    state2, steal2, info = _restore_with_stealing(mgr.sink)
    assert info["tail_records"] >= 2  # steal-confirm + steal-move
    ts2 = state2.tasks[ts.key]
    assert ts2.processing_on is not None
    assert ts2.processing_on.address == thief.address
    assert ts.key not in steal2.in_flight, "confirm window leaked open"
    assert not steal2.in_flight_occupancy, "occupancy overlays leaked"
    assert state_digest(state2) == state_digest(state)
    # drive every task to memory on both states: the replayed steal's
    # ledger row must JOIN (superseding the request row), not age out
    for st in (state, state2):
        _run_flood(st)
        assert st.ledger.open_rows == 0, "ledger row leaked open"
    assert state_digest(state2) == state_digest(state)


def test_restart_before_steal_confirm():
    """Crash with the confirm window still open: the snapshot carries
    the in_flight entry, and the victim's answer arriving AFTER the
    restart finds it and completes the move."""
    state, sched, steal = _steal_setup()
    mgr = DurabilityManager(
        state, MemorySink(), full_every=10**6, state_digests=True
    )
    mgr.attach()
    ts = next(
        t for t in state.tasks.values() if t.state == "processing"
    )
    victim = ts.processing_on
    thief = next(w for w in state.workers.values() if w is not victim)
    steal.move_task_request(ts, victim, thief)
    stim = steal.in_flight[ts.key].stimulus_id
    mgr.snapshot()
    mgr.flush_journal()

    state2, steal2, _info = _restore_with_stealing(mgr.sink)
    assert ts.key in steal2.in_flight
    assert steal2.in_flight[ts.key].stimulus_id == stim
    asyncio.run(steal2.move_task_confirm(
        key=ts.key, state="ready", stimulus_id=stim
    ))
    ts2 = state2.tasks[ts.key]
    assert ts2.processing_on is not None
    assert ts2.processing_on.address == thief.address
    _run_flood(state2)
    assert state2.ledger.open_rows == 0


# ------------------------------------------------------ the chaos proof


def test_scenario_scheduler_bounce_oracle():
    from distributed_tpu.sim.chaos import scenario_scheduler_bounce

    model = load_model()
    sim, rep = scenario_scheduler_bounce(model=model)
    assert rep["counters"]["scheduler_bounces"] == 1
    assert rep["bounce_tail_records"] > 0
    assert rep["keys_lost"] == 0
    assert rep["keys_done"] >= rep["keys_wanted"]
    # deterministic: the same scenario digests identically
    _sim2, rep2 = scenario_scheduler_bounce(model=model)
    assert rep["digest"] == rep2["digest"]


def test_scenario_scheduler_bounce_native():
    from distributed_tpu import native
    from distributed_tpu.sim.chaos import scenario_scheduler_bounce

    if native.load() is None:
        pytest.skip("native toolchain unavailable")
    model = load_model()
    sim, rep = scenario_scheduler_bounce(model=model, native=True)
    assert sim.state.native is not None, "native engine never attached"
    assert rep["counters"]["scheduler_bounces"] == 1
    assert rep["keys_lost"] == 0
    assert rep["keys_done"] >= rep["keys_wanted"]


# The PYTHONHASHSEED sweep of the bounce proof lives with the rest of
# the hashseed harness: tests/test_determinism.py::
# test_bounce_scenario_across_hashseeds (seeds 6/8 caught the original
# plain-set ``stealable``/``saturated`` divergence).

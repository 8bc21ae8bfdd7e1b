"""Control-plane self-profiling tests (diagnostics/selfprofile.py;
docs/observability.md "Self-profiling"): the wall budget's self-time
semantics, the control-plane sampler's phase stamping and boundaries,
the stall watchdog, the shared-watcher lifecycle, profiler stop()
flushing, scope-aware ``Scheduler.get_profile``, and the ``/profile``
routes on both roles."""

from __future__ import annotations

import asyncio
import json
import threading
import time as _time

from distributed_tpu import config
from distributed_tpu.diagnostics.profile import (
    Profiler,
    _SharedWatcher,
    create,
    merge,
    process,
)
from distributed_tpu.diagnostics.selfprofile import (
    ControlPlaneProfiler,
    LoopWatchdog,
    WallBudget,
    profile_records,
    profile_to_speedscope,
)

from conftest import gen_test


# ------------------------------------------------------------ WallBudget


def test_wall_budget_self_time_nesting():
    """Entering a child phase pauses the parent: totals are SELF time,
    and the sum of self times equals the inclusive wall."""
    fake = [0.0]
    budget = WallBudget(clock=lambda: fake[0])
    budget.push("engine.drain", "stim-1")
    fake[0] = 1.0
    budget.push("engine.scalar-arm:waiting,processing", "stim-1")
    fake[0] = 1.5
    budget.pop()
    fake[0] = 2.0
    budget.pop()
    totals = budget.snapshot()
    assert totals["engine.drain"] == 1.5  # 2.0 inclusive minus 0.5 child
    assert totals["engine.scalar-arm:waiting,processing"] == 0.5
    assert budget.snapshot_counts() == {
        "engine.drain": 1,
        "engine.scalar-arm:waiting,processing": 1,
    }
    # balanced stack: the thread is outside every phase again
    assert budget.current(threading.get_ident()) == ("", "")
    # unbalanced pop never corrupts the accumulators
    budget.pop()
    assert budget.snapshot() == totals


def test_wall_budget_active_visible_cross_thread():
    budget = WallBudget()
    seen = {}
    ready = threading.Event()
    release = threading.Event()

    def worker():
        budget.push("kernel.dispatch", "stim-k")
        ready.set()
        release.wait(5)
        budget.pop()

    t = threading.Thread(target=worker)
    t.start()
    assert ready.wait(5)
    seen = budget.current(t.ident)
    release.set()
    t.join()
    assert seen == ("kernel.dispatch", "stim-k")
    assert budget.current(t.ident) == ("", "")


def test_wall_budget_phase_context_restores_on_error():
    budget = WallBudget()
    try:
        with budget.phase("egress.flush"):
            raise RuntimeError("boom")
    except RuntimeError:
        pass
    assert budget.current(threading.get_ident()) == ("", "")
    assert budget.snapshot_counts()["egress.flush"] == 1


# ---------------------------------------------------- profiler mechanics


def test_profiler_stop_flushes_current_cycle():
    """stop() must fold the in-flight cycle into history — a short-lived
    profiler (shorter than one cycle) must not lose its samples."""
    p = Profiler(interval=0.001, cycle=60.0)  # cycle never rolls on its own
    frame = sys_frame()
    p.start()
    p._add_sample(frame, 0.0)
    assert not p.history  # still in the current cycle
    p.stop()
    assert len(p.history) == 1
    assert p.history[0][1]["count"] == 1
    assert p.current["count"] == 0  # flushed, not duplicated
    assert p.get_profile()["count"] == 1


def sys_frame():
    """A real frame object to feed _add_sample directly."""
    import sys

    return sys._getframe()


def test_process_stop_boundary_cuts_outer_frames():
    frame = sys_frame()  # stack: ...pytest... -> this test -> sys_frame
    full = create()
    process(frame, full)
    cut = create()
    process(frame, cut, stop=__file__.rsplit("/", 1)[-1])
    # the boundary file's own frames (and everything outer) are cut:
    # only the root count remains
    assert full["children"], "unbounded process lost the stack"
    assert cut["count"] == 1 and not cut["children"]


def test_control_plane_profiler_stamps_phase_and_counts_idle():
    budget = WallBudget()
    p = ControlPlaneProfiler(
        idents=lambda: [threading.get_ident()], wall=budget,
        interval=0.001, cycle=60.0, stop=None,
    )
    p._last_sample = 0.0
    p._last_cycle = 0.0
    budget.push("engine.drain", "stim-x")
    try:
        p._add_sample(sys_frame(), 1.0, threading.get_ident())
    finally:
        budget.pop()
    assert p.total_samples == 1 and p.idle_samples == 0
    tree = p.get_profile()
    assert "phase:engine.drain" in tree["children"]
    assert list(p.samples) == [(1.0, "engine.drain", "stim-x")]

    # idle selector frames count apart from the tree
    class _Code:
        co_filename = "/usr/lib/python3/selectors.py"
        co_name = "select"

    class _Frame:
        f_code = _Code()
        f_back = None
        f_lineno = 1

    p._add_sample(_Frame(), 2.0, threading.get_ident())
    assert p.idle_samples == 1
    assert p.get_profile()["count"] == 1  # idle sample stayed out


def test_profile_records_and_speedscope_roundtrip():
    budget = WallBudget()
    with budget.phase("engine.drain"):
        pass
    p = ControlPlaneProfiler(
        idents=lambda: [], wall=budget, interval=0.001, cycle=60.0,
    )
    p._last_cycle = 0.0
    with budget.phase("egress.flush"):
        p._add_sample(sys_frame(), 1.0, threading.get_ident())
    records = profile_records("scheduler", p, budget, None)
    kinds = [r["kind"] for r in records]
    assert kinds[0] == "head" and "profile" in kinds and "samples" in kinds
    head = records[0]
    assert "engine.drain" in head["wall_seconds"]
    tree = next(r for r in records if r["kind"] == "profile")["tree"]
    ss = profile_to_speedscope(tree)
    json.dumps(ss)  # must be JSON-safe
    prof = ss["profiles"][0]
    assert prof["samples"] and len(prof["samples"]) == len(prof["weights"])
    assert sum(prof["weights"]) == tree["count"]
    # every sample's frame indices are valid
    nframes = len(ss["shared"]["frames"])
    assert all(0 <= i < nframes for s in prof["samples"] for i in s)


# ------------------------------------------------- shared-watcher lifecycle


def _wait_for(cond, timeout=5.0):
    deadline = _time.monotonic() + timeout
    while _time.monotonic() < deadline:
        if cond():
            return True
        _time.sleep(0.01)
    return False


def test_shared_watcher_register_unregister_and_thread_exit():
    """A fresh watcher spins its sampler thread up on first register,
    lingers briefly after the last unregister, exits, and restarts on
    re-registration (profile.py _SharedWatcher._run)."""
    w = _SharedWatcher()
    p = Profiler(interval=0.005, cycle=60.0, idents=lambda: [])
    p._last_sample = 0.0
    p._last_cycle = 0.0
    w.register(p)
    t1 = w._thread
    assert t1 is not None and t1.is_alive()
    w.unregister(p)
    # linger is 0.5s: the thread must exit after it
    assert _wait_for(lambda: not t1.is_alive(), timeout=3.0)
    # re-registration restarts a fresh sampler thread
    w.register(p)
    t2 = w._thread
    assert t2 is not None and t2.is_alive() and t2 is not t1
    w.unregister(p)
    assert _wait_for(lambda: not t2.is_alive(), timeout=3.0)


def test_shared_watcher_broken_idents_drops_only_offender():
    """A broken _due_idents callback must drop THAT profiler and leave
    the rest sampling (profile.py:137-141)."""
    w = _SharedWatcher()
    ident = threading.get_ident()

    healthy = Profiler(interval=0.005, cycle=60.0, idents=lambda: [ident])

    def broken_idents():
        raise RuntimeError("boom")

    broken = Profiler(interval=0.005, cycle=60.0, idents=broken_idents)
    for p in (healthy, broken):
        p._last_sample = 0.0
        p._last_cycle = _time.monotonic()
    w.register(healthy)
    w.register(broken)
    try:
        # the broken profiler is unregistered by the watcher; the
        # healthy one keeps accumulating samples of this (busy) thread
        assert _wait_for(lambda: broken not in w._profilers)
        assert healthy in w._profilers
        before = healthy.get_profile()["count"]
        assert _wait_for(
            lambda: healthy.get_profile()["count"] > before
        ), "healthy profiler stopped sampling after the offender was dropped"
    finally:
        w.unregister(healthy)
        w.unregister(broken)


# ----------------------------------------------------------- stall watchdog


def test_loop_watchdog_single_capture_per_episode():
    from distributed_tpu.tracing import FlightRecorder

    budget = WallBudget()
    tr = FlightRecorder(enabled=True, ring_size=64)
    wd = LoopWatchdog(
        trace=tr, wall=budget, interval=0.01, stall_threshold=0.08
    )
    blocked = threading.Event()

    def fake_loop():
        for _ in range(3):
            wd.tick()
            _time.sleep(0.01)
        budget.push("engine.drain", "stim-stall")
        blocked.set()
        _time.sleep(0.3)  # the stall: 0.3s >> threshold 0.08s
        budget.pop()
        for _ in range(10):  # recovered and ticking: no second capture
            wd.tick()
            _time.sleep(0.02)

    t = threading.Thread(target=fake_loop)
    t.start()
    blocked.wait(5)
    wd.start(t.ident)
    t.join()
    wd.stop()
    assert wd.stalls_total == 1
    stall = wd.stalls[0]
    assert stall["phase"] == "engine.drain"
    assert stall["stim"] == "stim-stall"
    assert "fake_loop" in stall["traceback"]
    events = [e for e in tr.tail() if e["cat"] == "stall"]
    assert len(events) == 1
    assert events[0]["name"] == "engine.drain"
    assert "fake_loop" in events[0]["key"]
    assert events[0]["n"] >= 80  # lag in ms, at least the threshold


@gen_test()
async def test_loop_watchdog_names_blocking_coroutine_frame():
    """A busy-wait inside a coroutine on a real event loop: exactly one
    capture naming the blocking frame, one ``stall`` ring event, and a
    watchdog that re-arms once the loop ticks again."""
    from distributed_tpu.tracing import FlightRecorder

    tr = FlightRecorder(enabled=True, ring_size=64)
    wd = LoopWatchdog(trace=tr, interval=0.02, stall_threshold=0.12)
    wd.start(threading.get_ident())

    async def ticker():
        while True:
            wd.tick()
            await asyncio.sleep(0.02)

    tick_task = asyncio.create_task(ticker())
    try:
        await asyncio.sleep(0.1)  # healthy ticks first

        def _block_loop():
            t0 = _time.perf_counter()
            while _time.perf_counter() - t0 < 0.35:
                pass  # the loop's thread is pinned here

        _block_loop()
        await asyncio.sleep(0.3)  # recovery: the watchdog re-arms
    finally:
        tick_task.cancel()
        wd.stop()
    assert wd.stalls_total == 1
    assert "_block_loop" in wd.stalls[0]["traceback"]
    events = [e for e in tr.tail() if e["cat"] == "stall"]
    assert len(events) == 1 and "_block_loop" in events[0]["key"]
    assert wd.hist_lag.count > 0


def test_engine_flood_samples_carry_phases_and_arm_attribution():
    """A profiled engine flood: phase-stamped samples under
    ``engine.drain``, whose wall is nonzero; with arm attribution on,
    per-arm rows hold more than a fifth of the engine's wall (a floor
    for a noisy host; tests/test_profile_run.py gates the 0.70 share on
    the longer simulator table)."""
    from test_overhead import flood, make_state

    state = make_state()
    main = threading.get_ident()
    prof = ControlPlaneProfiler(idents=lambda: [main], wall=state.wall,
                                interval=0.002)
    prof.start()
    try:
        flood(state)
    finally:
        prof.stop()
    assert state.wall.snapshot().get("engine.drain", 0.0) > 0.0
    assert prof.total_samples > 0
    assert any(k.startswith("phase:engine.drain")
               for k in prof.get_profile()["children"])
    assert any(ph == "engine.drain" for _, ph, _s in prof.samples)

    with config.set({"scheduler.profile.arm-attribution": True}):
        state = make_state()
    flood(state)
    totals = state.wall.snapshot()
    arms = sum(v for k, v in totals.items()
               if k.startswith("engine.scalar-arm:"))
    assert arms > 0, "arm attribution produced no per-arm rows"
    assert arms / (totals.get("engine.drain", 0.0) + arms) > 0.2, totals


# ------------------------------------------------------------- live cluster


@gen_test()
async def test_profile_routes_and_get_profile_scope():
    """Both roles serve /profile JSONL; Scheduler.get_profile grows a
    scope= arg whose 'scheduler' scope includes the control-plane tree
    without touching workers."""
    from test_observability import http_get, new_cluster

    from distributed_tpu.client.client import Client

    async with await new_cluster() as cluster:
        async with Client(cluster.scheduler_address) as c:
            await c.gather(c.map(lambda x: x + 1, range(20)))
            sched = cluster.scheduler
            assert sched.cp_profiler is not None
            assert sched.watchdog is not None and sched.watchdog.ticks_total >= 0
            # keep the loop busy until the 20ms sampler catches at least
            # one NON-idle control-plane stack (idle select() samples
            # deliberately stay out of the tree)
            for _ in range(200):
                if sched.cp_profiler.get_profile()["count"] > 0:
                    break
                await c.gather(c.map(lambda x: x + 1, range(50)))
            own = await sched.get_profile(scope="scheduler")
            merged = await sched.get_profile(scope="all")
            workers_only = await sched.get_profile(scope="workers")
            assert own["count"] > 0
            assert merged["count"] >= own["count"]
            assert merged["count"] >= workers_only["count"]
            try:
                await sched.get_profile(scope="nope")
            except ValueError:
                pass
            else:
                raise AssertionError("bad scope accepted")

            # wall budget recorded the engine seams on the live path
            wall = sched.state.wall.snapshot()
            assert wall.get("engine.drain", 0.0) > 0.0
            assert "egress.flush" in wall

            # /profile routes on both roles
            status, body = await http_get(
                sched.http_server.port, "/profile"
            )
            assert status == 200
            records = [
                json.loads(ln) for ln in body.decode().splitlines() if ln
            ]
            assert records[0]["kind"] == "head"
            assert records[0]["role"] == "scheduler"
            assert "engine.drain" in records[0]["wall_seconds"]
            assert any(r["kind"] == "profile" for r in records)
            worker = cluster.workers[0]
            status, body = await http_get(
                worker.http_server.port, "/profile"
            )
            assert status == 200
            wrecords = [
                json.loads(ln) for ln in body.decode().splitlines() if ln
            ]
            assert wrecords[0]["role"] == "worker"
            which = {
                r.get("which") for r in wrecords if r["kind"] == "profile"
            }
            assert {"loop", "exec"} <= which

            # metrics expose the new families on both roles
            for port in (sched.http_server.port, worker.http_server.port):
                status, body = await http_get(port, "/metrics")
                text = body.decode()
                assert "dtpu_wall_seconds_total" in text
                assert "dtpu_loop_lag_seconds_bucket" in text
                assert "dtpu_profile_samples_total" in text

            # cluster dump carries the profile tail
            dump = await sched.get_cluster_state()
            prof = dump["scheduler"]["profile"]
            assert "wall_seconds" in prof and "tree" in prof
            slim = await sched.get_cluster_state(exclude=["profile"])
            assert "profile" not in slim["scheduler"]


# ------------------------------------------------------------- config gate


@gen_test()
async def test_selfprofile_disabled_leaves_no_machinery():
    """scheduler.profile.enabled=False: no sampler, no watchdog — the
    knob is the kill switch for constrained hosts."""
    from test_observability import new_cluster

    with config.set({"scheduler.profile.enabled": False}):
        async with await new_cluster() as cluster:
            assert cluster.scheduler.cp_profiler is None
            assert cluster.scheduler.watchdog is None
            worker = cluster.workers[0]
            assert worker.cp_profiler is None
            assert worker.watchdog is None


def test_merge_keeps_phase_pseudo_nodes():
    a = create()
    a["count"] = 2
    a["children"]["phase:engine.drain"] = {
        "count": 2, "children": {},
        "identifier": "phase:engine.drain",
        "description": "phase:engine.drain",
    }
    b = create()
    b["count"] = 3
    b["children"]["phase:engine.drain"] = {
        "count": 3, "children": {},
        "identifier": "phase:engine.drain",
        "description": "phase:engine.drain",
    }
    m = merge(a, b)
    assert m["count"] == 5
    assert m["children"]["phase:engine.drain"]["count"] == 5

"""Native transition engine (native/engine.cpp + scheduler/native_engine.py).

The contract under test (docs/native_engine.md): floods and
recommendation rounds driven through the compiled engine produce
BIT-IDENTICAL outputs to the pure-python oracle — final task states,
per-key stories, journals, ledger digests, and per-destination message
multisets — with anything the C++ core does not model escaping to the
oracle per key.  Plus the fallback chain: no toolchain / kill-switch =>
the oracle engages silently.
"""

from __future__ import annotations

import logging
import os
import random
import subprocess
import sys
import time

import pytest

from distributed_tpu import config, native
from distributed_tpu.scheduler.state import SchedulerState
from distributed_tpu.utils.collections import OrderedSet


def _native_state(**kw):
    state = SchedulerState(**kw)
    if not state.attach_native(build=True):
        pytest.skip("native toolchain unavailable")
    return state


class _Spec:
    __slots__ = ()

    def __repr__(self):
        return "<spec>"


SPEC = _Spec()

OVR = {
    "scheduler.trace.enabled": False,
    "scheduler.native-engine.enabled": False,  # explicit attach only
    "scheduler.native-engine.min-flood": 0,    # no oracle routing floor
}


class _StepClock:
    """Deterministic injectable clock in the VirtualClock mold: time
    only advances when the harness steps it, never per read — so both
    engines see identical stamps for identical work.  (Clock-call
    COUNTS are explicitly not part of the parity contract: the native
    path hoists reads the oracle performs per row.)"""

    def __init__(self):
        self.t = 0.0

    def step(self):
        self.t += 0.25

    def __call__(self):
        return self.t


def build_flood_state(native_on, n_workers=32, width=64, layers=8,
                      fanin=2, seed=0, journal=False, restrictions=False,
                      actors=False, audit=True):
    """A SchedulerState holding ``width`` scattered roots and ``layers``
    layers of ``width`` tasks on random earlier ones.  ``audit`` gives it
    the parity harness's step clock and ledger digests; without them it
    is the timed engine of the speedup test."""
    with config.set(OVR):
        state = SchedulerState(validate=False,
                               **({"clock": _StepClock()} if audit else {}))
        state.ledger.digest_enabled = audit
        if native_on:
            if not state.attach_native(build=True):
                pytest.skip("native toolchain unavailable")
        if journal:
            state.trace.journal_start()
        for i in range(n_workers):
            state.add_worker_state(
                f"sim://w{i}", nthreads=1, memory_limit=2**30,
                name=f"w{i}",
            )
        rng = random.Random(seed)
        addrs = list(state.workers)
        prev = []
        for i in range(width):
            k = f"root-{i}"
            state.client_desires_keys([k], "c")
            recs, cm, wm = state._transition(
                k, "memory", "scatter", nbytes=65536,
                worker=addrs[i % len(addrs)],
            )
            state._transitions(recs, cm, wm, "scatter")
            prev.append(k)
        tasks, deps, prios = {}, {}, {}
        ann = {}
        rank = 0
        for j in range(layers):
            layer = [f"L{j}-{i}" for i in range(width)]
            for k in layer:
                deps[k] = {
                    prev[rng.randrange(len(prev))]
                    for _ in range(fanin)
                }
                tasks[k] = SPEC
                prios[k] = (rank,)
                rank += 1
                if restrictions and rng.random() < 0.1:
                    ann[k] = {"workers": [addrs[rng.randrange(len(addrs))]],
                              "allow_other_workers": True}
            prev = layer
        state.update_graph_core(
            tasks, deps, prev, client="c", priorities=prios,
            annotations_by_key=ann or None,
            actors=[k for k in tasks if actors and k.endswith("-0")],
            stimulus_id="graph",
        )
    return state


def _build_pair(**kwargs):
    """(oracle, native) SchedulerStates with the identical graph."""
    return [build_flood_state(native_on, **kwargs)
            for native_on in (False, True)]


def _drive(state, seed=0, err_rate=0.0, release_at=None):
    """Drive every processing task to completion via floods; returns the
    collected (client_msgs, worker_msgs) rounds."""
    rng = random.Random(seed)
    out = []
    rounds = 0
    with config.set(OVR):
        while True:
            batch = [
                (
                    ts.key, ws.address, f"fin-{rounds}-{i}",
                    {
                        "nbytes": 1024 + (hash(ts.key) % 7) * 512,
                        "typename": "int",
                        "startstops": [{
                            "action": "compute", "start": 0.0,
                            "stop": 0.01,
                        }],
                    },
                )
                for ws in state.workers.values()
                for i, ts in enumerate(list(ws.processing))
            ]
            if not batch:
                break
            state.clock.step()  # virtual time advances between floods
            if err_rate and rng.random() < err_rate:
                errs = [
                    (k, w, s, dict(exception_text="boom"))
                    for k, w, s, _kw in batch
                ]
                out.append(state.stimulus_tasks_erred_batch(errs))
            else:
                out.append(state.stimulus_tasks_finished_batch(batch))
            if release_at is not None and rounds == release_at:
                out.append(state.client_releases_keys(
                    [f"root-{i}" for i in range(4)], "c", "rel",
                ))
            rounds += 1
            assert rounds < 5000
    return out


def _freeze(obj):
    if isinstance(obj, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in obj.items()))
    if isinstance(obj, (list, tuple)):
        return tuple(_freeze(v) for v in obj)
    if isinstance(obj, (str, bytes, int, float, bool)) or obj is None:
        return obj
    return repr(type(obj))


def _canon(rounds):
    out = []
    for cm, wm in rounds:
        for d in (cm, wm):
            c = {}
            for dest, msgs in d.items():
                c[dest] = sorted(
                    (
                        _freeze({k: v for k, v in m.items()
                                 if k != "run_spec"})
                        for m in msgs
                    ),
                    key=repr,
                )
            out.append(c)
    return out


def _stories(state):
    return [row[:5] for row in state.transition_log]


def _snapshot(state):
    return {
        key: (
            ts.state,
            ts.processing_on.address if ts.processing_on else None,
            tuple(ws.address for ws in ts.who_has),
            tuple(d.key for d in ts.waiters),
            tuple(d.key for d in ts.waiting_on),
        )
        for key, ts in state.tasks.items()
    }


# ------------------------------------------------------------- parity


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_multiflood_parity(seed):
    """Randomized multi-flood traces: bit-identical states, stories,
    journals, ledger digests and message multisets vs the oracle."""
    oracle, nat = _build_pair(seed=seed, journal=True)
    ro = _drive(oracle, seed=seed, release_at=3)
    rn = _drive(nat, seed=seed, release_at=3)
    c = nat.native.counters()
    assert c["transitions"] > 0, "native never ran"
    # the compiled arms absorb their share: under 10 % escape
    assert c["escapes"] < 0.10 * (c["transitions"]
                                  + c["oracle_transitions"]), c
    assert _snapshot(oracle) == _snapshot(nat)
    assert _stories(oracle) == _stories(nat)
    assert _canon(ro) == _canon(rn)
    # journals: the counter clock makes stamps identical too
    assert list(oracle.trace.journal) == list(nat.trace.journal)
    # decision ledger: same rows, same joins, same digest
    assert oracle.ledger.digest() == nat.ledger.digest()
    assert oracle.transition_counter == nat.transition_counter


def _drive_probed(state, seed=0):
    """_drive plus randomized introspection between batches: every
    probe is a hydration barrier on the native side (TaskState /
    WorkerState properties, the story deque, the ledger digest, the
    returned lazy message dicts).  Returns the probe results so the
    harness can compare them bit-for-bit across engines."""
    rng = random.Random(seed ^ 0x5EED)
    probes = []
    rounds = 0
    with config.set(OVR):
        while True:
            batch = [
                (
                    ts.key, ws.address, f"fin-{rounds}-{i}",
                    {
                        "nbytes": 1024 + (hash(ts.key) % 7) * 512,
                        "typename": "int",
                        "startstops": [{
                            "action": "compute", "start": 0.0,
                            "stop": 0.01,
                        }],
                    },
                )
                for ws in state.workers.values()
                for i, ts in enumerate(list(ws.processing))
            ]
            if not batch:
                break
            state.clock.step()
            cm, wm = state.stimulus_tasks_finished_batch(batch)
            keys = sorted(state.tasks)
            for _ in range(rng.randrange(4)):
                ts = state.tasks[keys[rng.randrange(len(keys))]]
                probes.append((
                    ts.key, ts.state, ts.nbytes,
                    tuple(sorted(w.address for w in ts.who_has)),
                    tuple(sorted(d.key for d in ts.waiters)),
                ))
            if rng.random() < 0.5:
                probes.append(len(state.transition_log))
            if rng.random() < 0.4:
                probes.append(state.ledger.digest())
            if rng.random() < 0.4:
                probes.append(sorted(
                    (dest, len(msgs)) for dest, msgs in wm.items()
                ))
            if rng.random() < 0.4:
                addrs = sorted(state.workers)
                ws = state.workers[addrs[rng.randrange(len(addrs))]]
                probes.append((
                    ws.address, ws.occupancy, ws.nbytes,
                    len(ws.processing),
                ))
            rounds += 1
            assert rounds < 5000
    return probes


@pytest.mark.parametrize("seed", [11, 12])
def test_randomized_introspection_parity(seed):
    """The lazy-hydration property test: arbitrary python-truth reads
    between batches land on identical truth at the moment of the read,
    and the whole trace stays bit-identical vs the oracle — states,
    stories, journal, ledger digests AND the probe results themselves."""
    oracle, nat = _build_pair(seed=seed, journal=True)
    po = _drive_probed(oracle, seed=seed)
    pn = _drive_probed(nat, seed=seed)
    c = nat.native.counters()
    assert c["transitions"] > 0, "native never ran"
    assert c["hydrations"] > 0, "nothing was ever deferred"
    assert c["hydration_cache_hits"] > 0, \
        "every probe forced a replay — the cache never hit"
    assert po == pn
    assert _snapshot(oracle) == _snapshot(nat)
    assert _stories(oracle) == _stories(nat)
    assert list(oracle.trace.journal) == list(nat.trace.journal)
    assert oracle.ledger.digest() == nat.ledger.digest()
    assert oracle.transition_counter == nat.transition_counter


def test_no_introspection_flood_defers_fully():
    """A purely-native flood with nothing reading python truth parks
    its segments: zero tape rows hydrate inside the flood, and the
    first later read (here: the message dict) replays them all."""
    _oracle, nat = _build_pair(seed=13, width=16, layers=2)
    ne = nat.native
    batch = [
        (ts.key, ws.address, "nf", {"nbytes": 8})
        for ws in nat.workers.values()
        for ts in list(ws.processing)
    ]
    assert batch
    h0 = ne.hydrations
    cm, wm = nat.stimulus_tasks_finished_batch(batch)
    assert ne._pending, "flood did not defer"
    assert ne.hydrations == h0, "flood hydrated rows with no reader"
    n_msgs = sum(len(v) for v in wm.values())  # lazy read: forces sync
    assert not ne._pending
    assert ne.hydrations > h0
    assert n_msgs > 0


#: the timed engine: no flight recorder, no telemetry shadow
TIMED = {**OVR, "scheduler.telemetry.enabled": False}


def _timed_flood(state):
    """Drive to quiescence: (whole-loop seconds, seconds inside the
    stimulus calls alone, rows hydrated inside them).  Building a batch
    reads ``ws.processing``, which hydrates the previous flood's
    deferred segments; that stays outside the engine timer."""
    ne = state.native
    wall_engine, hydrated, rounds = 0.0, 0, 0
    t_all = time.perf_counter()
    while True:
        batch = [
            (
                ts.key, ws.address, f"f{rounds}-{i}",
                {"nbytes": 2048, "startstops": [{
                    "action": "compute", "start": 0.0, "stop": 0.01,
                }]},
            )
            for ws in state.workers.values()
            for i, ts in enumerate(list(ws.processing))
        ]
        if not batch:
            return time.perf_counter() - t_all, wall_engine, hydrated
        h0 = ne.hydrations if ne is not None else 0
        t0 = time.perf_counter()
        state.stimulus_tasks_finished_batch(batch)
        wall_engine += time.perf_counter() - t0
        if ne is not None:
            hydrated += ne.hydrations - h0
        rounds += 1
        assert rounds < 5000


def test_native_flood_speedup_defers_inside_the_engine_timer():
    """Against the oracle on the same flood, best of 5 pairs: >= 10x on
    the engine plane (the stimulus calls alone) and >= 1.3x on the whole
    loop with its batch building.  One noisy phase of the host shrinks
    single pairs; a real regression drops every pair.  No row hydrates
    inside the engine timer on a flood nothing reads."""

    def run(native_on):
        with config.set(TIMED):
            return _timed_flood(build_flood_state(
                native_on, layers=10, audit=False))

    run(False)  # untimed warm-up on each arm
    run(True)
    loop_ratios, engine_ratios, hydrated = [], [], 0
    for _ in range(5):
        loop_o, engine_o, _ = run(False)
        loop_n, engine_n, h = run(True)
        loop_ratios.append(loop_o / loop_n)
        engine_ratios.append(engine_o / engine_n)
        hydrated += h
    assert hydrated == 0, f"{hydrated} rows hydrated inside the engine"
    assert max(engine_ratios) >= 10.0, engine_ratios
    assert max(loop_ratios) >= 1.3, loop_ratios


def test_parity_with_erred_floods_and_restrictions():
    """Erred floods (uncompiled arm) and restricted tasks force per-key
    escapes; outputs stay bit-identical."""
    oracle, nat = _build_pair(seed=7, restrictions=True)
    ro = _drive(oracle, seed=7, err_rate=0.3)
    rn = _drive(nat, seed=7, err_rate=0.3)
    c = nat.native.counters()
    assert c.get("escape_restricted", 0) > 0
    assert _snapshot(oracle) == _snapshot(nat)
    assert _stories(oracle) == _stories(nat)
    assert _canon(ro) == _canon(rn)


def test_parity_under_check_mode(monkeypatch):
    """DTPU_NATIVE_CHECK audits the SoA against python truth after
    every flood; a clean run raises nothing and stays bit-identical."""
    monkeypatch.setenv("DTPU_NATIVE_CHECK", "1")
    oracle, nat = _build_pair(seed=3)
    assert nat.native.check
    _drive(oracle, seed=3)
    _drive(nat, seed=3)
    assert _snapshot(oracle) == _snapshot(nat)
    assert _stories(oracle) == _stories(nat)


def test_check_mode_catches_injected_divergence(monkeypatch):
    """Corrupting one SoA field makes the next flood's audit raise —
    the dual-run mode actually bites."""
    monkeypatch.setenv("DTPU_NATIVE_CHECK", "1")
    _oracle, nat = _build_pair(seed=4, width=16, layers=2)
    ne = nat.native
    # consume the dirty marks the ingest left behind (the unreachable-
    # task cull dirties its dependency neighborhood) BEFORE corrupting:
    # the next flood's resync would otherwise heal the injected
    # divergence and the audit would rightly find nothing
    ne.flush()
    ts = next(iter(nat.tasks.values()))
    ne.lib.eng_task_who_wants(ne.h, ts.nrow, 99)  # corrupt
    with pytest.raises(AssertionError, match="diverged"):
        _drive(nat, seed=4)


def test_escape_classes_rootish_and_actor():
    """Rootish groups (dep-free, width > 2x total threads) and actors
    escape to the oracle with the right labels, and outputs still
    match."""
    oracle, nat = _build_pair(
        n_workers=8, width=40, layers=3, fanin=0, seed=5
    )
    _drive(oracle, seed=5)
    _drive(nat, seed=5)
    c = nat.native.counters()
    assert c.get("escape_rootish", 0) > 0
    assert _snapshot(oracle) == _snapshot(nat)
    assert _stories(oracle) == _stories(nat)

    oracle, nat = _build_pair(
        n_workers=16, width=24, layers=2, seed=9, actors=True
    )
    _drive(oracle, seed=9)
    _drive(nat, seed=9)
    c = nat.native.counters()
    assert c.get("escape_actor", 0) > 0
    assert _snapshot(oracle) == _snapshot(nat)
    assert _stories(oracle) == _stories(nat)


def test_misrouted_completion_still_applies_metadata():
    """A completion from a worker the task was stolen away from is
    dropped by the worker guard — but the oracle pops the event's
    metadata first.  The native path must replay exactly that
    (reviewer-found parity gap; OP_META)."""
    outs = []
    for native_on in (False, True):
        with config.set(OVR):
            state = SchedulerState(validate=False)
            if native_on and not state.attach_native(build=True):
                pytest.skip("native toolchain unavailable")
            w1 = state.add_worker_state(
                "sim://w0", nthreads=1, memory_limit=2**30, name="w0"
            )
            w2 = state.add_worker_state(
                "sim://w1", nthreads=1, memory_limit=2**30, name="w1"
            )
            tasks = {"mk-0": SPEC, "mk-1": SPEC, "mk-2": SPEC}
            state.update_graph_core(
                tasks, {k: set() for k in tasks}, list(tasks),
                client="c", priorities={k: (i,) for i, k in
                                        enumerate(tasks)},
                stimulus_id="g",
            )
            ts = next(ts for ts in state.tasks.values()
                      if ts.state == "processing")
            victim = ts.processing_on
            thief = w2 if victim is w1 else w1
            # steal-style re-placement outside any transition
            state._exit_processing_common(ts)
            ts.state = "waiting"
            state._add_to_processing(ts, thief, "steal", kind="steal")
            # the victim's in-flight completion, carrying metadata
            state.stimulus_tasks_finished_batch([(
                ts.key, victim.address, "late",
                {"nbytes": 8, "metadata": {"late": True}},
            )])
            outs.append((ts.state, ts.metadata,
                         ts.processing_on.address))
    assert outs[0] == outs[1]
    assert outs[0][0] == "processing"
    assert outs[0][1] == {"late": True}


def test_sim_digest_parity_native_vs_oracle():
    """Same-seed ClusterSim runs, native on vs off: bit-identical
    whole-run digests, makespans and ledger digests (steal + AMM
    cycles included)."""
    from distributed_tpu.sim import ClusterSim, SyntheticDag

    reports = {}
    for native_on in (True, False):
        sim = ClusterSim(
            40, nthreads=1, seed=0, validate=False, native=native_on,
            config_overrides={"scheduler.telemetry.enabled": False,
                              "scheduler.native-engine.min-flood": 0},
        )
        sim.install_digest()
        if native_on and sim.state.native is None:
            pytest.skip("native toolchain unavailable")
        trace = SyntheticDag(
            n_layers=6, layer_width=80, fanin=2, seed=0,
            layers_per_chunk=2, n_roots=40, linked_chunks=False,
        )
        trace.start(sim)
        rep = sim.run()
        reports[native_on] = (
            sim.digest(), rep["virtual_makespan_s"],
            sim.state.ledger.digest(),
        )
        if native_on:
            assert sim.state.native.counters()["transitions"] > 0
    assert reports[True] == reports[False]


# ------------------------------------------------------- fallback chain


def test_native_disable_env_forces_silent_fallback():
    """DTPU_NATIVE_DISABLE=1: the pure-python fallback engages with no
    warning logged and no native attach — the no-toolchain path,
    provable on a box that has g++."""
    code = """
import logging, sys
records = []
h = logging.Handler()
h.emit = lambda r: records.append(r)
logging.getLogger("distributed_tpu").addHandler(h)
from distributed_tpu import native
assert native.disabled()
assert native.load() is None
assert native.load_nowait() is None
from distributed_tpu.scheduler.state import SchedulerState
s = SchedulerState()
assert s.native is None
assert not s.attach_native(build=True)
s.add_worker_state("tcp://x:1", nthreads=1, memory_limit=2**30)
ts = s.new_task("k1", object())
ts.priority = (0,)
s.transitions({"k1": "waiting"}, "stim")
assert s.tasks["k1"].state == "processing"
warned = [r for r in records if r.levelno >= logging.WARNING]
assert not warned, [r.getMessage() for r in warned]
print("FALLBACK_OK")
"""
    env = dict(os.environ, DTPU_NATIVE_DISABLE="1")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, timeout=120,
        env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
    )
    assert out.returncode == 0, out.stderr.decode()[-2000:]
    assert b"FALLBACK_OK" in out.stdout


def test_needs_build_keys_on_flags_and_source_list(tmp_path, monkeypatch):
    """_needs_build keys on the recorded build (the .buildinfo
    sidecar): a changed source list or flag set makes the library
    stale."""
    lib = tmp_path / "fake.so"
    lib.write_bytes(b"x")
    info = tmp_path / "fake.so.buildinfo"
    src = tmp_path / "a.cpp"
    src.write_text("// src")
    monkeypatch.setattr(native, "_LIB_PATH", str(lib))
    monkeypatch.setattr(native, "_BUILDINFO_PATH", str(info))
    monkeypatch.setattr(native, "_SOURCES", [str(src)])
    # no sidecar: stale by definition
    assert native._needs_build()
    info.write_text(__import__("json").dumps(native._build_spec()))
    os.utime(str(lib))  # newer than src
    assert not native._needs_build()
    # source list drift: same files on disk, different command
    monkeypatch.setattr(
        native, "_SOURCES", [str(src), str(tmp_path / "b.cpp")]
    )
    (tmp_path / "b.cpp").write_text("// b")
    os.utime(str(lib))
    assert native._needs_build(), "source-list drift went unnoticed"
    # flag drift, same sources
    monkeypatch.setattr(native, "_SOURCES", [str(src)])
    monkeypatch.setattr(
        native, "_FLAGS", list(native._FLAGS) + ["-DX"]
    )
    assert native._needs_build(), "flag drift went unnoticed"


def test_needs_build_keys_on_source_content_not_mtime(tmp_path, monkeypatch):
    """A library newer than its sources is still stale when a source's
    content differs from what it was built from (a copied tree can
    carry such a library); a touched but unchanged source is not."""
    lib = tmp_path / "fake.so"
    info = tmp_path / "fake.so.buildinfo"
    src = tmp_path / "a.cpp"
    src.write_text("// v1")
    monkeypatch.setattr(native, "_LIB_PATH", str(lib))
    monkeypatch.setattr(native, "_BUILDINFO_PATH", str(info))
    monkeypatch.setattr(native, "_SOURCES", [str(src)])
    lib.write_bytes(b"x")
    info.write_text(__import__("json").dumps(native._build_spec()))
    assert not native._needs_build()
    os.utime(str(src), (1, 1))  # far older than the library
    assert not native._needs_build(), "an mtime-only touch rebuilt"
    src.write_text("// v2")
    os.utime(str(src), (1, 1))
    assert native._needs_build(), "a content edit went unnoticed"


RACE_CHILD = r"""
import ctypes, sys, time
from distributed_tpu import native

src, lib, delay = sys.argv[1], sys.argv[2], float(sys.argv[3])
native._SOURCES = [src]
native._LIB_PATH = lib
native._BUILDINFO_PATH = lib + ".buildinfo"
time.sleep(delay)
assert not native._needs_build() or native._build(), "build failed"
ctypes.CDLL(lib).tdigest_new
print("LOADED")
"""

# a compiler that replaces its output as a linker does (unlink, then a
# new file) and writes it in two halves, half a second apart
SLOW_GXX = """#!{python}
import os, sys, time
out = sys.argv[sys.argv.index("-o") + 1]
data = open({built!r}, "rb").read()
if os.path.exists(out):
    os.unlink(out)
with open(out, "wb") as f:
    f.write(data[: len(data) // 2])
    f.flush()
    time.sleep(0.5)
    f.write(data[len(data) // 2:])
"""


def test_concurrent_builds_never_load_a_half_written_library(tmp_path):
    """Processes that find the library missing at staggered times (test
    workers starting together) build it one at a time, and none loads
    a file another compiler is still writing: the compiler here writes
    its output slowly, in two halves."""
    src = tmp_path / "tdigest.cpp"
    src.write_bytes(open(native._SOURCES[0], "rb").read())
    built = str(tmp_path / "built.so")
    subprocess.run(["g++", *native._FLAGS, str(src), "-o", built],
                   check=True, timeout=120)
    bin_dir = tmp_path / "bin"
    bin_dir.mkdir()
    gxx = bin_dir / "g++"
    gxx.write_text(SLOW_GXX.format(python=sys.executable, built=built))
    gxx.chmod(0o755)
    lib = str(tmp_path / "race.so")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo,
               PATH=f"{bin_dir}{os.pathsep}{os.environ['PATH']}")
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", RACE_CHILD, str(src), lib, str(0.15 * i)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
        )
        for i in range(6)
    ]
    outs = [p.communicate(timeout=240) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0 and b"LOADED" in out, err.decode()[-2000:]
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]


def test_min_flood_routes_small_floods_to_oracle():
    """Floods below scheduler.native-engine.min-flood run the oracle
    (per-flood bridge overhead outweighs the savings there)."""
    with config.set({"scheduler.trace.enabled": False,
                     "scheduler.native-engine.enabled": False,
                     "scheduler.native-engine.min-flood": 64}):
        state = SchedulerState(validate=False)
        if not state.attach_native(build=True):
            pytest.skip("native toolchain unavailable")
        state.add_worker_state(
            "sim://w0", nthreads=4, memory_limit=2**30, name="w0"
        )
        tasks = {f"t-{i}": SPEC for i in range(4)}
        state.update_graph_core(
            tasks, {k: set() for k in tasks}, list(tasks), client="c",
            priorities={k: (i,) for i, k in enumerate(tasks)},
            stimulus_id="g",
        )
        floods_before = state.native.floods
        batch = [
            (ts.key, ws.address, f"s{i}", {"nbytes": 8})
            for ws in state.workers.values()
            for i, ts in enumerate(list(ws.processing))
        ]
        assert 0 < len(batch) < 64
        state.stimulus_tasks_finished_batch(batch)
        assert state.native.floods == floods_before  # oracle routed
        for k in batch:
            assert state.tasks[k[0]].state == "memory"


def test_late_attach_first_op_is_a_flood():
    """The server attaches via the prebuild callback AFTER tasks are
    already in flight; the very first native operation is then a
    task-finished flood whose flush() must initialize its buffers
    (reviewer-found: a shared lazy-init dict made this path raise and
    silently disable the engine)."""
    with config.set(OVR):
        state = SchedulerState(validate=False)
        for i in range(4):
            state.add_worker_state(
                f"sim://w{i}", nthreads=1, memory_limit=2**30,
                name=f"w{i}",
            )
        addrs = list(state.workers)
        for i in range(8):
            k = f"r-{i}"
            state.client_desires_keys([k], "c")
            recs, cm, wm = state._transition(
                k, "memory", "sc", nbytes=256, worker=addrs[i % 4]
            )
            state._transitions(recs, cm, wm, "sc")
        tasks = {f"m-{i}": SPEC for i in range(8)}
        deps = {f"m-{i}": {f"r-{i % 8}"} for i in range(8)}
        state.update_graph_core(
            tasks, deps, list(tasks), client="c",
            priorities={k: (i,) for i, k in enumerate(tasks)},
            stimulus_id="g",
        )
        # mid-run attach (the prebuild on_ready path): everything
        # adopted dirty, nothing flushed yet
        if not state.attach_native(build=True):
            pytest.skip("native toolchain unavailable")
        batch = [
            (ts.key, ws.address, f"s{i}", {"nbytes": 8})
            for ws in state.workers.values()
            for i, ts in enumerate(list(ws.processing))
        ]
        assert batch
        state.stimulus_tasks_finished_batch(batch)
        assert state.native is not None, "flood disabled the engine"
        assert state.native.counters()["transitions"] > 0
        for k, *_ in batch:
            assert state.tasks[k].state == "memory"


def test_plugin_without_marker_forces_oracle():
    """Any plugin lacking tape_safe gates the whole flood off the
    native path (the conservative default)."""
    _oracle, nat = _build_pair(seed=6, width=8, layers=1)

    class _P:
        def transition(self, *a, **k):
            pass

    nat.plugins["opaque"] = _P()
    assert not nat.native.active()
    nat.plugins.pop("opaque")
    assert nat.native.active()


def test_wall_bills_native_phase():
    """The ctypes drain bills to engine.native nested under
    engine.drain (dtpu_wall_seconds_total)."""
    _oracle, nat = _build_pair(seed=8, width=16, layers=2)
    _drive(nat, seed=8)
    totals = nat.wall.snapshot()
    assert totals.get("engine.native", 0.0) > 0.0
    assert totals.get("engine.drain", 0.0) > 0.0


# ------------------------------------------------------------ OrderedSet


def test_ordered_set_semantics():
    s: OrderedSet = OrderedSet()
    s.add("a"), s.add("b"), s.add("c")
    s.add("a")  # re-add keeps position
    assert list(s) == ["a", "b", "c"]
    s.discard("b")
    assert list(s) == ["a", "c"]
    s.add("b")  # removed then re-added: appends
    assert list(s) == ["a", "c", "b"]
    assert s == {"a", "b", "c"}
    assert len(s) == 3 and "c" in s and "z" not in s
    # interop with plain sets in either position
    plain = {"a", "z"}
    plain -= s
    assert plain == {"z"}
    assert (s & {"a", "b"}) == {"a", "b"}
    assert list(s & {"a", "b"}) == ["a", "b"]  # keeps left order
    assert sorted({"q"} | s) == ["a", "b", "c", "q"]
    assert list(s.difference({"a"})) == ["c", "b"]
    assert s.union({"q"}) == {"a", "b", "c", "q"}
    s.remove("a")
    with pytest.raises(KeyError):
        s.remove("a")


# The PYTHONHASHSEED sweep of the partition chaos scenario lives with
# the rest of the hashseed harness: tests/test_determinism.py::
# test_partition_chaos_across_hashseeds (seeds 1/6 caught the original
# `(released, memory)` crash).


def test_ordered_set_determinism_across_hashseed():
    """Iteration order is insertion order, independent of
    PYTHONHASHSEED — the property the engine's cross-process
    determinism rests on."""
    from conftest import sweep_hashseed_stdout

    out = sweep_hashseed_stdout(
        "from distributed_tpu.utils.collections import OrderedSet\n"
        "s = OrderedSet()\n"
        "for x in ['k%d' % i for i in range(50)]: s.add(x)\n"
        "s.discard('k7'); s.add('k7')\n"
        "print(','.join(s))\n",
        seeds=("0", "1", "2"), timeout=60,
    )
    assert out.strip().startswith("k0,k1,")

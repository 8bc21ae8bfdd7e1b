"""graft-lint tests: every rule proven on seeded-violation fixtures, the
pragma/baseline suppression paths, and the CLI over the real repo (the
tier-1 CI wiring — a clean tree is an acceptance criterion, so this file
IS the lint gate)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

from distributed_tpu.analysis.core import all_rules, run_lint

REPO_ROOT = Path(__file__).resolve().parent.parent


def make_repo(tmp_path: Path, files: dict[str, str]) -> Path:
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return tmp_path


def findings_for(tmp_path, files, rule):
    root = make_repo(tmp_path, files)
    result = run_lint(root, rule_names=[rule])
    assert not result.errors, result.errors
    return result.findings


# --------------------------------------------------------------- registry


def test_registry_has_all_contract_rules():
    rules = all_rules()
    assert set(rules) >= {
        "sans-io", "monotonic-time", "blocking-in-async", "handler-parity",
        "jit-purity", "swallowed-exceptions", "mirror-parity",
        "wire-no-copy", "state-machine", "await-atomicity", "config-keys",
        "determinism",
    }
    assert len(rules) >= 12
    for rule in rules.values():
        assert rule.description and rule.scope


# ---------------------------------------------------------------- sans-io


def test_sans_io_fires_on_seeded_violations(tmp_path):
    src = """
        import asyncio
        from distributed_tpu.comm.core import connect

        async def pull(self):
            await asyncio.sleep(0)

        def load(path):
            return open(path).read()
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/scheduler/state.py": src}, "sans-io"
    )
    msgs = "\n".join(f.message for f in found)
    assert "imports 'asyncio'" in msgs
    assert "imports from 'distributed_tpu.comm'" in msgs
    assert "async/await" in msgs
    assert "open" in msgs
    assert len(found) >= 4


def test_sans_io_clean_engine_passes(tmp_path):
    src = """
        from collections import deque

        def transition(state, key):
            return {"released": "waiting"}.get(state)
    """
    assert not findings_for(
        tmp_path, {"distributed_tpu/scheduler/state.py": src}, "sans-io"
    )


def test_sans_io_ignores_out_of_scope_files(tmp_path):
    # the same IO is legal outside the transition engines
    src = "import asyncio\n"
    assert not findings_for(
        tmp_path, {"distributed_tpu/scheduler/server.py": src}, "sans-io"
    )


# --------------------------------------------------------- monotonic-time


def test_monotonic_time_fires_including_aliases(tmp_path):
    src = """
        import time
        import time as _t
        from time import sleep

        def wait_for_worker(deadline):
            t0 = time.time()
            _t.sleep(0.1)
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/scheduler/ttl.py": src}, "monotonic-time"
    )
    msgs = "\n".join(f.message for f in found)
    assert "time.time()" in msgs
    assert "time.sleep()" in msgs
    assert "imports wall-clock" in msgs
    assert len(found) == 3


def test_monotonic_time_allows_sanctioned_clocks(tmp_path):
    src = """
        from time import monotonic, perf_counter

        from distributed_tpu.utils.misc import time, wall_clock

        def stamp():
            return time(), wall_clock(), monotonic(), perf_counter()
    """
    assert not findings_for(
        tmp_path, {"distributed_tpu/scheduler/ttl.py": src}, "monotonic-time"
    )


# ------------------------------------------------------ blocking-in-async


def test_blocking_in_async_fires(tmp_path):
    src = """
        import subprocess
        import time

        async def handler(self, path):
            time.sleep(1)
            subprocess.run(["ls"])
            with open(path) as f:
                f.read()
            self._lock.acquire()
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/worker/srv.py": src}, "blocking-in-async"
    )
    msgs = "\n".join(f.message for f in found)
    assert "time.sleep" in msgs
    assert "subprocess.run" in msgs
    assert "sync file IO" in msgs
    assert "lock.acquire" in msgs
    assert len(found) == 4


def test_blocking_in_async_exempts_executor_targets_and_sync_defs(tmp_path):
    src = """
        import asyncio
        import time

        def plain(path):
            time.sleep(1)  # sync helper: not loop code
            return open(path).read()

        async def handler(loop, path):
            def _work():
                time.sleep(1)  # executor target
                with open(path) as f:
                    return f.read()

            await asyncio.sleep(0.1)
            return await loop.run_in_executor(None, _work)
    """
    assert not findings_for(
        tmp_path, {"distributed_tpu/worker/srv.py": src}, "blocking-in-async"
    )


# --------------------------------------------------------- handler-parity


def test_handler_parity_unknown_rpc_op(tmp_path):
    src = """
        class Worker:
            def __init__(self):
                handlers = {"get_data": self.get_data}

            def get_data(self, keys=()):
                return keys

            async def fetch(self, addr):
                return await self.rpc(addr).get_dta(keys=[])
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/worker/srv.py": src}, "handler-parity"
    )
    assert len(found) == 1
    assert "get_dta" in found[0].message and "no server registers" in found[0].message


def test_handler_parity_keyword_mismatch(tmp_path):
    src = """
        class Worker:
            def __init__(self):
                handlers = {"get_data": self.get_data}

            def get_data(self, comm, keys=()):
                return keys

            async def fetch(self, addr):
                return await self.rpc(addr).get_data(keys=[], who="me")
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/worker/srv.py": src}, "handler-parity"
    )
    assert len(found) == 1
    assert "who" in found[0].message


def test_handler_parity_accepts_update_registration_and_stream_msgs(tmp_path):
    src = """
        class Ext:
            def __init__(self, scheduler):
                scheduler.stream_handlers.update(
                    {"shuffle-ping": self.ping}
                )

            def ping(self, id=None, stimulus_id=None):
                return id

        class Worker:
            def tell(self):
                self.batched_stream.send(
                    {"op": "shuffle-ping", "id": 1, "stimulus_id": "s"}
                )
    """
    assert not findings_for(
        tmp_path, {"distributed_tpu/shuffle/ext.py": src}, "handler-parity"
    )


def test_handler_parity_stream_msg_keyword_not_accepted(tmp_path):
    src = """
        class Server:
            def __init__(self):
                stream_handlers = {"task-done": self.handle_done}

            def handle_done(self, key=None):
                return key

            def report(self):
                self.batched_stream.send(
                    {"op": "task-done", "key": "k", "nbytes": 3}
                )
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/worker/srv.py": src}, "handler-parity"
    )
    assert len(found) == 1
    assert "nbytes" in found[0].message


def test_handler_parity_learns_manual_dispatch_arms(tmp_path):
    src = """
        def consume(q):
            msg = q.get()
            if msg.get("op") != "started":
                raise RuntimeError(msg)

        def produce(q, addr):
            q.put({"op": "started", "address": addr})
    """
    assert not findings_for(
        tmp_path, {"distributed_tpu/worker/boot.py": src}, "handler-parity"
    )


# ------------------------------------------------------------- jit-purity


def test_jit_purity_fires_on_host_syncs_and_captures(tmp_path):
    src = """
        import functools

        import jax
        import jax.numpy as jnp
        import numpy as np

        _CACHE = {}

        @functools.partial(jax.jit, static_argnames=("K",))
        def kern(x, K):
            n = float(x)
            k = float(K)  # static arg: concrete python value, fine
            v = x.item()
            h = np.asarray(x)
            return jnp.sum(x) + len(_CACHE)

        def call(x):
            return kern(x, K=[1, 2])
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/ops/kern.py": src}, "jit-purity"
    )
    msgs = "\n".join(f.message for f in found)
    assert "float() on a traced value" in msgs
    assert ".item() forces" in msgs
    assert "numpy.asarray on a traced value" in msgs
    assert "mutable module global '_CACHE'" in msgs
    assert "unhashable literal for static arg 'K'" in msgs
    assert len(found) == 5  # float(K) must NOT be flagged


def test_jit_purity_flags_mutable_static_default_and_jit_wrap(tmp_path):
    src = """
        import jax
        import jax.numpy as jnp

        def make(n):
            def shard(x, meta=[]):
                return jnp.sum(x) + meta.count(0) + x.tolist()[0]

            return jax.jit(shard, static_argnames=("meta",))
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/ops/wrap.py": src}, "jit-purity"
    )
    msgs = "\n".join(f.message for f in found)
    assert "mutable (unhashable) default" in msgs
    assert ".tolist()" in msgs
    assert len(found) == 2


def test_jit_purity_clean_kernel_passes(tmp_path):
    src = """
        import functools

        import jax
        import jax.numpy as jnp

        _EPS = 1e-6  # immutable scalar global: fine to close over

        @functools.partial(jax.jit, static_argnames=("K",))
        def kern(costs, K):
            top = jax.lax.top_k(costs, K)[0]
            return jnp.where(top > _EPS, top, 0.0)

        def host_wrapper(costs_host, K):
            import numpy as np

            return np.asarray(kern(jnp.asarray(costs_host), K=int(K)))
    """
    assert not findings_for(
        tmp_path, {"distributed_tpu/ops/kern.py": src}, "jit-purity"
    )


# ------------------------------------------------- swallowed-exceptions


def test_swallowed_exceptions_fires(tmp_path):
    src = """
        def dispatch(handler):
            try:
                handler()
            except Exception:
                pass
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/rpc/disp.py": src}, "swallowed-exceptions"
    )
    assert len(found) == 1


def test_swallowed_exceptions_allows_logged_or_narrow(tmp_path):
    src = """
        import logging

        logger = logging.getLogger(__name__)

        def dispatch(handler):
            try:
                handler()
            except KeyError:
                pass  # narrow: deliberate
            except Exception:
                logger.exception("handler failed")
    """
    assert not findings_for(
        tmp_path, {"distributed_tpu/rpc/disp.py": src}, "swallowed-exceptions"
    )


# ----------------------------------------------------------- mirror-parity


def test_mirror_parity_fires_on_rogue_mutations(tmp_path):
    src = """
        def sneak_occupancy(ws, delta):
            ws.occupancy += delta

        def sneak_status(ws):
            ws.status = "paused"

        def sneak_replica(ws, ts):
            ws.has_what[ts] = None
            ws.nbytes += 10

        def sneak_container(ws, ts):
            ws.processing.pop(ts, None)
            del ws.has_what[ts]
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/scheduler/rogue.py": src}, "mirror-parity"
    )
    fields = sorted(
        f.message.split("mirrored field `")[1].split("`")[0] for f in found
    )
    assert fields == [
        "has_what", "has_what", "nbytes", "occupancy", "processing", "status",
    ], found


def test_mirror_parity_allows_helpers_scope_and_reads(tmp_path):
    src = """
        class WorkerState:
            def __init__(self):
                self.occupancy = 0.0
                self.status = "running"

            def clean(self):
                ws = WorkerState()
                ws.status = self.status
                return ws

        class SchedulerState:
            def _adjust_occupancy(self, ws, delta):
                ws.occupancy = max(0.0, ws.occupancy + delta)

            def add_replica(self, ts, ws):
                ws.nbytes += ts.nbytes
                ws.has_what[ts] = None

            def set_worker_status(self, ws, status):
                ws.status = status

        def reads_are_fine(ws):
            return ws.occupancy / max(ws.nthreads, 1), ws.processing.get(None)

        def other_objects_are_fine(ts, client):
            ts.nbytes = 5          # TaskState, not a worker
            client.status = "x"    # not a worker-state binding name
    """
    assert not findings_for(
        tmp_path, {"distributed_tpu/scheduler/state.py": src}, "mirror-parity"
    )
    # worker-side modules share field names but keep their own state:
    # out of scope by construction
    rogue = """
        def worker_side(ws):
            ws.occupancy = 1.0
    """
    assert not findings_for(
        tmp_path, {"distributed_tpu/worker/state_machine.py": rogue},
        "mirror-parity",
    )


# ------------------------------------------------------ soa-hydration


def test_soa_hydration_fires_on_raw_slot_writes(tmp_path):
    src = """
        def sneak_state(ts):
            ts._state = "memory"

        def sneak_relation(ts, ws):
            ts._waiting_on.add(ts)
            ws._processing[ts] = 1.0
            ws._occupancy += 2.0

        def sneak_alias(ts):
            push = ts._waiters.add
            return push

        def sneak_log(s, row):
            s._transition_log.append(row)
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/scheduler/rogue.py": src}, "soa-hydration"
    )
    fields = sorted(
        f.message.split("SoA-backed slot `")[1].split("`")[0] for f in found
    )
    assert fields == [
        "_occupancy", "_processing", "_state", "_transition_log",
        "_waiters", "_waiting_on",
    ], found


def test_soa_hydration_allows_registered_helpers_and_reads(tmp_path):
    src = """
        class TaskState:
            def __init__(self):
                self._state = "released"
                self._waiting_on = set()

            @property
            def state(self):
                return self._state

            @state.setter
            def state(self, value):
                self._state = value

        class NativeEngine:
            def _apply_tape_inner(self, ts, s, row):
                ts._state = "memory"
                log = s._transition_log.append
                log(row)

            def sync(self, ts):
                ts._nbytes = 5

        def reads_are_fine(ts):
            return ts._state, len(ts._waiting_on)

        def other_underscores_are_fine(ts, obj):
            ts._nrow_cache = 1       # not an SoA-backed slot
            obj._state = "x"         # not a task/worker/state binding
    """
    assert not findings_for(
        tmp_path, {"distributed_tpu/scheduler/state.py": src}, "soa-hydration"
    )
    # worker-side state machines keep their own underscore fields
    rogue = """
        def worker_side(ws):
            ws._occupancy = 1.0
    """
    assert not findings_for(
        tmp_path, {"distributed_tpu/worker/state_machine.py": rogue},
        "soa-hydration",
    )


# ------------------------------------------------------- wire-no-copy


def test_wire_no_copy_fires_on_materialization(tmp_path):
    src = """
        def write_frames(writer, frames):
            for f in frames:
                writer.write(bytes(f))

        def reassemble(parts):
            return b"".join(bytes(p) for p in parts)
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/comm/rogue.py": src}, "wire-no-copy"
    )
    # bytes(f), b"".join(...), bytes(p) inside the genexp
    assert len(found) == 3, found
    assert any("join" in f.message for f in found)


def test_wire_no_copy_allows_sanctioned_idioms(tmp_path):
    src = """
        import struct

        def scatter(writer, frames):
            for f in frames:
                writer.write(f)            # pass-through, no copy

        def gather(parts):
            out = bytearray(sum(len(p) for p in parts))
            pos = 0
            for p in parts:
                out[pos:pos + len(p)] = p  # one preallocated gather
                pos += len(p)
            return memoryview(out).toreadonly()

        def construction_not_conversion(n):
            return bytes(16), struct.pack("<Q", n), bytes()

        def outside_scope_is_fine():
            pass
    """
    assert not findings_for(
        tmp_path, {"distributed_tpu/protocol/clean.py": src}, "wire-no-copy"
    )
    # scheduler code may materialize freely: out of scope by construction
    rogue = """
        def report(frames):
            return b"".join(bytes(f) for f in frames)
    """
    assert not findings_for(
        tmp_path, {"distributed_tpu/scheduler/report.py": rogue},
        "wire-no-copy",
    )


def test_wire_no_copy_pragma_suppresses(tmp_path):
    src = """
        def error_repr(frames):
            # graft-lint: allow[wire-no-copy] error-path repr only
            return repr(bytes(frames[0]))
    """
    root = make_repo(tmp_path, {"distributed_tpu/comm/err.py": src})
    result = run_lint(root, rule_names=["wire-no-copy"])
    assert not result.findings
    assert result.suppressed == 1


# ------------------------------------------------------ pragma / baseline


def test_inline_pragma_suppresses_with_reason(tmp_path):
    src = """
        def dispatch(handler):
            try:
                handler()
            # graft-lint: allow[swallowed-exceptions] probe path, outcome irrelevant
            except Exception:
                pass
    """
    root = make_repo(tmp_path, {"distributed_tpu/rpc/disp.py": src})
    result = run_lint(root, rule_names=["swallowed-exceptions"])
    assert not result.findings
    assert result.suppressed == 1


def test_inline_pragma_without_reason_does_not_suppress(tmp_path):
    src = """
        def dispatch(handler):
            try:
                handler()
            # graft-lint: allow[swallowed-exceptions]
            except Exception:
                pass
    """
    root = make_repo(tmp_path, {"distributed_tpu/rpc/disp.py": src})
    result = run_lint(root, rule_names=["swallowed-exceptions"])
    assert len(result.findings) == 1


def test_baseline_entry_suppresses_and_requires_reason(tmp_path):
    src = """
        def dispatch(handler):
            try:
                handler()
            except Exception:
                pass
    """
    root = make_repo(tmp_path, {"distributed_tpu/rpc/disp.py": src})
    (root / "graft-lint-baseline.toml").write_text(textwrap.dedent("""
        [[allow]]
        rule = "swallowed-exceptions"
        path = "distributed_tpu/rpc/disp.py"
        symbol = "dispatch"
        reason = "probe path, outcome irrelevant"
    """))
    result = run_lint(root, rule_names=["swallowed-exceptions"])
    assert not result.findings and result.suppressed == 1

    # an entry with no reason is itself an error, and never suppresses
    (root / "graft-lint-baseline.toml").write_text(textwrap.dedent("""
        [[allow]]
        rule = "swallowed-exceptions"
        path = "distributed_tpu/rpc/disp.py"
    """))
    result = run_lint(root, rule_names=["swallowed-exceptions"])
    assert len(result.findings) == 1
    assert any("no reason" in e for e in result.errors)
    assert result.exit_code == 1


def test_baseline_stale_entries_are_reported(tmp_path):
    root = make_repo(tmp_path, {"distributed_tpu/rpc/disp.py": "x = 1\n"})
    (root / "graft-lint-baseline.toml").write_text(textwrap.dedent("""
        [[allow]]
        rule = "swallowed-exceptions"
        path = "distributed_tpu/rpc/gone.py"
        reason = "was real once"
    """))
    result = run_lint(root)
    assert result.stale_baseline


def test_config_scoping_and_disable(tmp_path):
    src = "import asyncio\n"
    root = make_repo(tmp_path, {"distributed_tpu/graph/order.py": src})
    assert run_lint(root, rule_names=["sans-io"]).findings
    (root / "graft-lint.toml").write_text(textwrap.dedent("""
        [rules.sans-io]
        exclude = ["distributed_tpu/graph/order.py"]
    """))
    assert not run_lint(root, rule_names=["sans-io"]).findings
    (root / "graft-lint.toml").write_text(textwrap.dedent("""
        [rules.sans-io]
        enabled = false
    """))
    assert not run_lint(root, rule_names=["sans-io"]).findings


# ------------------------------------------------------- CLI / repo gate


def test_cli_json_clean_on_this_repo():
    """The tier-1 lint gate: the real tree must be graft-lint clean.

    Runs the module CLI exactly as CI does; any new violation (or a
    broken/stale-reasonless baseline entry) fails this test."""
    proc = subprocess.run(
        [sys.executable, "-m", "distributed_tpu.analysis", "--format", "json",
         "--root", str(REPO_ROOT)],
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report["findings"] == []
    assert report["errors"] == []


def test_cli_determinism_clean_on_this_repo():
    """The determinism gate on its own: every decision/digest/journal
    surface in the real tree is free of hash-seed-ordered iteration
    (docs/determinism.md).  Split from the full-lint gate so a
    determinism regression names its rule in the failure."""
    proc = subprocess.run(
        [sys.executable, "-m", "distributed_tpu.analysis",
         "--rule", "determinism", "--format", "json",
         "--root", str(REPO_ROOT)],
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report["findings"] == []
    assert report["errors"] == []


def test_cli_list_rules():
    proc = subprocess.run(
        [sys.executable, "-m", "distributed_tpu.analysis", "--list-rules"],
        capture_output=True, text=True, timeout=60, cwd=REPO_ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0
    for name in ("sans-io", "monotonic-time", "blocking-in-async",
                 "handler-parity", "jit-purity", "swallowed-exceptions",
                 "state-machine", "await-atomicity", "config-keys"):
        assert name in proc.stdout


# ------------------------------------------------- state-machine (rule 9)


#: a minimal but complete machine: every edge reachable, every handler
#: registered, batch arm matching its oracle
CLEAN_MACHINE = """
    ALL_TASK_STATES = ("released", "waiting", "memory")

    class S:
        def __init__(self):
            self._transitions_table = {
                ("released", "waiting"): self._transition_released_waiting,
                ("waiting", "memory"): self._transition_waiting_memory,
                ("waiting", "released"): self._transition_waiting_released,
                ("memory", "released"): self._transition_memory_released,
            }

        def _transition_released_waiting(self, key, stimulus_id):
            return {}, {}, {}

        def _transition_waiting_memory(self, key, stimulus_id):
            return {}, {}, {}

        def _transition_waiting_released(self, key, stimulus_id):
            return {}, {}, {}

        def _transition_memory_released(self, key, stimulus_id):
            return {}, {}, {}

        def stimulus_done(self, ts, recommendations):
            if ts.state == "released":
                recommendations[ts.key] = "waiting"
            recommendations[ts.key] = "memory"
            recommendations[ts.key] = "released"
            return recommendations
"""


def test_state_machine_clean_fixture_passes(tmp_path):
    assert not findings_for(
        tmp_path, {"distributed_tpu/scheduler/state.py": CLEAN_MACHINE},
        "state-machine",
    )


def test_state_machine_flags_unresolvable_pair(tmp_path):
    # (released, memory) is in no table, and the through-released
    # fallback cannot apply when the start already IS released
    src = CLEAN_MACHINE + """
        def bad(self, dts, recommendations):
            if dts.state == "released":
                recommendations[dts.key] = "memory"
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/scheduler/state.py": src},
        "state-machine",
    )
    pair = [f for f in found if "no registered transition" in f.message]
    assert len(pair) == 1 and "(released, memory)" in pair[0].message
    assert pair[0].symbol == "bad"


def test_state_machine_accepts_released_fallback(tmp_path):
    # (memory, waiting) missing, but memory->released and
    # released->waiting both exist: the engine routes through released
    src = CLEAN_MACHINE + """
        def ok(self, dts, recommendations):
            if dts.state == "waiting":
                recommendations[dts.key] = "memory"   # direct
            if dts.state == "memory":
                recommendations[dts.key] = "waiting"  # via released
    """
    assert not findings_for(
        tmp_path, {"distributed_tpu/scheduler/state.py": src},
        "state-machine",
    )


def test_state_machine_flags_unknown_state(tmp_path):
    src = CLEAN_MACHINE + """
        def typo(self, ts, recommendations):
            recommendations[ts.key] = "wating"
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/scheduler/state.py": src},
        "state-machine",
    )
    assert len(found) == 1 and "'wating'" in found[0].message


def test_state_machine_flags_unreachable_edge_and_dead_handler(tmp_path):
    src = """
        class S:
            def __init__(self):
                self._transitions_table = {
                    ("released", "waiting"): self._transition_released_waiting,
                    ("waiting", "queued"): self._transition_waiting_queued,
                }

            def _transition_released_waiting(self, key):
                return {}

            def _transition_waiting_queued(self, key):
                return {}

            def _transition_memory_forgotten(self, key):
                return {}

            def stimulus(self, ts, recommendations):
                recommendations[ts.key] = "waiting"
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/scheduler/state.py": src},
        "state-machine",
    )
    msgs = "\n".join(f.message for f in found)
    # nothing ever emits "queued": the edge is dead weight
    assert "(waiting, queued)" in msgs and "unreachable" in msgs
    # a handler in no table, called from nowhere
    assert "_transition_memory_forgotten" in msgs
    assert len(found) == 2


def test_state_machine_flags_batch_oracle_drift(tmp_path):
    src = CLEAN_MACHINE + """
        def stimulus_task_done(self, key):
            return self._transition(key, "memory", "sid")

        def stimulus_tasks_done_batch(self, items):
            for key in items:
                self._transition(key, "released", "sid")

        def stimulus_orphan_batch(self, items):
            return items
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/scheduler/state.py": src},
        "state-machine",
    )
    msgs = "\n".join(f.message for f in found)
    assert "different transition surface" in msgs
    assert "stimulus_orphan_batch" in msgs and "no scalar oracle" in msgs
    assert len(found) == 2


def test_state_machine_emissions_cross_module(tmp_path):
    # an emission in a sibling scheduler module resolves against the
    # machine owning the subpackage
    other = """
        def release_all(self, state, keys):
            recs = {k: "wating" for k in keys}
            return state.transitions(recs, "sid")
    """
    found = findings_for(
        tmp_path,
        {
            "distributed_tpu/scheduler/state.py": CLEAN_MACHINE,
            "distributed_tpu/scheduler/ext.py": other,
        },
        "state-machine",
    )
    assert len(found) == 1
    assert found[0].path == "distributed_tpu/scheduler/ext.py"
    assert "'wating'" in found[0].message


def test_state_machine_extractor_model_and_serialization(tmp_path):
    from distributed_tpu.analysis.config import LintConfig
    from distributed_tpu.analysis.core import LintContext
    from distributed_tpu.analysis.model import (
        extract_machines,
        machine_to_dot,
        machine_to_json,
    )

    root = make_repo(
        tmp_path, {"distributed_tpu/scheduler/state.py": CLEAN_MACHINE}
    )
    ctx = LintContext(root, LintConfig())
    machines = extract_machines(ctx.all_modules)
    assert len(machines) == 1
    m = machines[0]
    assert m.name == "scheduler"
    assert m.states == ("memory", "released", "waiting")
    assert {(t.start, t.finish) for t in m.transitions} == {
        ("released", "waiting"), ("waiting", "memory"),
        ("waiting", "released"), ("memory", "released"),
    }
    # every emission resolved, none flagged
    assert m.emissions
    assert all(
        e.resolution in ("direct", "fallback", "any-start")
        for e in m.emissions
    )
    guarded = [e for e in m.emissions if e.starts is not None]
    assert any(
        e.starts == ("released",) and e.finish == "waiting" for e in guarded
    )
    import json as _json

    doc = _json.loads(machine_to_json(m))
    assert doc["module"] == "distributed_tpu/scheduler/state.py"
    assert len(doc["transitions"]) == 4 and len(doc["emissions"]) == len(
        m.emissions
    )
    dot = machine_to_dot(m)
    assert '"released" -> "waiting"' in dot
    assert "_transition_released_waiting" in dot


def test_state_machine_artifacts_no_drift(tmp_path):
    """The checked-in docs/state_machine/ model must match a fresh
    extraction — regenerate with
    ``python -m distributed_tpu.analysis --dump-model docs/state_machine``
    whenever either state machine changes."""
    proc = subprocess.run(
        [sys.executable, "-m", "distributed_tpu.analysis",
         "--dump-model", str(tmp_path), "--root", str(REPO_ROOT)],
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for name in ("scheduler", "worker"):
        for ext in (".json", ".dot"):
            fresh = (tmp_path / (name + ext)).read_text()
            checked = (
                REPO_ROOT / "docs" / "state_machine" / (name + ext)
            ).read_text()
            assert fresh == checked, (
                f"docs/state_machine/{name}{ext} is stale — regenerate it"
            )


# ----------------------------------------------- await-atomicity (rule 10)


def test_await_atomicity_fires_on_slot_reuse_steal_shape(tmp_path):
    """Must-fire: the PR 3 slot-reuse race — a mirror-slot worker binding
    priced into a device plan, then used to address a steal after the
    plan await; churn during the await can reuse the slot for a
    different worker."""
    src = """
        class WorkStealing:
            async def balance_device(self):
                state = self.scheduler.state
                victim = state.mirror.ws_of[self.vslot]
                plan = await self.run_device_kernel()
                self.batched_send(victim, {"op": "steal-request",
                                           "key": plan})
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/scheduler/stealing.py": src},
        "await-atomicity",
    )
    assert len(found) == 1
    f = found[0]
    assert f.symbol == "balance_device" and "'victim'" in f.message
    assert "sink" in f.message


def test_await_atomicity_fires_on_readinto_buffer_shape(tmp_path):
    """Must-fire: the PR 4 readinto race — a StreamReader._buffer
    binding drained after a _wait_for_data await with no exception/EOF
    re-check (the sanctioned fix in comm/tcp.py binds via getattr and
    raises the reader's stored exception before every drain)."""
    src = """
        async def readinto_exactly(reader, view):
            n = view.nbytes
            pos = 0
            buffer = reader._buffer
            while pos < n:
                if not buffer:
                    await reader._wait_for_data("readinto")
                take = min(len(buffer), n - pos)
                view[pos:pos + take] = buffer[:take]
                del buffer[:take]
                pos += take
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/comm/rogue.py": src}, "await-atomicity"
    )
    assert found, "the readinto race shape must fire"
    assert any("'buffer'" in f.message for f in found)


def test_await_atomicity_revalidation_and_rebind_pass(tmp_path):
    src = """
        class Scheduler:
            async def guarded(self, key, addr):
                state = self.state
                ws = state.workers.get(addr)
                await self.flush()
                if state.workers.get(addr) is ws:
                    ws.processing.pop(key, None)

            async def reread(self, key):
                state = self.state
                ts = state.tasks.get(key)
                nbytes = await self.fetch(ts.key)
                ts = state.tasks.get(key)
                ts.nbytes = nbytes

            async def before_await_is_fine(self, key):
                ts = self.state.tasks.get(key)
                ts.nbytes = 1
                await self.flush()
    """
    assert not findings_for(
        tmp_path, {"distributed_tpu/scheduler/server.py": src},
        "await-atomicity",
    )


def test_await_atomicity_pragma_suppresses(tmp_path):
    src = """
        async def push(self, key):
            ts = self.state.tasks.get(key)
            await self.flush()
            # graft-lint: allow[await-atomicity] key is unforgettable here: pinned by the caller
            ts.nbytes = 1
    """
    root = make_repo(tmp_path, {"distributed_tpu/scheduler/ext.py": src})
    result = run_lint(root, rule_names=["await-atomicity"])
    assert not result.findings
    assert result.suppressed == 1


# ------------------------------------------------------------ config-keys


CONFIG_FIXTURE = """
    defaults = {
        "scheduler": {"bandwidth": 1, "dead-knob": 2},
        "worker": {"preload": [], "nested": {"a": 1, "b": 2}},
    }
"""


def test_config_keys_missing_and_dead(tmp_path):
    reader = """
        from distributed_tpu import config

        def f(prefix):
            config.get("scheduler.bandwidth")
            config.get("scheduler.typo-key")
            config.get("worker.nested")
            config.get(f"{prefix}.preload")
    """
    found = findings_for(
        tmp_path,
        {
            "distributed_tpu/config.py": CONFIG_FIXTURE,
            "distributed_tpu/reader.py": reader,
        },
        "config-keys",
    )
    msgs = sorted(f.message for f in found)
    assert len(found) == 2, found
    assert "scheduler.typo-key" in msgs[0] and "not present" in msgs[0]
    assert "scheduler.dead-knob" in msgs[1] and "dead configuration" in msgs[1]


def test_config_keys_indirect_full_path_constant_counts_as_read(tmp_path):
    reader = """
        from distributed_tpu import config

        KEY = "scheduler.dead-knob"

        def f():
            config.get("scheduler.bandwidth")
            config.get("worker.nested")
            config.get("worker.preload")
            return config.get(KEY)
    """
    assert not findings_for(
        tmp_path,
        {
            "distributed_tpu/config.py": CONFIG_FIXTURE,
            "distributed_tpu/reader.py": reader,
        },
        "config-keys",
    )


# ------------------------------------------- handler-parity batch plane


def test_handler_parity_batch_without_scalar_and_orphan_keys(tmp_path):
    src = """
        class Server:
            def __init__(self):
                stream_handlers = {"task-done": self.handle_done}
                self.stream_batch_handlers["task-done"] = self.handle_done_batch
                self.stream_batch_handlers["task-gone"] = self.handle_gone_batch

            def handle_done(self, key=None, stimulus_id=None):
                self._trace_ingress("task-done", 1, stimulus_id)
                return key

            def handle_done_batch(self, msgs, worker=""):
                self._trace_ingress("task-done", len(msgs), "")
                out = []
                for m in msgs:
                    k = m.pop("key", None)
                    sid = m.pop("stimulus_id", "")
                    nb = m.pop("nbytes", 0)
                    out.append((k, sid, nb))
                return out

            def handle_gone_batch(self, msgs):
                return msgs
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/worker/srv.py": src}, "handler-parity"
    )
    msgs = "\n".join(f.message for f in found)
    assert "'task-gone'" in msgs and "no scalar stream handler" in msgs
    assert "nbytes" in msgs and "no scalar stream handler for the op accepts" in msgs
    assert len(found) == 2


def test_handler_parity_batch_dropping_scalar_param_flagged(tmp_path):
    src = """
        class Server:
            def __init__(self):
                stream_handlers = {"task-done": self.handle_done}
                self.stream_batch_handlers["task-done"] = self.handle_done_batch

            def handle_done(self, key=None, nbytes=0, stimulus_id=None):
                self._trace_ingress("task-done", 1, stimulus_id)
                return key

            def handle_done_batch(self, msgs, worker=""):
                self._trace_ingress("task-done", len(msgs), "")
                return [m.pop("key", None) for m in msgs]
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/worker/srv.py": src}, "handler-parity"
    )
    assert len(found) == 1
    assert "neither consumes nor carries through" in found[0].message
    assert "nbytes" in found[0].message


def test_handler_parity_batch_residual_carry_through_passes(tmp_path):
    src = """
        class Server:
            def __init__(self):
                stream_handlers = {"task-done": self.handle_done}
                self.stream_batch_handlers["task-done"] = self.handle_done_batch

            def handle_done(self, key=None, nbytes=0, stimulus_id=None,
                            **kw):
                self._trace_ingress("task-done", 1, stimulus_id)
                return key

            def handle_done_batch(self, msgs, worker=""):
                self._trace_ingress("task-done", len(msgs), "")
                out = []
                for m in msgs:
                    key = m.pop("key", None)
                    sid = m.pop("stimulus_id", "")
                    out.append((key, sid, m))
                return out
    """
    assert not findings_for(
        tmp_path, {"distributed_tpu/worker/srv.py": src}, "handler-parity"
    )


def test_handler_parity_batch_wholesale_forward_passes(tmp_path):
    """An arm with no keyed reads forwards its messages wholesale
    (``**m`` delegation, ``m.items()``) — nothing provably drops, so the
    dropped-keys claim must stay silent."""
    src = """
        class Server:
            def __init__(self):
                stream_handlers = {"task-done": self.handle_done}
                self.stream_batch_handlers["task-done"] = self.handle_done_batch
                stream_handlers["task-gone"] = self.handle_gone
                self.stream_batch_handlers["task-gone"] = self.handle_gone_batch

            def handle_done(self, key=None, nbytes=0, stimulus_id=None):
                self._trace_ingress("task-done", 1, stimulus_id)
                return key

            def handle_done_batch(self, msgs, worker=""):
                return [self.handle_done(**m) for m in msgs]

            def handle_gone(self, key=None, reason=None):
                self.trace.emit("ingress", "task-gone", "")
                return key

            def handle_gone_batch(self, msgs, worker=""):
                self.trace.emit("ingress", "task-gone", "", n=len(msgs))
                return [sorted(m.items()) for m in msgs]
    """
    assert not findings_for(
        tmp_path, {"distributed_tpu/worker/srv.py": src}, "handler-parity"
    )
    # note: handle_done_batch carries NO emission of its own — the
    # wholesale delegation to the emitting scalar covers the batch
    # plane transitively (trace-parity pass 5)


def test_handler_parity_trace_parity_must_fire(tmp_path):
    """Trace-parity (pass 5): a batched op whose arms never stamp the
    flight recorder's ingress hop is flagged on BOTH planes — the blind
    spot causal stimulus tracing exists to remove."""
    src = """
        class Server:
            def __init__(self):
                stream_handlers = {"task-done": self.handle_done}
                self.stream_batch_handlers["task-done"] = self.handle_done_batch

            def handle_done(self, key=None, stimulus_id=None):
                return key

            def handle_done_batch(self, msgs, worker=""):
                out = []
                for m in msgs:
                    out.append((m.pop("key", None), m.pop("stimulus_id", ""), m))
                return out
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/worker/srv.py": src}, "handler-parity"
    )
    msgs = "\n".join(f.message for f in found)
    assert "emits no ingress trace" in msgs
    assert "batch arm for op 'task-done'" in msgs
    assert "scalar twin of batched op 'task-done'" in msgs
    assert len(found) == 2


def test_handler_parity_trace_parity_accepts_direct_emit_and_helper(tmp_path):
    """Both sanctioned emission shapes pass: the ``*trace_ingress``
    helper and a direct ``<...>.trace.emit("ingress", ...)``; an emit
    with a NON-ingress category does not count."""
    src = """
        class Server:
            def __init__(self):
                stream_handlers = {"task-done": self.handle_done}
                self.stream_batch_handlers["task-done"] = self.handle_done_batch
                stream_handlers["task-gone"] = self.handle_gone
                self.stream_batch_handlers["task-gone"] = self.handle_gone_batch

            def handle_done(self, key=None, stimulus_id=None):
                self.trace.emit("ingress", "task-done", stimulus_id)
                return key

            def handle_done_batch(self, msgs, worker=""):
                self._trace_ingress("task-done", len(msgs), "")
                return [(m.pop("key", None), m.pop("stimulus_id", ""), m)
                        for m in msgs]

            def handle_gone(self, key=None, stimulus_id=None):
                self.trace.emit("engine", "not-ingress", stimulus_id)
                return key

            def handle_gone_batch(self, msgs, worker=""):
                self._trace_ingress("task-gone", len(msgs), "")
                return [(m.pop("key", None), m.pop("stimulus_id", ""), m)
                        for m in msgs]
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/worker/srv.py": src}, "handler-parity"
    )
    assert len(found) == 1
    assert "scalar twin of batched op 'task-gone'" in found[0].message


def test_await_atomicity_bare_annotation_is_not_a_bind(tmp_path):
    """A value-less ``ts: TaskState`` annotation after the await binds
    nothing — it must not move the last bind past the await and mask the
    stale pre-await read."""
    src = """
        class Scheduler:
            async def annotated(self, key):
                ts = self.state.tasks.get(key)
                await self.flush()
                ts: object
                ts.nbytes = 1
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/scheduler/server.py": src},
        "await-atomicity",
    )
    assert len(found) == 1
    assert "'ts'" in found[0].message


def test_cli_dump_model_rejects_rule_combination():
    """--dump-model runs no rules; silently skipping a requested --rule
    would let a CI gate pass without linting, so the combination is a
    hard usage error."""
    proc = subprocess.run(
        [sys.executable, "-m", "distributed_tpu.analysis",
         "--dump-model", "/tmp/_should_not_exist_dump",
         "--rule", "state-machine", "--root", str(REPO_ROOT)],
        capture_output=True, text=True, timeout=60, cwd=REPO_ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 2
    assert "pure extraction mode" in proc.stderr
    assert not os.path.exists("/tmp/_should_not_exist_dump")


# ---------------------------------------------------- determinism (rule 12)


#: the PR 13 bug, verbatim shape: TaskState relation fields as plain
#: sets, iterated inside a transition to build recommendations — the
#: recommendation order (and with it the journal/digest) then depends
#: on PYTHONHASHSEED
PR13_RELATION_SET_BUG = """
    class TaskState:
        def __init__(self, key):
            self.key = key
            self.dependents: set[TaskState] = set()
            self.waiters: set[TaskState] = set()

    class SchedulerState:
        def _transition_processing_memory(self, ts: TaskState, stimulus_id):
            recommendations = {}
            for dts in ts.dependents:
                if not dts.waiters:
                    recommendations[dts.key] = "released"
            return recommendations
"""


def test_determinism_fires_on_pr13_relation_set_bug(tmp_path):
    found = findings_for(
        tmp_path,
        {"distributed_tpu/scheduler/state.py": PR13_RELATION_SET_BUG},
        "determinism",
    )
    assert any(
        f.symbol.endswith("_transition_processing_memory") for f in found
    ), [f.message for f in found]
    assert any("recommendations" in f.message for f in found)


def test_determinism_clean_with_ordered_relations(tmp_path):
    # the actual PR 13 fix: OrderedSet relations make iteration order
    # insertion order, which is stimulus-derived and seed-independent
    src = """
        from distributed_tpu.utils.collections import OrderedSet

        class TaskState:
            def __init__(self, key):
                self.key = key
                self.dependents: OrderedSet[TaskState] = OrderedSet()
                self.waiters: OrderedSet[TaskState] = OrderedSet()

        class SchedulerState:
            def _transition_processing_memory(self, ts: TaskState, stimulus_id):
                recommendations = {}
                for dts in ts.dependents:
                    if not dts.waiters:
                        recommendations[dts.key] = "released"
                return recommendations
    """
    assert not findings_for(
        tmp_path, {"distributed_tpu/scheduler/state.py": src}, "determinism"
    )


#: the PR 14 bug: steal victims picked by first-match scan over the
#: plain ``saturated`` set — which worker loses a task depends on the
#: hash seed, so two same-seed runs steal differently
PR14_SATURATED_SET_BUG = """
    class SchedulerState:
        def __init__(self):
            self.saturated: set = set()

        def pick_steal_victim(self):
            for ws in self.saturated:
                if ws.nprocessing > 1:
                    return ws
            return None
"""


def test_determinism_fires_on_pr14_saturated_set_bug(tmp_path):
    found = findings_for(
        tmp_path,
        {"distributed_tpu/ops/stealing.py": PR14_SATURATED_SET_BUG},
        "determinism",
    )
    assert any(f.symbol.endswith("pick_steal_victim") for f in found), [
        f.message for f in found
    ]


def test_determinism_clean_with_keyed_sorted(tmp_path):
    # sorted() with a deterministic key is a sanitizer: the scan order
    # no longer depends on the set's internal layout
    src = """
        class SchedulerState:
            def __init__(self):
                self.saturated: set = set()

            def pick_steal_victim(self):
                for ws in sorted(self.saturated, key=lambda w: w.address):
                    if ws.nprocessing > 1:
                        return ws
                return None
    """
    assert not findings_for(
        tmp_path, {"distributed_tpu/ops/stealing.py": src}, "determinism"
    )


def test_determinism_pragma_suppresses_with_reason(tmp_path):
    src = """
        class SchedulerState:
            def __init__(self):
                self.saturated: set = set()

            def pick_steal_victim(self):
                # graft-lint: allow[determinism] victim choice audited order-free
                for ws in self.saturated:
                    if ws.nprocessing > 1:
                        return ws
                return None
    """
    root = make_repo(tmp_path, {"distributed_tpu/ops/stealing.py": src})
    result = run_lint(root, rule_names=["determinism"])
    assert not result.findings
    assert result.suppressed == 1


def test_determinism_fires_on_unstable_min_key(tmp_path):
    # min() over a set with a key that can tie picks whichever tied
    # element the iteration meets first — needs an address tiebreak
    src = """
        class SchedulerState:
            def __init__(self):
                self.idle: set = set()

            def decide_worker(self):
                return min(self.idle, key=lambda ws: ws.occupancy)
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/scheduler/state.py": src}, "determinism"
    )
    assert len(found) == 1
    assert "min" in found[0].message


def test_determinism_clean_with_address_tiebreak(tmp_path):
    src = """
        class SchedulerState:
            def __init__(self):
                self.idle: set = set()

            def decide_worker(self):
                return min(self.idle, key=lambda ws: (ws.occupancy, ws.address))
    """
    assert not findings_for(
        tmp_path, {"distributed_tpu/scheduler/state.py": src}, "determinism"
    )


def test_determinism_fires_on_id_keyed_sort_and_set_pop(tmp_path):
    src = """
        class Plan:
            def __init__(self):
                self.pending: set = set()

            def order_policies(self, policies):
                return sorted(policies, key=id)

            def take(self):
                return self.pending.pop()
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/scheduler/amm.py": src}, "determinism"
    )
    msgs = " | ".join(f.message for f in found)
    assert "id()" in msgs, msgs
    assert ".pop()" in msgs or "pop" in msgs, msgs


def test_determinism_next_iter_requires_singleton_guard(tmp_path):
    src = """
        class S:
            def __init__(self):
                self.workers: set = set()

            def only_unsafe(self):
                return next(iter(self.workers))

            def only_safe(self):
                if len(self.workers) == 1:
                    return next(iter(self.workers))
                return None
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/scheduler/state.py": src}, "determinism"
    )
    assert len(found) == 1
    assert found[0].symbol.endswith("only_unsafe")


# ------------------------------------------------- tape_safe contract pass


def test_tape_safe_plugin_reading_occupancy_fires(tmp_path):
    # tape_safe plugins replay against lazily-hydrated rows: derived
    # aggregates like ws.occupancy are NOT restored row-locally, so a
    # tape_safe=True plugin touching them diverges under replay
    src = """
        class StealTap:
            tape_safe = True

            def transition(self, key, start, finish, stimulus_id=None, ws=None):
                if ws is not None and ws.occupancy > 1.0:
                    self.hot.append(key)
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/scheduler/state.py": src}, "determinism"
    )
    assert any("occupancy" in f.message for f in found), [
        f.message for f in found
    ]


def test_tape_safe_plugin_cross_row_scan_fires(tmp_path):
    # reached through a same-class helper: the contract pass follows
    # self.method() calls from transition()
    src = """
        class CensusTap:
            tape_safe = True

            def transition(self, key, start, finish, stimulus_id=None):
                self._rescan()

            def _rescan(self):
                self.n = len([ts for ts in self.state.tasks.values()])
    """
    found = findings_for(
        tmp_path, {"distributed_tpu/scheduler/state.py": src}, "determinism"
    )
    assert any("tasks" in f.message for f in found), [f.message for f in found]


def test_tape_safe_plugin_args_only_is_clean(tmp_path):
    src = """
        class CountTap:
            tape_safe = True

            def transition(self, key, start, finish, stimulus_id=None):
                self.counts[finish] = self.counts.get(finish, 0) + 1
    """
    assert not findings_for(
        tmp_path, {"distributed_tpu/scheduler/state.py": src}, "determinism"
    )


def test_non_tape_safe_plugin_may_read_occupancy(tmp_path):
    # the contract pass only binds classes that DECLARE tape_safe = True
    src = """
        class LooseTap:
            tape_safe = False

            def transition(self, key, start, finish, stimulus_id=None, ws=None):
                if ws is not None and ws.occupancy > 1.0:
                    self.hot.append(key)
    """
    assert not findings_for(
        tmp_path, {"distributed_tpu/scheduler/state.py": src}, "determinism"
    )


# ------------------------------------------- baseline prune / moved symbol


def test_baseline_moved_symbol_matches_before_path(tmp_path):
    # a baselined finding whose enclosing function moved file intact is
    # still suppressed via (rule, symbol) — not double-reported as one
    # stale entry plus one new finding
    src = """
        def dispatch(handler):
            try:
                handler()
            except Exception:
                pass
    """
    root = make_repo(tmp_path, {"distributed_tpu/rpc/new_home.py": src})
    (root / "graft-lint-baseline.toml").write_text(textwrap.dedent("""
        [[allow]]
        rule = "swallowed-exceptions"
        path = "distributed_tpu/rpc/old_home.py"
        symbol = "dispatch"
        reason = "probe path, outcome irrelevant"
    """))
    result = run_lint(root, rule_names=["swallowed-exceptions"])
    assert not result.findings
    assert result.suppressed == 1
    assert not result.stale_baseline

    # without a symbol the entry stays pinned to its path: path mismatch
    # means stale + unsuppressed, as before
    (root / "graft-lint-baseline.toml").write_text(textwrap.dedent("""
        [[allow]]
        rule = "swallowed-exceptions"
        path = "distributed_tpu/rpc/old_home.py"
        reason = "probe path, outcome irrelevant"
    """))
    result = run_lint(root, rule_names=["swallowed-exceptions"])
    assert len(result.findings) == 1
    assert result.stale_baseline


def test_prune_baseline_round_trip_preserves_live_blocks(tmp_path):
    from distributed_tpu.analysis.baseline import Baseline

    src = """
        def dispatch(handler):
            try:
                handler()
            except Exception:
                pass
    """
    root = make_repo(tmp_path, {"distributed_tpu/rpc/disp.py": src})
    baseline_path = root / "graft-lint-baseline.toml"
    baseline_path.write_text(textwrap.dedent("""\
        # graft-lint baseline — every entry argues its case.

        # probe dispatch: outcome is irrelevant by design, see rpc docs
        [[allow]]
        rule = "swallowed-exceptions"
        path = "distributed_tpu/rpc/disp.py"
        symbol = "dispatch"
        reason = "probe path, outcome irrelevant"

        # this one rotted: the file is long gone
        [[allow]]
        rule = "swallowed-exceptions"
        path = "distributed_tpu/rpc/gone.py"
        reason = "was real once"
    """))
    baseline = Baseline.load(baseline_path)
    result = run_lint(root, baseline=baseline)
    assert not result.findings

    dropped = baseline.prune(baseline_path)
    assert dropped == ["swallowed-exceptions @ distributed_tpu/rpc/gone.py"]
    text = baseline_path.read_text()
    # the live entry survives verbatim, rationale comment included
    assert "# probe dispatch: outcome is irrelevant by design" in text
    assert 'reason = "probe path, outcome irrelevant"' in text
    # the stale block is gone, comment and all
    assert "gone.py" not in text
    assert "# this one rotted" not in text
    # file header preamble is kept
    assert text.startswith("# graft-lint baseline")

    # re-load + re-lint: nothing further to prune, file untouched
    baseline2 = Baseline.load(baseline_path)
    run_lint(root, baseline=baseline2)
    assert baseline2.prune(baseline_path) == []
    assert baseline_path.read_text() == text


def test_prune_baseline_refuses_partial_run():
    import pytest

    from distributed_tpu.analysis.cli import main

    with pytest.raises(SystemExit) as exc:
        main(["--prune-baseline", "--rule", "determinism",
              "--root", str(REPO_ROOT)])
    assert exc.value.code == 2

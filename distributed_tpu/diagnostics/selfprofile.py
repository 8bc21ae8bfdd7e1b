"""Control-plane self-profiling: the scheduler watches itself.

``diagnostics/profile.py`` samples worker *executor* threads — the
threads running user tasks.  The paper's innovation, though, lives in
the control plane: the event-loop thread running ``transitions_batch``,
``send_all`` flushes and mirror uploads, and the jax-placement planner
thread.  This module turns those blind spots into a continuously
answered question ("where did the scheduler's second go?") with three
cooperating pieces (docs/observability.md "Self-profiling"):

- :class:`WallBudget` — exact monotonic-clock accumulators per
  control-plane *phase* (:data:`PHASES`, and — opt-in,
  ``scheduler.profile.arm-attribution`` — the per-transition
  ``engine.scalar-arm:<start>,<finish>`` arms).  Phases are entered at
  the hot-path seams of every role sharing an event loop (scheduler,
  workers, clients) and never across an ``await``; totals export as
  ``dtpu_wall_seconds_total{phase=}`` at ``/metrics`` and the
  per-arm table is the payoff artifact ``sim.profile_run`` emits
  (ROADMAP item 4's prioritization input).  One budget per event loop
  (:func:`loop_budget`): on an in-process cluster the per-thread stack
  gives true self time across roles, and ``loop.idle`` is the loop's
  selector wait.  While a ``jax.profiler`` trace is active every phase
  also shows on it as a ``TraceAnnotation``, on the device trace's
  clock.
- :class:`ControlPlaneProfiler` — a :class:`~distributed_tpu.
  diagnostics.profile.Profiler` aimed at the loop/planner thread idents,
  with a ``stop=`` frame boundary so the shared asyncio ``run_forever``
  prefix doesn't swamp the tree, idle selector frames counted apart from
  the signal, and the active phase + stimulus id stamped onto every
  sample (the join to the flight recorder's causal timeline).
- :class:`LoopWatchdog` — a loop-side tick measuring event-loop lag
  into ``dtpu_loop_lag_seconds`` plus an off-loop monitor thread that,
  when the loop stops ticking past ``scheduler.profile.stall-threshold``,
  captures the blocked loop thread's stack via ``sys._current_frames``
  into a flight-recorder ``stall`` event (formatted traceback +
  in-progress phase): the postmortem for "the scheduler froze".

Always-on budget: batch-level phase enters only (a handful of monotonic
reads per engine pass), sampling at a low configurable rate
(``scheduler.profile.interval``), arm attribution off by default.
tests/test_overhead.py gates sampling-on overhead <5% on the engine
flood.

Covered by graft-lint's monotonic-time rule (diagnostics/**): every
clock read here is the monotonic ``utils.misc.time``, and the watchdog
thread waits on an ``Event``, never ``time.sleep``.
"""

from __future__ import annotations

import asyncio
import logging
import sys
import threading
import traceback as _traceback
import weakref
from collections import deque
from typing import Any, Callable, Iterable

from distributed_tpu import config
from distributed_tpu.diagnostics.profile import Profiler, create, merge, process
from distributed_tpu.tracing import (
    SECONDS_BUCKETS,
    Histogram,
    to_jsonl,
    trace_annotation,
)
from distributed_tpu.utils.misc import time

logger = logging.getLogger("distributed_tpu.selfprofile")

#: phase vocabulary (docs/observability.md "Self-profiling") — the
#: batch-level phases entered unconditionally at the hot-path seams.
#: ``engine.scalar-arm:<start>,<finish>`` (scheduler) and
#: ``wengine.scalar-arm:<start>,<finish>`` (worker) join them when
#: ``scheduler.profile.arm-attribution`` is on.
PHASES = (
    "engine.drain",      # a transition-engine round drained to fixed point
    "wengine.stimulus",  # a worker state-machine stimulus batch
    "egress.flush",      # Scheduler.stream_payload_flush coalescing/writes
    "kernel.dispatch",   # a device placement plan (loop or planner thread)
    "mirror.upload",     # fleet-mirror device upload (scatter or full)
    "telemetry.fold",    # heartbeat telemetry folding into the aggregate
    "loop.idle",         # the event loop's selector wait (loop_budget)
    "ingest.graph",      # Scheduler.update_graph, update_graph_core inside
    "ingest.stream",     # scheduler stream payload decode + dispatch
    "plan.snapshot",     # JaxPlacement.plan_graph's filter + snapshot
    "plan.merge",        # an async plan landing on the loop
    "steal.cycle",       # one WorkStealing.balance cycle
    "amm.cycle",         # one active-memory-manager round
    "wshell.stream",     # worker stream payload decode + dispatch
    "wshell.instructions",  # Worker.handle_stimulus -> _handle_instructions
    "client.submit",     # Client.compute_graph's validation + packing
    "client.gather",     # unpacking a gather's results
)

#: the loop's selector wait, added by the selector wrapper of
#: :func:`loop_budget` straight to the totals (never on the stack)
LOOP_IDLE = "loop.idle"

#: innermost frames in these files mean "the loop is idle in select()" —
#: counted apart so an idle scheduler's tree stays signal-dense
IDLE_FILES = ("selectors.py",)

#: pseudo-frame prefix for the phase layer stamped under a profile root
PHASE_PREFIX = "phase:"


class WallBudget:
    """Exact wall attribution of control-plane threads by phase.

    A per-thread phase *stack* (entering a child phase pauses the
    parent's accumulation, so every total is **self time**) and
    per-thread totals, summed by :meth:`snapshot`.  ``push``/``pop`` are
    the hot-path API (a monotonic read and a few dict operations each,
    with no lock: each thread folds into its own dicts); :meth:`phase`
    is the context-manager convenience for batch-level seams.  The top
    of each thread's stack is published in ``_active`` so the sampler
    and stall watchdog (other threads) can stamp the in-progress phase +
    stimulus id onto samples and stall events.  While a profiler trace
    is active each pushed phase is also a ``jax.profiler``
    ``TraceAnnotation`` of its name on the pushing thread
    (:func:`distributed_tpu.tracing.trace_annotation`); entering and
    leaving it is billed to no phase, so self times read the same
    traced or not.
    """

    def __init__(self, clock: Callable[[], float] = time):
        # REAL monotonic clock even under the simulator: the budget
        # measures python cost, not virtual time (sim.profile_run)
        self.clock = clock
        self._lock = threading.Lock()
        self._tls = threading.local()
        # (totals, counts) of every thread that ever billed here; a
        # thread's dicts are written by that thread alone, and read by
        # snapshot() as whole-dict copies (atomic under the GIL)
        self._threads: list[tuple[dict[str, float], dict[str, int]]] = []
        # thread ident -> (phase, stimulus) of that thread's stack top
        self._active: dict[int, tuple[str, str]] = {}

    # ------------------------------------------------------------ hot path

    def _join(self) -> list:
        """This thread's first use: its stack and dicts."""
        tl = self._tls
        tl.stack, tl.totals, tl.counts = [], {}, {}
        with self._lock:
            self._threads.append((tl.totals, tl.counts))
        return tl.stack

    def push(self, phase: str, stim: str = "") -> None:
        now = self.clock()
        tl = self._tls
        try:
            st = tl.stack
        except AttributeError:
            st = self._join()
        if st:
            top = st[-1]
            totals = tl.totals
            totals[top[0]] = totals.get(top[0], 0.0) + (now - top[2])
            top[2] = now
        ann = trace_annotation(phase)
        if ann is not None:  # the mirror's own cost is billed to no phase
            now = self.clock()
            if st:
                st[-1][2] = now
        st.append([phase, stim, now, ann])
        self._active[threading.get_ident()] = (phase, stim)

    def pop(self) -> None:
        now = self.clock()
        tl = self._tls
        st = getattr(tl, "stack", None)
        if not st:  # unbalanced pop: never corrupt the accumulators
            return
        phase, _stim, seg, ann = st.pop()
        totals, counts = tl.totals, tl.counts
        totals[phase] = totals.get(phase, 0.0) + (now - seg)
        counts[phase] = counts.get(phase, 0) + 1
        if ann is not None:
            ann.__exit__(None, None, None)
            now = self.clock()
        ident = threading.get_ident()
        if st:
            top = st[-1]
            top[2] = now
            self._active[ident] = (top[0], top[1])
        else:
            self._active.pop(ident, None)

    def add(self, phase: str, dt: float) -> None:
        """One entry of ``dt`` seconds straight into this thread's
        totals, outside the stack (the selector wrapper's ``loop.idle``)."""
        tl = self._tls
        if not hasattr(tl, "totals"):
            self._join()
        totals, counts = tl.totals, tl.counts
        totals[phase] = totals.get(phase, 0.0) + dt
        counts[phase] = counts.get(phase, 0) + 1

    # ----------------------------------------------------------- slow path

    def phase(self, name: str, stim: str = ""):
        """``with budget.phase("egress.flush", stim): ...``"""
        return _PhaseCtx(self, name, stim)

    def current(self, ident: int) -> tuple[str, str]:
        """(phase, stimulus) at the top of thread ``ident``'s stack
        ("", "") when it is outside every phase.  Safe from any thread."""
        return self._active.get(ident, ("", ""))

    def _sum(self, which: int) -> dict:
        with self._lock:
            parts = [t[which] for t in self._threads]
        out: dict = {}
        for part in parts:
            for k, v in part.copy().items():
                out[k] = out.get(k, 0) + v
        return out

    def snapshot(self) -> dict[str, float]:
        """Self seconds per phase, summed over threads."""
        return self._sum(0)

    def snapshot_counts(self) -> dict[str, int]:
        """Entries per phase, summed over threads."""
        return self._sum(1)

    def clear(self) -> None:
        with self._lock:
            for totals, counts in self._threads:
                totals.clear()
                counts.clear()

    def __repr__(self) -> str:
        return f"<WallBudget phases={len(self.snapshot())}>"


class _PhaseCtx:
    __slots__ = ("_budget", "_name", "_stim")

    def __init__(self, budget: WallBudget, name: str, stim: str):
        self._budget = budget
        self._name = name
        self._stim = stim

    def __enter__(self):
        self._budget.push(self._name, self._stim)
        return self

    def __exit__(self, *exc):
        self._budget.pop()


#: event loop -> its WallBudget (weak: a closed loop takes its budget)
_LOOP_BUDGETS: "weakref.WeakKeyDictionary[asyncio.AbstractEventLoop, WallBudget]" = (
    weakref.WeakKeyDictionary()
)


def loop_budget() -> WallBudget:
    """The :class:`WallBudget` of the running event loop, made on first
    use; a fresh budget of its own outside any loop.

    Every role built on one loop (the scheduler, the workers and the
    clients of an in-process cluster) bills into this one budget, so its
    per-thread stack gives true self time across roles.  On first use
    the loop's selector is wrapped to add its waits to ``loop.idle``;
    a loop without a selector (``uvloop``) has no ``loop.idle``."""
    try:
        loop = asyncio.get_running_loop()
    except RuntimeError:
        return WallBudget()
    budget = _LOOP_BUDGETS.get(loop)
    if budget is None:
        budget = _LOOP_BUDGETS[loop] = WallBudget()
        _time_selector(loop, budget)
    return budget


def _time_selector(loop: asyncio.AbstractEventLoop, budget: WallBudget) -> None:
    """Bill the loop's selector waits to ``loop.idle``: straight into the
    totals on the loop thread, at the bottom of its (then empty) phase
    stack, and as a trace annotation while a profiler trace is active."""
    selector = getattr(loop, "_selector", None)
    if selector is None:
        return
    select = selector.select
    clock = budget.clock

    def timed_select(timeout=None):
        ann = trace_annotation(LOOP_IDLE)
        t0 = clock()
        try:
            return select(timeout)
        finally:
            budget.add(LOOP_IDLE, clock() - t0)
            if ann is not None:
                ann.__exit__(None, None, None)

    selector.select = timed_select


class ControlPlaneProfiler(Profiler):
    """Statistical profiler for control-plane threads (loop + planner).

    Differences from the executor profiler it extends:

    - defaults come from the ``scheduler.profile.*`` subtree (shared by
      both roles, like ``scheduler.trace.*``), not ``worker.profile``;
    - ``stop`` frame boundary cuts the shared asyncio machinery prefix;
    - samples whose innermost frame sits in ``IDLE_FILES`` count into
      ``idle_samples`` instead of the tree (an idle selector wait is not
      control-plane work);
    - every tree insertion lands under a ``phase:<name>`` pseudo-frame
      read from the :class:`WallBudget` of the sampled thread, and the
      (ts, phase, stimulus) triple of recent samples is kept in
      ``samples`` — the join between profiles and the flight recorder.
    """

    def __init__(self, idents: Callable[[], Iterable[int]],
                 wall: WallBudget | None = None,
                 interval: float | None = None, cycle: float | None = None,
                 maxlen: int | None = None, stop: str | None = None):
        cfg = config.get("scheduler.profile")
        super().__init__(
            thread_filter="dtpu-control-plane",  # unused: idents given
            interval=(
                interval if interval is not None
                else config.parse_timedelta(cfg["interval"])
            ),
            cycle=(
                cycle if cycle is not None
                else config.parse_timedelta(cfg["cycle"])
            ),
            maxlen=maxlen if maxlen is not None else int(cfg["history"]),
            idents=idents,
            stop=stop if stop is not None else (cfg["stop"] or None),
        )
        self.wall = wall
        self.total_samples = 0
        self.idle_samples = 0
        #: recent (ts, phase, stim) sample stamps, newest last
        self.samples: deque[tuple[float, str, str]] = deque(maxlen=512)

    def _add_sample(self, frame, now: float, ident: int | None = None) -> None:
        self.total_samples += 1
        if frame.f_code.co_filename.endswith(IDLE_FILES):
            self.idle_samples += 1
            return
        phase, stim = ("", "")
        if self.wall is not None and ident is not None:
            phase, stim = self.wall.current(ident)
        with self._lock:
            root = self.current
            root["count"] += 1
            process(frame, _phase_node(root, phase), stop=self.stop_file)
            self.samples.append((now, phase, stim))
            if now - self._last_cycle > self.cycle:
                self.history.append((now, self.current))
                self.current = create()
                self._last_cycle = now


def _phase_node(root: dict, phase: str) -> dict:
    ident = PHASE_PREFIX + (phase or "unattributed")
    node = root["children"].get(ident)
    if node is None:
        node = root["children"][ident] = {
            "count": 0,
            "children": {},
            "identifier": ident,
            "description": ident,
        }
    return node


class LoopWatchdog:
    """Tick/stall watchdog for one event loop.

    Loop side: :meth:`tick` runs as a periodic callback and observes the
    loop's scheduling lag (actual gap minus the nominal interval) into
    ``hist_lag`` — a loaded loop shows up as a fattening
    ``dtpu_loop_lag_seconds`` histogram long before anything freezes.

    Thread side: a daemon monitor (``Event.wait`` paced, never a
    blocking sleep) notices when the last tick is older than
    ``stall-threshold`` while the loop is supposed to be alive, and —
    exactly once per stall episode — captures the loop thread's stack
    via ``sys._current_frames()`` into a ``stall`` record and
    flight-recorder event carrying the formatted traceback and the
    in-progress :class:`WallBudget` phase.  The episode re-arms only
    after a fresh tick proves the loop recovered.

    The flight-recorder ring is SINGLE-WRITER by design (``emit`` is an
    unsynchronized in-place slot write on the loop thread), so the
    capture only buffers the event; the first :meth:`tick` after
    recovery writes it into the ring from the loop thread.  The
    ``stalls`` deque and the log warning carry the postmortem
    immediately either way — including when the loop never recovers.
    """

    def __init__(self, trace: Any = None, wall: WallBudget | None = None,
                 interval: float | None = None,
                 stall_threshold: float | None = None,
                 max_stalls: int = 32):
        cfg = config.get("scheduler.profile")
        self.interval = (
            interval if interval is not None
            else config.parse_timedelta(cfg["watchdog-interval"])
        )
        self.stall_threshold = (
            stall_threshold if stall_threshold is not None
            else config.parse_timedelta(cfg["stall-threshold"])
        )
        self.trace = trace
        self.wall = wall
        self.hist_lag = Histogram(SECONDS_BUCKETS)
        self.stalls: deque[dict] = deque(maxlen=max_stalls)
        self.stalls_total = 0
        self.ticks_total = 0
        # stall events captured off-loop, ring-written by tick() on the
        # loop thread (deque append/popleft are GIL-atomic)
        self._pending_events: deque[tuple] = deque()
        self._last_tick = 0.0
        self._loop_ident: int | None = None
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    # ------------------------------------------------------------ loop side

    def tick(self) -> None:
        now = time()
        if self._last_tick:
            self.hist_lag.observe(max(0.0, now - self._last_tick - self.interval))
        self._last_tick = now
        self.ticks_total += 1
        while self._pending_events:
            # ring writes happen HERE, on the loop thread: the watchdog
            # thread must never race the loop's own emits
            phase, stim, tb, lag_ms = self._pending_events.popleft()
            if self.trace is not None:
                self.trace.emit(
                    "stall", phase or "loop-blocked", stim, key=tb, n=lag_ms
                )

    # ---------------------------------------------------------- thread side

    def start(self, loop_ident: int) -> None:
        self._loop_ident = loop_ident
        self._last_tick = time()
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="dtpu-stall-watchdog", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread = None

    def _run(self) -> None:
        # check twice per threshold: a stall is noticed within ~1.5x the
        # threshold without the monitor itself becoming a busy loop
        period = max(min(self.interval, self.stall_threshold / 2), 0.005)
        reported = False
        while not self._stop.wait(period):
            lag = time() - self._last_tick
            if lag <= self.stall_threshold:
                reported = False  # fresh tick seen: episode over, re-arm
                continue
            if reported:
                continue  # one stall event per episode
            reported = True
            try:
                self._capture(lag)
            except Exception:  # pragma: no cover - diagnostics must not kill
                logger.exception("stall capture failed")

    def _capture(self, lag: float) -> None:
        frame = sys._current_frames().get(self._loop_ident)
        tb = "".join(_traceback.format_stack(frame)) if frame is not None else ""
        phase, stim = ("", "")
        if self.wall is not None and self._loop_ident is not None:
            phase, stim = self.wall.current(self._loop_ident)
        rec = {
            "ts": time(),
            "lag_s": round(lag, 4),
            "phase": phase,
            "stim": stim,
            "traceback": tb,
        }
        self.stalls.append(rec)
        self.stalls_total += 1
        # the ring slot's key field carries the formatted traceback (a
        # stall is rare, the postmortem IS the payload); buffered here,
        # ring-written by the next on-loop tick — see the class docstring
        self._pending_events.append(
            (phase, stim, tb, int(lag * 1000))
        )
        logger.warning(
            "event loop stalled %.2fs (phase=%s stim=%s); stack:\n%s",
            lag, phase or "?", stim or "?", tb,
        )


# ------------------------------------------------------------- exposure


def profile_records(role: str, profiler: ControlPlaneProfiler | None,
                    wall: WallBudget | None,
                    watchdog: LoopWatchdog | None,
                    extra_trees: dict[str, dict] | None = None) -> list[dict]:
    """The ``/profile`` route body, shared by both roles: a ``head``
    record (counters, wall totals, recent stalls), one ``profile``
    record per tree (``which`` = ``loop`` / extra keys such as ``exec``),
    and a ``samples`` record with the recent (ts, phase, stim) stamps.
    Serialized with :func:`distributed_tpu.tracing.to_jsonl`."""
    head: dict[str, Any] = {"v": 1, "kind": "head", "role": role}
    if wall is not None:
        head["wall_seconds"] = {
            k: round(v, 6) for k, v in wall.snapshot().items()
        }
        head["wall_entries"] = wall.snapshot_counts()
    if profiler is not None:
        head["samples_total"] = profiler.total_samples
        head["idle_samples"] = profiler.idle_samples
    if watchdog is not None:
        head["ticks_total"] = watchdog.ticks_total
        head["stalls_total"] = watchdog.stalls_total
        head["stalls"] = list(watchdog.stalls)
    records = [head]
    if profiler is not None:
        records.append({
            "v": 1, "kind": "profile", "which": "loop",
            "tree": profiler.get_profile(),
        })
        records.append({
            "v": 1, "kind": "samples",
            "recent": [
                {"ts": ts, "phase": ph, "stim": st}
                for ts, ph, st in list(profiler.samples)
            ],
        })
    for which, tree in (extra_trees or {}).items():
        records.append(
            {"v": 1, "kind": "profile", "which": which, "tree": tree}
        )
    return records


def profile_jsonl(role: str, profiler: ControlPlaneProfiler | None,
                  wall: WallBudget | None, watchdog: LoopWatchdog | None,
                  extra_trees: dict[str, dict] | None = None) -> str:
    return to_jsonl(profile_records(role, profiler, wall, watchdog,
                                    extra_trees))


def profile_to_speedscope(tree: dict, name: str = "dtpu-profile") -> dict:
    """Convert a profile call tree (``diagnostics.profile`` format, as
    served by ``/profile`` ``profile`` records) into a speedscope
    sampled profile (https://www.speedscope.app file format): each
    node's *self* count becomes one weighted sample of its root-first
    stack, so the flamegraph shows exactly the sampled distribution."""
    frames: list[dict] = []
    findex: dict[str, int] = {}
    samples: list[list[int]] = []
    weights: list[int] = []

    def frame_id(node: dict) -> int:
        ident = node["identifier"]
        i = findex.get(ident)
        if i is None:
            i = findex[ident] = len(frames)
            parts = ident.split(";")
            frames.append({
                "name": node.get("description") or parts[0] or ident,
                "file": parts[1] if len(parts) > 1 else "",
                "line": int(parts[2]) if len(parts) > 2
                and parts[2].isdigit() else 0,
            })
        return i

    def walk(node: dict, stack: list[int]) -> None:
        children = node.get("children", {})
        self_count = node.get("count", 0) - sum(
            c.get("count", 0) for c in children.values()
        )
        if self_count > 0 and stack:
            samples.append(stack)
            weights.append(self_count)
        for child in children.values():
            walk(child, stack + [frame_id(child)])

    walk(tree, [])
    total = sum(weights)
    return {
        "$schema": "https://www.speedscope.app/file-format-schema.json",
        "exporter": "distributed_tpu",
        "name": name,
        "shared": {"frames": frames},
        "profiles": [{
            "type": "sampled",
            "name": name,
            "unit": "none",
            "startValue": 0,
            "endValue": total,
            "samples": samples,
            "weights": weights,
        }],
    }


__all__ = [
    "ControlPlaneProfiler",
    "IDLE_FILES",
    "LOOP_IDLE",
    "LoopWatchdog",
    "PHASES",
    "PHASE_PREFIX",
    "WallBudget",
    "loop_budget",
    "merge",
    "profile_jsonl",
    "profile_records",
    "profile_to_speedscope",
]

"""JAX placement co-processor: batched decide_worker on device.

The north-star integration (BASELINE.json): instead of running the
python ``decide_worker`` min-loop per task (reference scheduler.py:8550,
~1 ms/task), the scheduler plans a whole incoming graph in one pass at
``update_graph`` time — ``ops.leveled`` packs the DAG into topological
levels with a single O(T+E) native pass and places every wave with
frontier-sized jitted dispatches, one host sync for the whole graph.
The plan is consumed as a per-task hint
by ``decide_worker_non_rootish`` via the ``SchedulerState.placement``
hook; any deviation (worker died, restrictions, occupancy drift) falls
back to the python locality oracle, and WorkStealing rebalances
dynamically — the plan is a speculative hint exactly like the
reference's root-ish ``tg.last_worker`` co-assignment
(reference scheduler.py:2135).

Toggle via ``scheduler.jax.enabled`` / ``scheduler.jax.min-batch``.
"""

from __future__ import annotations

import asyncio
import atexit
import itertools
import logging
import math as _math
import threading
from typing import TYPE_CHECKING, Any

from distributed_tpu import config
from distributed_tpu.graph.spec import Key

if TYPE_CHECKING:
    from distributed_tpu.scheduler.state import SchedulerState, TaskState, WorkerState

logger = logging.getLogger("distributed_tpu.jax_placement")

_DEFAULT_NBYTES = 10_000.0  # cost-model guess for unobserved outputs

_MESH_UNSET = object()  # mesh not built yet (vs. None = build failed/off)

import os as _os
_PARK_DEBUG: "list | None" = [] if _os.environ.get("DTPU_PARK_DEBUG") else None


#: atexit grace for an in-flight plan: long enough for a compile or
#: dispatch to drain (seconds), short enough that a hung device call
#: cannot pin the exit for more than this
_EXIT_DRAIN_S = 15.0


class _DaemonExecutor:
    """Single daemon-thread executor with the tiny slice of the
    concurrent.futures API the planner uses (submit/shutdown).

    ThreadPoolExecutor threads are non-daemon and joined at interpreter
    exit; a hung device call would pin the process forever.  A daemon
    thread just dies with the process — except that dying INSIDE an XLA
    compile/dispatch segfaults the interpreter teardown (reproduced ~80%
    with the sharded engine's seconds-long compiles in flight at exit),
    so an atexit hook waits a BOUNDED ``_EXIT_DRAIN_S`` for the
    in-flight job before teardown proceeds: normal plans drain, a hung
    call costs at most the grace period."""

    def __init__(self, name: str):
        import queue
        from concurrent.futures import Future

        self._Future = Future
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._idle = threading.Event()
        self._idle.set()
        self._pending = 0  # queued + running jobs, under _lock
        self._lock = threading.Lock()
        self._thread = threading.Thread(
            target=self._run, name=name, daemon=True
        )
        self._thread.start()
        atexit.register(self._drain_at_exit)

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            fut, fn, args = item
            try:
                if not fut.set_running_or_notify_cancel():
                    continue
                try:
                    fut.set_result(fn(*args))
                except BaseException as exc:  # noqa: BLE001 - to waiter
                    fut.set_exception(exc)
            finally:
                with self._lock:
                    self._pending -= 1
                    if self._pending == 0:
                        self._idle.set()

    def _drain_at_exit(self) -> None:
        self._idle.wait(_EXIT_DRAIN_S)

    def submit(self, fn, *args):
        fut = self._Future()
        with self._lock:
            self._pending += 1
            self._idle.clear()
        self._q.put((fut, fn, args))
        return fut

    def shutdown(self, wait: bool = False, cancel_futures: bool = False) -> None:
        self._q.put(None)
        if self._idle.is_set():
            # nothing in flight: drop the exit hook so repeated
            # create/close cycles don't accumulate registrations.  With
            # a job still running the hook MUST stay — close-then-exit
            # mid-XLA-dispatch is exactly the teardown segfault the
            # drain exists for.
            try:
                atexit.unregister(self._drain_at_exit)
            except Exception:  # pragma: no cover - interpreter teardown
                pass


def device_dispatch_worthwhile(n_workers: int, n_items: int,
                               min_items: int,
                               periodic: bool = False) -> bool:
    """Shared gate for every scheduler device-kernel path (placement,
    stealing, AMM): the co-processor pays off only with enough workers
    (below ``scheduler.jax.min-workers`` the O(deps) python oracles win)
    and enough items to amortize a dispatch.

    ``periodic``: the caller dispatches on the event loop EVERY cycle
    (stealing balance, AMM, rebalance) rather than once per graph, so it
    keeps its own higher worker floor — forcing ``min-workers`` down to
    study placement hints must not drag a per-tick jax dispatch into
    small clusters (measured: 9x wall blowup at 16 workers)."""
    if not config.get("scheduler.jax.enabled"):
        return False
    floor = max(config.get("scheduler.jax.min-workers"), 2)
    if periodic:
        floor = max(floor, config.get("scheduler.jax.periodic-min-workers"))
    return n_workers >= floor and n_items >= min_items


def _report_precompile(fut) -> None:
    """Nothing else reads the precompile job's future: a failure would
    pass unseen, and every later bucket would compile on first use."""
    if not fut.cancelled() and fut.exception() is not None:
        logger.error("ahead-of-time compile failed", exc_info=fut.exception())


class JaxPlacement:
    """Whole-graph device planner behind the SchedulerState.placement hook.

    Planning runs OFF the event loop by default: ``plan_graph`` snapshots
    the batch into SoA arrays synchronously (cheap) and hands
    pack+place to a single worker thread, so jit compiles and device
    round-trips never block scheduling.  The plan is only a hint cache —
    tasks that reach ``decide_worker`` before the plan lands simply take
    the python locality oracle, and the plan serves the (much larger)
    tail of waves that become ready as execution proceeds.  Set
    ``scheduler.jax.sync-plan`` for deterministic tests.
    """

    def __init__(self, min_batch: int | None = None,
                 max_batch: int | None = None,
                 min_workers: int | None = None,
                 sync: bool | None = None,
                 min_transfer_ratio: float | None = None):
        self.min_transfer_ratio = (
            min_transfer_ratio if min_transfer_ratio is not None
            else float(config.get("scheduler.jax.min-transfer-ratio"))
        )
        self.min_batch = (
            min_batch if min_batch is not None
            else config.get("scheduler.jax.min-batch")
        )
        self.min_workers = (
            min_workers if min_workers is not None
            else config.get("scheduler.jax.min-workers")
        )
        self.max_batch = max_batch or 1_000_000
        hd = config.get("scheduler.jax.home-depth")
        self.home_depth: int | None = None if hd in ("inf", None) else int(hd)
        self.drift_yield = bool(config.get("scheduler.jax.drift-yield"))
        self.sync = (
            sync if sync is not None
            else bool(config.get("scheduler.jax.sync-plan"))
        )
        # device-mesh sharding (scheduler.jax.mesh subtree): when
        # enabled, the leveled engine runs as ONE partitioned XLA
        # program over the mesh and the fleet half comes from the
        # mirror's workers-axis shards; any failure falls back to the
        # single-device engine, which falls back to the python oracle.
        # "auto" (the default, ROADMAP item 2 leftover): the sharded
        # engine turns on iff MORE THAN ONE device is visible at
        # mesh-build time — a single-device host pays pure collective
        # overhead, so it keeps the single-device -> python fallback
        # chain.  Explicit booleans force it either way.
        mesh_cfg = config.get("scheduler.jax.mesh.enabled")
        self.mesh_enabled: bool | None = (
            mesh_cfg if isinstance(mesh_cfg, bool) else None
        )
        self.mesh_devices = int(config.get("scheduler.jax.mesh.devices"))
        self.mesh_layout = str(config.get("scheduler.jax.mesh.layout"))
        self._mesh: Any = _MESH_UNSET
        self.plan: dict[Key, str] = {}
        # stimulus id of the most recently LANDED plan: the decision
        # ledger stamps it onto every plan-homed placement row
        # (ledger.py ``plan_stim`` field), joining "this task ran on its
        # plan home" back to the flight recorder's ``kernel``
        # placement-plan event that computed the assignment
        self.plan_stim: str = ""
        self.plans_computed = 0
        self.plan_hits = 0
        self.plan_misses = 0
        self.plan_parks = 0
        self.plans_inflight = 0
        # miss breakdown (diagnostics): why CONSULTED hints were refused
        # (these partition plan_misses exactly)
        self.miss_reasons: dict[str, int] = {
            "worker-gone": 0, "restricted": 0, "dep-moved": 0,
            "idle-yield": 0, "park-declined": 0,
        }
        # hints discarded WITHOUT being consulted (not misses): pruned
        # as stale, or landed after the oracle had already placed them
        self.hint_drops: dict[str, int] = {
            "stale-dropped": 0, "landed-late": 0,
        }
        self.enabled = True
        self._executor: _DaemonExecutor | None = None
        # ahead-of-time compiles of the periodic device paths, per
        # mirror capacity (see _maybe_precompile)
        self._precompile_executor: _DaemonExecutor | None = None
        self._precompiled_cap = 0
        self.precompiled: Any = None  # Future[int] of the latest job
        from distributed_tpu.ops.compile_cache import enable_compile_cache

        enable_compile_cache()

    # ------------------------------------------------------------- hooks

    def on_add_worker(self, state: "SchedulerState", ws: "WorkerState") -> None:
        # plans stay valid as hints; new workers fill via stealing
        self._maybe_precompile(state)

    def _maybe_precompile(self, state: "SchedulerState") -> None:
        """Once the fleet is large enough for the periodic device paths
        (stealing, ReduceReplicas, and the mirror sync stealing reads),
        compile their programs for every row bucket at the mirror's
        capacity on a daemon thread.  Those paths pad their rows to a
        power of two of the live load, so without this a bucket met for
        the first time compiles on the event loop (AMM) or delays a
        steal plan, on every cluster that grows its load."""
        mirror = state.mirror
        if (
            mirror is None
            or not self.enabled
            or mirror.cap == self._precompiled_cap
            or not device_dispatch_worthwhile(
                len(state.workers), 0, 0, periodic=True
            )
        ):
            return
        self._precompiled_cap = mirror.cap
        if self._precompile_executor is None:
            self._precompile_executor = _DaemonExecutor("jax-precompile")
            # runs before the executor's exit drain (atexit is LIFO): the
            # job stops after the program in flight
            atexit.register(self._stop_precompile)
        self.precompiled = self._precompile_executor.submit(
            self._precompile, mirror, mirror.cap
        )
        self.precompiled.add_done_callback(_report_precompile)

    def _stop_precompile(self) -> None:
        self._precompiled_cap = -1

    def _precompile(self, mirror, cap: int) -> int:
        from distributed_tpu.ops import amm as ops_amm
        from distributed_tpu.ops import stealing as ops_stealing
        from distributed_tpu.scheduler.stealing import WorkStealing

        n = 0
        for lowered in itertools.chain(
            mirror.lower_device_view(WorkStealing.DEVICE_FIELDS, cap),
            ops_stealing.lower_all(cap, WorkStealing.DEVICE_MAX_TASKS),
            ops_amm.lower_all(cap),
        ):
            # a grown fleet or a closed scheduler supersedes this job;
            # stopping between programs also keeps interpreter exit from
            # tearing down mid-compile
            if not self.enabled or cap != self._precompiled_cap:
                break
            lowered.compile()
            n += 1
        return n

    def on_remove_worker(self, state: "SchedulerState", ws: "WorkerState") -> None:
        addr = ws.address
        # follow-dep hints survive a departure (the dep re-resolves
        # against live replicas); only spread hints pinned to the dead
        # worker are dropped
        self.plan = {
            k: a for k, a in self.plan.items()
            if a[0] is not None or a[1] != addr
        }
        # parked-task splicing on worker death lives in
        # SchedulerState.remove_worker (the state owns queue structures)

    def wants(self, ts: "TaskState") -> bool:
        return self.enabled and ts.key in self.plan

    # -------------------------------------------------------- consumption
    #
    # A plan's value is PROSPECTIVE locality: it co-assigns whole
    # subtrees so that once the first task of a tile runs home, every
    # later one finds its inputs local.  Consume-time objective
    # comparisons (occupancy + bytes already in place) cannot see that —
    # at decide time of the EARLY tasks nothing is local anywhere, so
    # "yield to any idle worker" systematically shreds the plan
    # (measured: even a hand-computed comm-optimal tiling lost to the
    # oracle when consumed through idle-yield).  The rules here:
    #
    #   open slot on the home worker  -> place there (hit)
    #   home busy, short backlog      -> PARK: the task queues scheduler-
    #                                    side and the home worker pulls it
    #                                    at its next slot-open
    #   home backlog beyond slack     -> the plan has drifted from live
    #                                    load: yield to the idle worker
    #                                    (objective with transfer latency)

    def resolve(
        self,
        state: "SchedulerState",
        ts: "TaskState",
        valid_workers: "set[WorkerState] | None",
    ) -> "tuple[str, WorkerState | None]":
        """(verdict, ws): ("hit", ws) place now; ("park", ws) defer to
        ws's queue-pull; ("miss", None) hint unusable, use the oracle.

        This is the single consumption point for BOTH transition
        drivers: the per-key engine and the batched flood engine
        (state.py ``stimulus_tasks_finished_batch``) route every ready
        task of a drain round through here against the LIVE occupancy,
        so hint verdicts are identical whichever driver delivered the
        stimulus — the batching lives in message dispatch and send
        coalescing, never in placement semantics (docs/batching.md).
        The plan itself is the batch decision: one ``plan_graph`` device
        call per submitted graph amortizes decide_worker over the whole
        batch, and each resolve is a dict lookup plus backlog math."""
        entry = self.plan.get(ts.key)
        if entry is None:
            return "miss", None
        follow_key, addr = entry
        if follow_key is not None:
            # locality hint: follow the chosen dependency to its LIVE
            # location — robust to upstream drift by construction; when
            # the task is restricted, prefer a holder that satisfies the
            # restriction over the first replica found
            dts = state.tasks.get(follow_key)
            ws = None
            if dts is not None and dts.who_has:
                for cand in dts.who_has:
                    if cand in state.running and (
                        valid_workers is None or cand in valid_workers
                    ):
                        ws = cand
                        break
            if ws is None:
                return self._miss(
                    ts,
                    "restricted"
                    if dts is not None
                    and any(c in state.running for c in dts.who_has)
                    else "dep-moved",
                )
        else:
            ws = state.workers.get(addr)
            if ws is None or ws not in state.running:
                return self._miss(ts, "worker-gone")
            if valid_workers is not None and ws not in valid_workers:
                return self._miss(ts, "restricted")

        # drift check FIRST (even before the open-slot test: a home with
        # a free slot but an hour of occupancy must not absorb more):
        # the plan balanced load GLOBALLY, so during a ready-burst every
        # worker's queue deepens together — the home only loses its
        # claim when it is an OUTLIER vs the cluster-average backlog.
        backlog = ws.occupancy / max(ws.nthreads, 1)
        avg = (
            state.total_occupancy / state.total_nthreads
            if state.total_nthreads
            else 0.0
        )
        if self.home_depth is None:
            # deep-stack mode: tiles become READY at different times, so
            # mid-graph the dispatched load is always concentrated on
            # whichever tiles unblocked first — that is the pipeline
            # working, not drift.  Only an extreme, persistent outlier
            # (a genuinely slow/overloaded home) sheds load.
            slack = 4.0 * avg + max(
                8 * state.transfer_latency,
                2 * state.get_task_duration(ts),
                2.0,
            )
        else:
            slack = avg + max(
                8 * state.transfer_latency, 2 * state.get_task_duration(ts)
            )
        if _PARK_DEBUG is not None:
            _PARK_DEBUG.append((backlog, slack))
        if backlog > slack and state.idle and self.drift_yield:
            idle_ws = next(iter(state.idle.values()))
            bw = state.bandwidth
            lat = state.transfer_latency

            def objective(w: "WorkerState") -> float:
                missing = 0.0
                n_missing = 0
                for dts in ts.dependencies:
                    if w not in dts.who_has:
                        n_missing += 1
                        if dts.nbytes > 0:
                            missing += dts.nbytes
                # same cost model as worker_objective: a fetch pays a
                # fixed RPC latency regardless of payload size, so the
                # hint (zero missing deps) wins ties against "any idle
                # worker" whenever following it avoids real transfers
                return (
                    w.occupancy / max(w.nthreads, 1)
                    + missing / bw
                    + n_missing * lat
                )

            if objective(idle_ws) < objective(ws):
                return self._miss(ts, "idle-yield")

        # home accepts up to a stack beyond the open-slot line: a worker
        # fed exactly one task per slot-open goes dry for a scheduler
        # round trip between tasks (completion -> stimulus -> pull ->
        # compute-task message).  home-depth "inf" stacks everything
        # worker-side (no parking at all) — safe because home-placed
        # tasks are exempt from stealing (ts.homed) and the drift check
        # above still sheds load when the home falls behind.
        if self.home_depth is None:
            depth = float("inf")
        else:
            sat = state.WORKER_SATURATION
            depth = (
                _math.ceil(ws.nthreads * sat) if _math.isfinite(sat)
                else 2 * ws.nthreads
            ) + self.home_depth * ws.nthreads
        if len(ws.processing) < depth:
            del self.plan[ts.key]
            self.plan_hits += 1
            # "plan" provenance: truthy for the steal exemption, and
            # the decision ledger labels the placement row kind "plan"
            # (the shuffle extension pins with "pin" — same exemption,
            # different ledger attribution)
            ts.homed = "plan" if follow_key is None else False
            return "hit", ws
        self.plan_parks += 1
        return "park", ws

    def _get_mesh(self, build: bool = False):
        """The engine mesh when the mesh path is enabled; ``None``
        means off, a one-device host, failed, or not built yet.

        Building initializes the jax backend (on the chip that takes
        seconds), so it only happens with ``build=True`` — which the
        plan path passes OFF the event loop (the daemon planner thread;
        sync mode builds inline, it is the explicit run-on-loop mode for
        tests).  Until the first async plan lands the mesh, on-loop
        snapshots see ``None`` and that plan runs with a replicated
        fleet upload — the mirror's sharded view joins from the second
        plan on."""
        if self.mesh_enabled is False:
            return None
        if self._mesh is _MESH_UNSET:
            if not build:
                return None
            mesh = None
            try:
                import jax

                from distributed_tpu.ops import partition as part

                if self.mesh_enabled is None and len(jax.devices()) < 2:
                    # auto mode on a 1-device host: stay on the
                    # single-device engine (tested: a 1x1 mesh is
                    # bit-identical but pays dispatch overhead)
                    mesh = None
                else:
                    mesh = part.make_engine_mesh(
                        self.mesh_devices or None, self.mesh_layout
                    )
            except Exception:
                logger.exception(
                    "engine mesh construction failed; "
                    "falling back to the single-device engine"
                )
            self._mesh = mesh
        return self._mesh

    def _miss(self, ts: "TaskState", reason: str):
        self.plan.pop(ts.key, None)
        self.plan_misses += 1
        self.miss_reasons[reason] += 1
        return "miss", None

    def decide_worker(
        self,
        state: "SchedulerState",
        ts: "TaskState",
        valid_workers: "set[WorkerState] | None",
    ) -> "WorkerState | None":
        """Legacy entry (no-worker recovery, opaque control planes):
        hit-or-miss only.  A would-be park is consumed as a miss — the
        caller is about to place the task elsewhere, so keeping the hint
        (and the park tally) would leak plan entries forever."""
        verdict, ws = self.resolve(state, ts, valid_workers)
        if verdict == "park":
            self.plan_parks -= 1
            self._miss(ts, "park-declined")
            return None
        return ws if verdict == "hit" else None


    # ---------------------------------------------------------- planning

    def plan_graph(self, state: "SchedulerState",
                   tasks: "dict[Key, TaskState]",
                   stimulus_id: str = "") -> int:
        """One device call placing the whole batch; returns tasks planned.

        ``stimulus_id`` is the submitting graph's causal id: the kernel
        dispatch is stamped into the flight recorder under it, joining
        the device plan to the ``update-graph`` ingress that caused it."""
        if not self.enabled:
            return 0
        # the batch filter and snapshot run on the loop: plan.snapshot
        with state.wall.phase("plan.snapshot", stimulus_id):
            planned = self._plan_snapshot(state, tasks)
        if planned is None:
            return 0
        batch, snapshot = planned
        state.trace.emit(
            "kernel", "placement-plan", stimulus_id, n=len(batch)
        )

        try:
            loop = asyncio.get_running_loop() if not self.sync else None
        except RuntimeError:
            loop = None
        if loop is None:
            try:
                # wall-budget seam (diagnostics/selfprofile.py): sync
                # mode dispatches ON the loop thread — bill it there
                state.wall.push("kernel.dispatch", stimulus_id)
                try:
                    plan, engine_shards = self._plan_from_arrays(*snapshot)
                finally:
                    state.wall.pop()
            except Exception:
                logger.exception(
                    "device planning failed; disabling co-processor"
                )
                self.enabled = False
                return 0
            if engine_shards:
                state.observe_engine_shards(engine_shards)
            self.plan.update(plan)
            self.plan_stim = stimulus_id
            self.plans_computed += 1
            return len(plan)

        if self._executor is None:
            # daemon planning thread: a non-daemon executor thread stuck
            # in a hung device call would keep the whole process from
            # exiting (concurrent.futures joins its threads atexit).
            # The plan simply never lands; the python oracle carries
            # the graph.
            self._executor = _DaemonExecutor("jax-placement")
        self.plans_inflight += 1
        wall = state.wall

        def _plan_job(*args):
            # wall-budget seam: the async plan bills its wall to the
            # PLANNER thread's stack (the budget is per-thread), so the
            # control-plane profiler's planner samples land under
            # phase:kernel.dispatch without touching the loop's stack
            wall.push("kernel.dispatch", stimulus_id)
            try:
                return self._plan_from_arrays(*args)
            finally:
                wall.pop()

        fut = self._executor.submit(_plan_job, *snapshot)

        def _done(f):
            try:
                plan = f.result()
            except BaseException as exc:
                if isinstance(exc, (KeyboardInterrupt, SystemExit)):
                    raise
                plan = None, None
                # a future cancelled by close() is a clean shutdown, not
                # a planning failure
                if not f.cancelled():
                    logger.exception(
                        "device planning failed; disabling co-processor"
                    )
                    self.enabled = False
            try:
                loop.call_soon_threadsafe(
                    self._merge, plan, state, stimulus_id
                )
            except RuntimeError:
                # loop closed before the plan landed: the merge (and its
                # inflight decrement) will never run on-loop
                self.plans_inflight -= 1

        fut.add_done_callback(_done)
        return 0

    def _plan_snapshot(self, state: "SchedulerState",
                       tasks: "dict[Key, TaskState]"):
        """``(batch, snapshot)`` of the tasks to plan, or None when this
        graph is not worth a device plan."""
        # drop stale hints first: keys gone from the scheduler or no
        # longer pending will never be consulted and would accumulate
        if self.plan:
            before = len(self.plan)
            self.plan = {
                k: a
                for k, a in self.plan.items()
                if (pts := state.tasks.get(k)) is not None
                and pts.state in ("released", "waiting", "queued", "no-worker")
            }
            self.hint_drops["stale-dropped"] += before - len(self.plan)
        # plan only runnable *pending* tasks whose dependencies are inside
        # the batch (external deps already sit on specific workers: the
        # python locality oracle is the right tool for those few).
        # Rootish tasks ARE planned: the partitioner co-assigns a tile's
        # sources with the tile, so inputs are born where they are
        # consumed instead of round-robined by rootish co-assignment.
        batch: list[TaskState] = []
        keyset = set(tasks)
        for ts in tasks.values():
            if ts.run_spec is None or ts.actor or ts.has_restrictions:
                continue
            if ts.state not in ("released", "waiting"):
                continue
            if all(dts.key in keyset for dts in ts.dependencies):
                batch.append(ts)
        if len(batch) < self.min_batch or len(batch) > self.max_batch:
            return None
        if len(state.workers) < max(self.min_workers, 2):
            return None
        # PRIORITY order is load-bearing: the partitioner's block init
        # chunks this axis, and scheduler priorities are depth-first
        # graph order (graph/order.py) — adjacent tasks are related
        batch.sort(key=lambda ts: ts.priority or (0,))
        durations, out_bytes, known_frac = self._snapshot_nodes(state, batch)
        ratio = self.min_transfer_ratio
        if (
            ratio
            and known_frac >= 0.5
            and float(out_bytes.mean()) / state.bandwidth
            + state.transfer_latency
            < ratio * float(durations.mean())
        ):
            # transfers are noise next to compute: locality hints cannot
            # pay for themselves on this graph (and occupancy-aware
            # consumption would discard them anyway) — skip the dispatch
            # before paying for the edge snapshot.  Only trustworthy
            # when durations are mostly MEASURED: the 500ms unknown-task
            # default would otherwise veto planning for every
            # first-of-its-kind graph exactly when the plan matters.
            return None
        return batch, self._snapshot(state, batch, durations, out_bytes)

    def planner_ident(self) -> int | None:
        """Thread ident of the daemon planner thread (None before the
        first async plan spawns it) — the control-plane profiler
        (diagnostics/selfprofile.py) samples it alongside the loop."""
        ex = self._executor
        thread = getattr(ex, "_thread", None) if ex is not None else None
        return thread.ident if thread is not None else None

    def close(self) -> None:
        """Release the planning thread (scheduler shutdown)."""
        self.enabled = False
        if self._precompile_executor is not None:
            self._stop_precompile()
            atexit.unregister(self._stop_precompile)
        for ex in (self._executor, self._precompile_executor):
            if ex is not None:
                ex.shutdown(wait=False, cancel_futures=True)
        self._executor = self._precompile_executor = None

    def _merge(self, plan_shards, state: "SchedulerState",
               stimulus_id: str = "") -> None:
        """Land an async plan on the loop thread, keeping only hints for
        tasks still pending — tasks the oracle placed while the plan was
        computing would otherwise accumulate as dead entries forever
        (and, with reused pure keys, serve stale hints to later graphs)."""
        with state.wall.phase("plan.merge", stimulus_id):
            self.plans_inflight -= 1
            plan, engine_shards = plan_shards or (None, None)
            if engine_shards:
                state.observe_engine_shards(engine_shards)
            if plan:
                live = {
                    k: v
                    for k, v in plan.items()
                    if (ts := state.tasks.get(k)) is not None
                    and ts.state in ("released", "waiting", "queued", "no-worker")
                }
                self.hint_drops["landed-late"] += len(plan) - len(live)
                if live:
                    self.plan.update(live)
                    self.plan_stim = stimulus_id
                    self.plans_computed += 1
                    logger.debug(
                        "planned %d tasks on device (%d already placed)",
                        len(live), len(plan) - len(live),
                    )

    @staticmethod
    def _snapshot_nodes(state: "SchedulerState", batch: list):
        """Per-task cost arrays + fraction of MEASURED durations (the
        payoff gate is meaningless against the unknown-task default)."""
        import numpy as np

        n = len(batch)
        durations = np.empty(n, np.float32)
        out_bytes = np.empty(n, np.float32)
        known = 0
        for i, ts in enumerate(batch):
            prefix = ts.prefix
            if prefix is not None and prefix.duration_average >= 0:
                known += 1
            durations[i] = state.get_task_duration(ts)
            nbytes = ts.nbytes
            if nbytes < 0 and prefix is not None and prefix.nbytes_total:
                counts = sum(prefix.state_counts.values()) or 1
                nbytes = prefix.nbytes_total / counts
            out_bytes[i] = nbytes if nbytes and nbytes > 0 else _DEFAULT_NBYTES
        return durations, out_bytes, known / max(n, 1)

    def _snapshot(self, state: "SchedulerState", batch: list,
                  durations, out_bytes):
        """Synchronous SoA snapshot of the batch + worker fleet (the
        TaskState graph must not be touched off-loop).

        The fleet half comes from the persistent mirror when available
        (scheduler/mirror.py): slot-indexed capacity-sized arrays with
        tombstone rows carrying ``running=False``/``nthreads=0`` — both
        device engines already mask on exactly those bits — copied
        because the planner thread reads them while the loop keeps
        mutating the live buffers.  Cost: O(dirty) refresh + numpy
        copies, no per-worker Python loop.  Without a mirror the
        from-scratch pack below remains the oracle path."""
        import numpy as np

        index = {ts.key: i for i, ts in enumerate(batch)}
        keys = [ts.key for ts in batch]
        src: list[int] = []
        dst: list[int] = []
        for i, ts in enumerate(batch):
            for dts in ts.dependencies:
                j = index.get(dts.key)
                if j is not None:
                    src.append(j)
                    dst.append(i)
        mirror = state.mirror
        if mirror is not None:
            fv = mirror.fleet_view()
            nthreads = fv.nthreads.copy()
            occupancy = fv.occupancy.copy()
            running = fv.running.copy()
            addrs = list(fv.addrs)
        else:
            workers = list(state.workers.values())
            nthreads = np.asarray([ws.nthreads for ws in workers], np.int32)
            occupancy = np.asarray(
                [ws.occupancy for ws in workers], np.float32
            )
            running = np.asarray(
                [ws in state.running for ws in workers], bool
            )
            addrs = [ws.address for ws in workers]
        # mesh plan path: grab the mirror's workers-axis device shards
        # ON LOOP (cheap O(dirty) scatter) so the planner thread reads
        # immutable jax arrays the kernel consumes with ZERO fleet H2D;
        # the host copies above still seed the load carry and the
        # uniform/wide decisions.  Building the mesh is jax backend
        # init — on-loop only in sync mode; the async path builds it in
        # the planner thread on its first plan (_plan_from_arrays).
        mesh = self._get_mesh(build=self.sync)
        fleet_dev = None
        if mesh is not None and mirror is not None:
            try:
                fleet_dev = mirror.sharded_device_view(mesh)
            except Exception:
                logger.exception(
                    "sharded mirror view failed; replicated fleet upload"
                )
        return (
            keys, durations, out_bytes,
            np.asarray(src, np.int32), np.asarray(dst, np.int32),
            nthreads, occupancy, running, addrs, state.bandwidth,
            state.transfer_latency, mesh, fleet_dev,
        )

    def _plan_from_arrays(self, keys, durations, out_bytes, src, dst,
                          nthreads, occupancy, running, addrs, bandwidth,
                          transfer_latency=0.0, mesh=None, fleet_dev=None):
        """Plan on pure arrays — safe to run off-loop (the only ``self``
        use is the one-time mesh build, deliberately placed HERE so jax
        backend init happens on the planner thread).  Returns
        ``(plan, engine_shards)`` where ``engine_shards`` is the sharded
        engine's per-shard stat list (None off the mesh path).

        Two device engines compose here (ops/partition.py docstring has
        the measurements):

        - ``ops.partition`` (preferred while T·W fits the dense score
          matrix): comm-volume partitioning over the priority axis,
          emitted as ABSOLUTE home hints ``(None, addr)`` — the park/
          pull consumption keeps whole tiles together, which is the
          point; drift tolerance comes from the backlog checks at
          consume time, not from re-resolution.
        - ``ops.leveled`` (the million-task fallback): wave-synchronous
          placement following heavy dependencies.  A locality choice is
          encoded FOLLOW-THIS-DEPENDENCY, not as an absolute address:
          ``resolve`` finds the dep's CURRENT holder at consume time, so
          a hint survives upstream drift (absolute addresses died with
          the first upstream deviation and the invalidation cascaded —
          measured at 84% of all misses on the rechunk+tensordot bench).
          Spread placements (choice 2) keep the planned address: their
          content IS the global load-balance assignment.
        """
        import numpy as np

        from distributed_tpu.ops import partition as part

        engine = config.get("scheduler.jax.partitioner")
        run_idx = np.flatnonzero(running)
        n_running = len(run_idx)
        T = len(keys)
        # load-balance durations on the nthreads-weighted axis: a
        # 2-thread worker should receive twice the work.  The
        # partitioner treats workers as equal bins, so spread the label
        # space: worker w appears nthreads[w] times and the labels fold
        # back at the end.  The dense-score cap must count LANES (and
        # the pow2 padding of T), not workers — the score matrix is
        # T_padded x lanes.
        lanes: list[int] = []
        for wi in run_idx:
            lanes.extend([int(wi)] * max(int(nthreads[wi]), 1))
        if (
            engine in ("auto", "numpy")
            and n_running >= 2
            and part._bucket(T) * len(lanes) <= part.DENSE_LIMIT
        ):
            weights = (
                out_bytes[src] / bandwidth + transfer_latency
            ).astype(np.float32)
            if engine == "numpy":
                labels = part.partition_numpy(
                    durations, weights, src, dst, len(lanes)
                )
            else:
                try:
                    labels = part.partition_padded(
                        durations, weights, src, dst, len(lanes)
                    )
                except Exception:
                    logger.exception(
                        "jax partitioner failed; numpy fallback"
                    )
                    labels = part.partition_numpy(
                        durations, weights, src, dst, len(lanes)
                    )
            return {
                key: (None, addrs[lanes[int(labels[i])]])
                for i, key in enumerate(keys)
            }, None

        from distributed_tpu.ops.leveled import place_graph_streamed

        # streamed driver: on large graphs the pack fill and H2D upload
        # pipeline, so the plan lands one wire-crossing sooner (falls
        # back to pack+place below the streaming threshold).  With a
        # mesh the same driver dispatches through the SHARDED engine —
        # per-shard H2D tiles, mirror-resident fleet rows — and any
        # failure there degrades to the single-device program (the
        # python oracle stays the final fallback at consume time).
        engine_stats: dict | None = None
        packed = result = None
        if mesh is None:
            # first async plan with the mesh path on: build it here,
            # off the event loop (no-op when the path is disabled)
            mesh = self._get_mesh(build=True)
        if mesh is not None:
            engine_stats = {}
            try:
                packed, result = place_graph_streamed(
                    durations, out_bytes, src, dst, nthreads, occupancy,
                    running, bandwidth=bandwidth, latency=transfer_latency,
                    mesh=mesh, fleet_dev=fleet_dev, stats=engine_stats,
                )
            except Exception:
                logger.exception(
                    "sharded engine failed; single-device fallback"
                )
                engine_stats = None
                packed = result = None
        if result is None:
            packed, result = place_graph_streamed(
                durations, out_bytes, src, dst, nthreads, occupancy,
                running, bandwidth=bandwidth, latency=transfer_latency,
            )
        assignment = result.assignment
        nw = len(addrs)
        n = len(keys)
        inv = np.empty(max(n, 1), np.int32)
        inv[packed.perm] = np.arange(n, dtype=np.int32)
        hs = packed.heavy_s[inv[:n]]
        h2s = packed.heavy2_s[inv[:n]]
        horig = np.where(hs >= 0, packed.perm[np.maximum(hs, 0)], -1)
        h2orig = np.where(h2s >= 0, packed.perm[np.maximum(h2s, 0)], -1)
        follow = np.where(
            result.choice == 0, horig,
            np.where(result.choice == 1, h2orig, -1),
        )
        return {
            key: (
                keys[int(follow[i])] if follow[i] >= 0 else None,
                addrs[int(assignment[i])],
            )
            for i, key in enumerate(keys)
            if 0 <= assignment[i] < nw
        }, (engine_stats or {}).get("shards")

    def __repr__(self) -> str:
        return (
            f"<JaxPlacement plans={self.plans_computed} "
            f"hits={self.plan_hits} misses={self.plan_misses} "
            f"pending={len(self.plan)} enabled={self.enabled}>"
        )

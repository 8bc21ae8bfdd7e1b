"""Persistent SchedulerState device mirror: delta-maintained fleet SoA.

The co-processor kernels (placement planning, work stealing, AMM replica
drops, rebalance — scheduler/jax_placement.py, stealing.py, amm.py,
server.py) all consume the same fleet snapshot: per-worker ``nthreads``,
``occupancy``, managed-memory ``nbytes``, processing depth and the
``running``/``idle`` bits.  Before this module each kernel cycle
re-derived those arrays from scratch with a Python loop over
``state.workers`` and paid a fresh H2D upload of the whole fleet.

``SchedulerMirror`` keeps ONE persistent structure-of-arrays copy of the
fleet, updated by deltas from the transition engine and the worker
lifecycle paths instead of rebuilt per cycle:

- **Stable slots.**  Every registered worker owns a slot in the SoA
  (``WorkerState.idx``); slots survive unrelated churn, tombstoned slots
  are reused LIFO, and capacity doubles (never shrinks) so array shapes
  stay jit-cache-friendly and row indices stay valid across calls.
- **Dirty rows, not deltas-with-values.**  Mutation sites mark the row
  dirty (a ``set.add``); ``refresh()`` re-reads the live ``WorkerState``
  fields for dirty rows only.  Completeness of the marking is the
  invariant — it is what the from-scratch oracle check verifies — and
  value-correctness then holds by construction.  Per-cycle cost is
  O(dirty), not O(W).
- **Device residency.**  ``device_view()`` keeps jax arrays cached
  across cycles; a cycle uploads only the rows that changed since the
  last device sync (a scatter of O(dirty) rows) or nothing at all when
  the resident arrays are still fresh.
- **Oracle + fallback.**  The from-scratch pack (``oracle_fleet``)
  remains both the correctness oracle and the runtime fallback: with
  the mirror disabled every consumer runs its original Python pack, and
  ``DTPU_MIRROR_CHECK=1`` re-derives the fleet from scratch on every
  view and asserts bit-identical equality — the same contract style as
  the batched transition engine's per-key oracle (docs/batching.md).

The mirror is pure host-side numpy except ``device_view``; jax is
imported lazily so schedulers on no-device hosts never touch it.
"""

from __future__ import annotations

import functools
import logging
import os
from typing import TYPE_CHECKING, Any, NamedTuple

import numpy as np

if TYPE_CHECKING:
    from distributed_tpu.scheduler.state import SchedulerState, WorkerState

logger = logging.getLogger("distributed_tpu.mirror")

#: worker status strings -> stable i8 codes (mirror rows are numeric)
STATUS_CODES: dict[str, int] = {
    "running": 0,
    "paused": 1,
    "closing": 2,
    "closing_gracefully": 3,
    "init": 4,
    "closed": 5,
}
STATUS_UNKNOWN = 7

#: fields refreshed per row, in (name, dtype) order — the single source
#: of truth for the SoA layout, the oracle rows and the device cache
FIELDS: tuple[tuple[str, Any], ...] = (
    ("nthreads", np.int32),
    ("occupancy", np.float32),
    ("nbytes", np.float32),
    ("nprocessing", np.int32),
    ("running", np.bool_),
    ("idle", np.bool_),
    ("status", np.int8),
)

_MIN_CAP = 8


class MirrorParityError(AssertionError):
    """Incremental mirror diverged from the from-scratch oracle pack."""


class FleetView(NamedTuple):
    """One refreshed snapshot of the fleet SoA.

    The arrays are the mirror's LIVE buffers (capacity-sized; tombstone
    rows are zeroed with ``running=False``): on-loop consumers may read
    them synchronously but must copy before handing them to another
    thread — the next ``refresh()`` mutates dirty rows in place.
    """

    slots: np.ndarray        # i32[L] live slot indices, ascending
    nthreads: np.ndarray     # i32[cap]
    occupancy: np.ndarray    # f32[cap]
    nbytes: np.ndarray       # f32[cap] managed memory
    nprocessing: np.ndarray  # i32[cap]
    running: np.ndarray      # bool[cap]
    idle: np.ndarray         # bool[cap] (idle AND running: thief-eligible)
    status: np.ndarray       # i8[cap] STATUS_CODES
    addrs: list              # [cap] slot -> address | None
    ws_of: list              # [cap] slot -> WorkerState | None
    live_list: list          # [L] WorkerState in slot order
    live_pos: np.ndarray     # i32[cap] slot -> position in live_list | -1
    n_live: int


def oracle_fleet(state: "SchedulerState") -> dict[str, tuple]:
    """The from-scratch fleet pack — the Python loop the mirror
    replaces, kept as the correctness oracle and the disabled-mirror
    fallback.  Returns ``{address: row}`` with exactly the dtypes the
    mirror stores, so comparison is bit-identical."""
    rows: dict[str, tuple] = {}
    for addr, ws in state.workers.items():
        rows[addr] = (
            np.int32(ws.nthreads),
            np.float32(ws.occupancy),
            np.float32(ws.nbytes),
            np.int32(len(ws.processing)),
            np.bool_(ws in state.running),
            np.bool_(addr in state.idle and ws in state.running),
            np.int8(STATUS_CODES.get(ws.status, STATUS_UNKNOWN)),
        )
    return rows


class SchedulerMirror:
    """Incrementally-maintained SoA mirror of the scheduler's fleet."""

    def __init__(self, state: "SchedulerState", *,
                 capacity_doubling: bool = True,
                 check: bool | None = None):
        self.state = state
        self.capacity_doubling = capacity_doubling
        #: DTPU_MIRROR_CHECK: verify against the from-scratch oracle on
        #: every view (tests / staging; production pays nothing)
        self.check = (
            check if check is not None
            else os.environ.get("DTPU_MIRROR_CHECK", "").lower()
            not in ("", "0", "false", "off", "no")
        )
        self.cap = 0
        self._free: list[int] = []     # tombstoned slots, LIFO reuse
        self._next_slot = 0            # high-water mark of ever-used slots
        self._alloc_arrays(_MIN_CAP)
        self.addrs: list = [None] * self.cap   # slot -> address | None
        self.ws_of: list = [None] * self.cap   # slot -> WorkerState | None
        self._dirty: set[int] = set()
        self._device_dirty: set[int] = set()
        self._members_dirty = True
        self._live_slots = np.zeros(0, np.int32)
        self._live_list: list = []
        self._live_pos = np.full(self.cap, -1, np.int32)
        # device cache: field name -> jax array (capacity-sized)
        self._dev: dict[str, Any] = {}
        self._dev_cap = -1
        # SHARDED device cache (the mesh plan path): field name ->
        # jax array placed with NamedSharding over the engine mesh's
        # "workers" axis.  Slot s lives on shard s // (cap // n_shards)
        # — the block mapping NamedSharding uses for dim 0 — so slot
        # stability (tombstone LIFO reuse, no compaction) IS shard
        # stability; only capacity growth remaps rows (counted as a
        # full per-shard re-pack).  Separate dirty set: both the
        # single-device and the sharded cache must observe every row
        # change regardless of which consumer synced last.
        self._sdev: dict[str, Any] = {}
        self._sdev_mesh: Any | None = None
        self._sdev_cap = -1
        self._sdev_dirty: set[int] = set()
        # ------------------------------------------------ counters
        # (exposed through diagnostics/metrics; asserted by tests)
        self.generation = 0          # bumps when a refresh changed rows
        self.deltas_applied = 0      # mark() calls on live rows
        self.rows_refreshed = 0      # rows re-read from live state
        self.rows_uploaded = 0       # device rows scattered (partial H2D)
        self.bytes_uploaded = 0      # partial-upload payload bytes
        self.full_uploads = 0        # full-array device_put (growth/init)
        self.membership_rebuilds = 0  # live-view rebuilds (churn only)
        self.dirty_high_water = 0    # max dirty rows seen at one refresh
        self.oracle_checks = 0
        self.oracle_failures = 0
        #: incremented by consumers that fell back to the from-scratch
        #: Python pack while this mirror exists — 0 on the hot path
        self.oracle_packs = 0
        # ---------------------------------------- per-shard counters
        # (sharded_device_view; dtpu_mirror_shard_* at /metrics): a
        # fresh cycle must show ZERO rows uploaded on EVERY shard, and
        # full_packs must not creep past growth events
        self.shard_rows_uploaded: list[int] = []
        self.shard_bytes_uploaded: list[int] = []
        self.shard_full_packs: list[int] = []

    # ------------------------------------------------------- allocation

    def _alloc_arrays(self, cap: int) -> None:
        self.cap = cap
        for name, dtype in FIELDS:
            setattr(self, name, np.zeros(cap, dtype))

    def _grow(self) -> None:
        new_cap = self.cap * 2 if self.capacity_doubling else self.cap + _MIN_CAP
        for name, _dtype in FIELDS:
            old = getattr(self, name)
            buf = np.zeros(new_cap, old.dtype)
            buf[: self.cap] = old
            setattr(self, name, buf)
        self.addrs.extend([None] * (new_cap - self.cap))
        self.ws_of.extend([None] * (new_cap - self.cap))
        lp = np.full(new_cap, -1, np.int32)
        lp[: self.cap] = self._live_pos
        self._live_pos = lp
        self.cap = new_cap
        # shapes changed: the device caches must be rebuilt wholesale
        # (growth also remaps slot->shard: rows_per_shard doubled)
        self._dev.clear()
        self._device_dirty.clear()
        self._sdev.clear()
        self._sdev_dirty.clear()

    # ---------------------------------------------------- delta sources

    def on_add_worker(self, ws: "WorkerState") -> None:
        """Assign a stable slot (tombstone reuse first, then growth)."""
        if self._free:
            slot = self._free.pop()
        else:
            if self._next_slot >= self.cap:
                self._grow()
            slot = self._next_slot
            self._next_slot += 1
        ws.idx = slot
        self.addrs[slot] = ws.address
        self.ws_of[slot] = ws
        self._dirty.add(slot)
        self.deltas_applied += 1
        self._members_dirty = True

    def on_remove_worker(self, ws: "WorkerState") -> None:
        """Tombstone the slot; the row zeroes at the next refresh."""
        slot = ws.idx
        if slot < 0 or slot >= len(self.addrs) or self.ws_of[slot] is not ws:
            return
        self.addrs[slot] = None
        self.ws_of[slot] = None
        self._free.append(slot)
        ws.idx = -1
        self._dirty.add(slot)
        self.deltas_applied += 1
        self._members_dirty = True

    def mark(self, ws: "WorkerState") -> None:
        """A mirrored field of ``ws`` changed: mark its row dirty."""
        slot = ws.idx
        if slot >= 0:
            self._dirty.add(slot)
            self.deltas_applied += 1

    # ---------------------------------------------------------- refresh

    def refresh(self) -> int:
        """Flush dirty rows from live state into the host SoA; returns
        the number of rows refreshed (0 when the mirror was fresh)."""
        n = len(self._dirty)
        if n == 0:
            return 0
        if n > self.dirty_high_water:
            self.dirty_high_water = n
        state = self.state
        idle = state.idle
        running = state.running
        # ascending slot order: refresh writes commute per slot, but a
        # deterministic walk keeps upsert/scatter row order (and any
        # digest over it) hash-seed-independent
        for slot in sorted(self._dirty):
            ws = self.ws_of[slot]
            if ws is None:
                self.nthreads[slot] = 0
                self.occupancy[slot] = 0.0
                self.nbytes[slot] = 0.0
                self.nprocessing[slot] = 0
                self.running[slot] = False
                self.idle[slot] = False
                self.status[slot] = STATUS_CODES["closed"]
            else:
                self.nthreads[slot] = ws.nthreads
                self.occupancy[slot] = ws.occupancy
                self.nbytes[slot] = ws.nbytes
                self.nprocessing[slot] = len(ws.processing)
                is_running = ws in running
                self.running[slot] = is_running
                self.idle[slot] = is_running and ws.address in idle
                self.status[slot] = STATUS_CODES.get(ws.status, STATUS_UNKNOWN)
        self._device_dirty.update(self._dirty)
        self._sdev_dirty.update(self._dirty)
        self._dirty.clear()
        self.rows_refreshed += n
        self.generation += 1
        return n

    def _rebuild_membership(self) -> None:
        self._live_slots = np.asarray(
            [s for s, ws in enumerate(self.ws_of) if ws is not None],
            np.int32,
        )
        self._live_list = [self.ws_of[int(s)] for s in self._live_slots]
        self._live_pos.fill(-1)
        self._live_pos[self._live_slots] = np.arange(
            len(self._live_slots), dtype=np.int32
        )
        self._members_dirty = False
        self.membership_rebuilds += 1

    # ------------------------------------------------------------ views

    def fleet_view(self) -> FleetView:
        """Refresh dirty rows and return the shared host snapshot every
        co-processor front-end consumes this cycle."""
        self.refresh()
        if self._members_dirty:
            self._rebuild_membership()
        if self.check:
            self.verify()
        return FleetView(
            slots=self._live_slots,
            nthreads=self.nthreads,
            occupancy=self.occupancy,
            nbytes=self.nbytes,
            nprocessing=self.nprocessing,
            running=self.running,
            idle=self.idle,
            status=self.status,
            addrs=self.addrs,
            ws_of=self.ws_of,
            live_list=self._live_list,
            live_pos=self._live_pos,
            n_live=len(self._live_list),
        )

    def device_view(
        self, fields: tuple[str, ...] = ("nthreads", "occupancy", "running", "idle")
    ) -> dict[str, Any] | None:
        """Device-resident fleet arrays, updated row-wise.

        Returns ``{field: jax array}`` (capacity-sized, matching slot
        indices) or ``None`` when jax is unavailable — callers then use
        the host arrays from :meth:`fleet_view`.  Upload cost per call:
        nothing when no row changed since the last device sync, an
        O(dirty) scatter otherwise, a full ``device_put`` only at first
        use or after capacity growth.
        """
        # wall-budget seam (diagnostics/selfprofile.py): refresh + H2D
        # bill to mirror.upload on whichever thread runs the view
        with self.state.wall.phase("mirror.upload"):
            return self._device_view(fields)

    def _device_view(self, fields: tuple[str, ...]) -> dict[str, Any] | None:
        self.refresh()
        try:
            import jax.numpy as jnp
        except Exception:  # pragma: no cover - no-jax hosts
            return None
        if self._dev_cap != self.cap:
            self._dev.clear()
            self._dev_cap = self.cap
        # only ever-requested fields live on device: scattering the
        # remaining FIELDS would ship rows nothing reads (the host
        # consumers use fleet_view) on exactly the dispatch-latency-
        # bound path this cache exists for
        if self._device_dirty and self._dev:
            n_changed = len(self._device_dirty)
            rows = np.fromiter(sorted(self._device_dirty), np.int32, n_changed)
            # pow2-pad the scatter (repeat a real row; identical values,
            # so duplicates are harmless) to bound jit-shape churn
            pad = _bucket(n_changed)
            if pad > n_changed:
                rows = np.concatenate(
                    [rows, np.full(pad - n_changed, rows[0], np.int32)]
                )
            rows_j = jnp.asarray(rows)
            for name in self._dev:
                host = getattr(self, name)
                vals = host[rows]
                self._dev[name] = _scatter_rows()(
                    self._dev[name], rows_j, jnp.asarray(vals)
                )
                self.bytes_uploaded += int(vals.nbytes)
            self.rows_uploaded += n_changed
            # flight-recorder kernel hop: dirty-row scatter volume per
            # device sync (a fresh cycle emits nothing — zero H2D)
            self.state.trace.emit(
                "kernel", "mirror-upload", "", n=n_changed, dest="scatter"
            )
        missing = [f for f in fields if f not in self._dev]
        if missing:
            # first use of a field (or capacity growth): full upload,
            # which carries every past change for that field
            for name in missing:
                self._dev[name] = jnp.asarray(getattr(self, name))
            self.full_uploads += 1
            self.state.trace.emit(
                "kernel", "mirror-upload", "", n=self.cap, dest="full"
            )
        self._device_dirty.clear()
        return {f: self._dev[f] for f in fields}

    def lower_device_view(self, fields: tuple[str, ...], cap: int):
        """Yield the dirty-row scatter :meth:`device_view` uses for
        ``fields`` at capacity ``cap``, lowered for every row bucket up
        to the capacity: compiled ahead, a live sync never compiles.
        Reads only the field dtypes, so it is safe off the event loop."""
        import jax

        dtypes = dict(FIELDS)
        for dtype in sorted({np.dtype(dtypes[f]).str for f in fields}):
            rows = _bucket(0)
            while True:
                yield _scatter_rows().lower(
                    jax.ShapeDtypeStruct((cap,), dtype),
                    jax.ShapeDtypeStruct((rows,), np.int32),
                    jax.ShapeDtypeStruct((rows,), dtype),
                )
                if rows >= cap:
                    break
                rows <<= 1

    def sharded_device_view(
        self,
        mesh,
        fields: tuple[str, ...] = ("nthreads", "occupancy", "running"),
    ) -> dict[str, Any] | None:
        """Mesh-sharded fleet arrays for the SHARDED placement engine
        (ops/leveled.place_graph_leveled_sharded): capacity-sized jax
        arrays placed with ``NamedSharding(mesh, P("workers"))`` — each
        device of the engine mesh holds exactly its block of slot rows.

        Upload cost per call mirrors :meth:`device_view`, but accounted
        PER SHARD: nothing when no row changed since the last sharded
        sync (a fresh cycle ships zero fleet rows on every shard —
        counter-asserted in tests/test_mirror.py), an O(dirty) scatter
        grouped by owning shard otherwise, and a full per-shard pack
        only at first use, capacity growth or a mesh change.  Returns
        ``None`` when jax is unavailable or the mesh cannot divide the
        capacity (callers fall back to replicated host arrays).
        """
        with self.state.wall.phase("mirror.upload"):
            return self._sharded_device_view(mesh, fields)

    def _sharded_device_view(
        self, mesh, fields: tuple[str, ...]
    ) -> dict[str, Any] | None:
        self.refresh()
        try:
            import jax
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
        except Exception:  # pragma: no cover - no-jax hosts
            return None
        try:
            n_shards = int(mesh.shape["workers"])
        except (KeyError, TypeError):
            return None
        if n_shards <= 0 or self.cap % n_shards != 0:
            # pow2 capacity x pow2 workers-axis in practice; a mesh that
            # cannot divide the slot space gets the replicated fallback
            return None
        sharding = NamedSharding(mesh, P("workers"))
        if len(self.shard_rows_uploaded) != n_shards:
            # first sharded view, or a DIFFERENT mesh shape: the label
            # space changed, so the counter vectors restart
            self.shard_rows_uploaded = [0] * n_shards
            self.shard_bytes_uploaded = [0] * n_shards
            self.shard_full_packs = [0] * n_shards
        if self._sdev_cap != self.cap or self._sdev_mesh != mesh:
            # capacity growth or mesh swap: arrays rebuild wholesale
            # below (counters keep accumulating — they are monotonic).
            # Mesh EQUALITY, not identity: a caller rebuilding an equal
            # mesh per cycle must not trigger a re-pack per plan.
            self._sdev.clear()
            self._sdev_cap = self.cap
            self._sdev_mesh = mesh
        rows_per_shard = self.cap // n_shards
        if self._sdev_dirty and self._sdev:
            # per-shard dirty-row scatter: group the dirty slots by
            # owning shard and ship each shard ONLY its rows (pow2-
            # padded with a repeated real row to bound jit-shape churn)
            by_shard: dict[int, list[int]] = {}
            for slot in sorted(self._sdev_dirty):
                by_shard.setdefault(slot // rows_per_shard, []).append(slot)
            for shard_i, slots in sorted(by_shard.items()):
                n_changed = len(slots)
                rows = np.asarray(slots, np.int32)
                pad = _bucket(n_changed)
                if pad > n_changed:
                    rows = np.concatenate(
                        [rows, np.full(pad - n_changed, rows[0], np.int32)]
                    )
                rows_j = jnp.asarray(rows)
                for name in self._sdev:
                    host = getattr(self, name)
                    vals = host[rows]
                    self._sdev[name] = _scatter_rows()(
                        self._sdev[name], rows_j, jnp.asarray(vals)
                    )
                    self.shard_bytes_uploaded[shard_i] += int(vals.nbytes)
                self.shard_rows_uploaded[shard_i] += n_changed
            self.rows_uploaded += len(self._sdev_dirty)
            self.state.trace.emit(
                "kernel", "mirror-upload", "", n=len(self._sdev_dirty),
                dest="shard-scatter",
            )
        missing = [f for f in fields if f not in self._sdev]
        if missing:
            # first use of a field / growth / mesh change: one full
            # sharded device_put — every shard receives its whole block
            for name in missing:
                self._sdev[name] = jax.device_put(
                    getattr(self, name), sharding
                )
            for shard_i in range(n_shards):
                self.shard_full_packs[shard_i] += 1
            self.full_uploads += 1
            self.state.trace.emit(
                "kernel", "mirror-upload", "", n=self.cap,
                dest="shard-full",
            )
        self._sdev_dirty.clear()
        return {f: self._sdev[f] for f in fields}

    def sharded_stats(self) -> dict[str, Any]:
        """Per-shard upload counters (empty lists before the first
        :meth:`sharded_device_view`); one list entry per ``workers``-
        axis shard of the engine mesh."""
        return {
            "n_shards": len(self.shard_rows_uploaded),
            "rows_uploaded": list(self.shard_rows_uploaded),
            "bytes_uploaded": list(self.shard_bytes_uploaded),
            "full_packs": list(self.shard_full_packs),
        }

    # ----------------------------------------------------------- oracle

    def verify(self) -> None:
        """Assert the incremental mirror equals the from-scratch pack
        bit-for-bit (raises :class:`MirrorParityError`).  Pending dirty
        rows are flushed first — the claim under test is that the DIRTY
        MARKING is complete, i.e. no mutation escaped the delta paths."""
        self.refresh()
        self.oracle_checks += 1
        state = self.state
        rows = oracle_fleet(state)
        try:
            live = [s for s in range(len(self.addrs)) if self.ws_of[s] is not None]
            assert len(live) == len(rows), (
                f"live slots {len(live)} != workers {len(rows)}"
            )
            for slot in live:
                ws = self.ws_of[slot]
                assert ws.idx == slot, (ws, slot, ws.idx)
                addr = self.addrs[slot]
                assert addr == ws.address, (addr, ws.address)
                expected = rows[addr]
                got = tuple(
                    getattr(self, name)[slot] for name, _ in FIELDS
                )
                for (name, _), e, g in zip(FIELDS, expected, got):
                    assert e == g and type(e) == type(g), (
                        f"{addr} slot {slot} field {name}: "
                        f"mirror={g!r} oracle={e!r}"
                    )
            for slot in self._free:
                assert self.ws_of[slot] is None and self.addrs[slot] is None, slot
        except AssertionError as e:
            self.oracle_failures += 1
            raise MirrorParityError(str(e)) from e

    def stats(self) -> dict[str, int]:
        """Counter snapshot for diagnostics, bench json and tests."""
        return {
            "generation": self.generation,
            "capacity": self.cap,
            "workers_live": int(len(self.state.workers)),
            "deltas_applied": self.deltas_applied,
            "rows_refreshed": self.rows_refreshed,
            "rows_uploaded": self.rows_uploaded,
            "bytes_uploaded": self.bytes_uploaded,
            "full_uploads": self.full_uploads,
            "membership_rebuilds": self.membership_rebuilds,
            "dirty_high_water": self.dirty_high_water,
            "oracle_checks": self.oracle_checks,
            "oracle_failures": self.oracle_failures,
            "oracle_packs": self.oracle_packs,
        }

    def __repr__(self) -> str:
        return (
            f"<SchedulerMirror cap={self.cap} live={len(self.state.workers)} "
            f"gen={self.generation} dirty={len(self._dirty)}>"
        )


@functools.cache
def _scatter_rows():
    """``arr.at[rows].set(vals)`` as one named jitted program: one
    compile per (array, row-bucket) shape, where eager indexing compiles
    a handful of primitives per shape for every field."""
    import jax

    @jax.jit
    def _scatter_rows(arr, rows, vals):
        return arr.at[rows].set(vals)

    return _scatter_rows


def _bucket(n: int, floor: int = 8) -> int:
    """Next power-of-two >= n (>= floor) — local so the mirror never
    imports the jax-backed ops modules."""
    b = floor
    while b < n:
        b <<= 1
    return b

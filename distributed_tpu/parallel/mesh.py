"""Device-mesh sharding for the placement co-processor.

Two engines are exposed here:

- ``sharded_decide_workers`` — the round-1 batched decide_worker
  (dense [B, W] cost tiles over a 2-D tasks x workers mesh);
- ``place_graph_leveled_sharded`` — a thin re-export of the SHARED
  sharded leveled engine (``ops/leveled.place_graph_leveled_sharded``):
  the one the product scheduler runs through its mesh plan path
  (scheduler/jax_placement.py), so the MULTICHIP dryrun gates the real
  code path instead of a parallel implementation.

Scales the scheduler kernels beyond one chip the TPU way (SURVEY.md §2.3
"TPU-native equivalent"): a 2-D ``jax.sharding.Mesh`` with axes

- ``"tasks"`` (data-parallel): placement-batch rows are split across
  devices — each device scores its slice of tasks;
- ``"workers"`` (model-parallel): the worker axis is split — each device
  scores tasks against its slice of workers, and the argmin is combined with
  an ``all_gather`` of per-shard (cost, nbytes, global index) triples over
  ICI.

The [B, W] cost matrix only ever exists as [B/dt, W/dw] tiles, one per
device.  Dependency edge lists are replicated (they are O(E) ints) and each
task-shard masks the edges that land in its row range — bandwidth-cheap and
keeps the segment-sum local.  ``jax.shard_map`` keeps the collectives
explicit; XLA lowers them onto ICI.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from distributed_tpu.ops.placement import WorkerArrays, PlacementBatch
from distributed_tpu.ops.partition import make_engine_mesh


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """Factor available devices into a (tasks, workers) mesh, e.g. 8 -> 4x2.

    Thin alias of :func:`ops.partition.make_engine_mesh` — one mesh
    constructor serves the dryrun, the tests and the product plan path.
    """
    return make_engine_mesh(n_devices, devices=devices)


def sharded_decide_workers(
    mesh: Mesh,
    workers: WorkerArrays,
    batch: PlacementBatch,
    bandwidth: float,
) -> jax.Array:
    """Distributed batched decide_worker (parallel mode): every task scored
    against the starting occupancy, cost tiles sharded (tasks x workers),
    argmin combined over the "workers" axis via all_gather.

    Tie-break parity with ops.placement._ordered_cost is preserved by
    combining (cost, worker_nbytes, global worker index) lexicographically.
    Returns assignment i32[B], fully replicated.
    """
    n_task_shards = mesh.shape["tasks"]
    n_worker_shards = mesh.shape["workers"]
    B = batch.duration.shape[0]
    W = workers.nworkers
    assert B % n_task_shards == 0, (B, n_task_shards)
    assert W % n_worker_shards == 0, (W, n_worker_shards)
    Bl = B // n_task_shards
    w_per_shard = W // n_worker_shards
    def kernel(nthreads, occupancy, wnbytes, running, duration, valid,
               edge_task, edge_dep, dep_bytes, has, restrict):
        # local shapes: [Wl] worker slices, [Bl] batch rows, edges replicated
        row0 = jax.lax.axis_index("tasks") * Bl
        let = edge_task - row0
        in_range = (let >= 0) & (let < Bl)
        let = jnp.clip(let, 0, Bl - 1)

        not_has = ~has[edge_dep]  # bool[E, Wl]
        contrib = jnp.where(
            in_range[:, None], dep_bytes[edge_dep][:, None] * not_has, 0.0
        )
        missing = jax.ops.segment_sum(contrib, let, num_segments=Bl)  # [Bl, Wl]

        holder = (
            jax.ops.segment_max(
                jnp.where(in_range[:, None], has[edge_dep].astype(jnp.int32), 0),
                let,
                num_segments=Bl,
            )
            > 0
        )
        holder &= running[None, :]
        # does ANY worker (across shards) hold a dep of this row?
        any_holder_local = holder.any(axis=1)
        any_holder = jax.lax.psum(
            any_holder_local.astype(jnp.int32), "workers"
        ) > 0
        cand = jnp.where(any_holder[:, None], holder, running[None, :])
        # restriction fallback parity with ops.placement.candidate_mask: if
        # the restrict set excludes every dep holder, fall back to
        # restrict & running (needs a cross-shard any)
        restricted = cand & restrict
        any_restricted = (
            jax.lax.psum(restricted.any(axis=1).astype(jnp.int32), "workers") > 0
        )
        cand = jnp.where(
            any_restricted[:, None], restricted, restrict & running[None, :]
        )
        cand &= valid[:, None]

        thr = jnp.maximum(nthreads, 1).astype(jnp.float32)
        cost = occupancy[None, :] / thr[None, :] + missing / jnp.float32(bandwidth)

        # per-shard best as (cost, nbytes, global idx), then lexicographic
        # min across the workers axis
        big = jnp.where(cand, cost, jnp.inf)
        best = big.min(axis=1, keepdims=True)
        tied = (big == best) & cand
        nb = jnp.where(tied, wnbytes[None, :], jnp.inf)
        best_nb = nb.min(axis=1, keepdims=True)
        tied2 = tied & (nb == best_nb)
        gidx = (
            jnp.arange(w_per_shard, dtype=jnp.int32)
            + jax.lax.axis_index("workers") * w_per_shard
        )
        best_idx = jnp.where(tied2, gidx[None, :], jnp.int32(2**31 - 1)).min(axis=1)

        cs = jax.lax.all_gather(best[:, 0], "workers")   # [S, Bl]
        nbs = jax.lax.all_gather(best_nb[:, 0], "workers")
        idxs = jax.lax.all_gather(best_idx, "workers")
        order = jnp.lexsort((idxs, nbs, cs), axis=0)[0]  # winner shard per row
        pick = jnp.take_along_axis(idxs, order[None, :], axis=0)[0]
        best_cost = jnp.take_along_axis(cs, order[None, :], axis=0)[0]
        pick = jnp.where(jnp.isinf(best_cost) | ~valid, -1, pick)
        # replicate across the workers axis rows already identical; gather
        # across tasks axis happens via out_specs
        return pick.astype(jnp.int32)

    restrict = batch.restrict
    if restrict is None:
        restrict = jnp.ones((B, W), bool)

    fn = jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(
            P("workers"), P("workers"), P("workers"), P("workers"),
            P("tasks"), P("tasks"),
            P(None), P(None), P(None),            # edges + dep_bytes replicated
            P(None, "workers"),                   # has: [D, W] worker-sharded
            P("tasks", "workers"),                # restrict tiles
        ),
        out_specs=P("tasks"),
        check_vma=False,
    )
    with mesh:
        return fn(
            workers.nthreads, workers.occupancy, workers.nbytes, workers.running,
            batch.duration, batch.valid, batch.edge_task, batch.edge_dep,
            batch.dep_bytes, batch.has, restrict,
        )


# ---------------------------------------------------------------------
# sharded leveled engine — the SHARED implementation lives in
# ops/leveled.py (place_graph_leveled_sharded: fused wave runs,
# per-shard H2D, psum/all_gather combine); this wrapper keeps the
# dryrun-era call shape so the MULTICHIP gate exercises exactly the
# engine the product scheduler's mesh plan path runs.
# ---------------------------------------------------------------------


def place_graph_leveled_sharded(mesh, packed, nthreads, occupancy0,
                                running, axis: str = "tasks"):
    """Run the shared sharded leveled engine over ``mesh``.

    Returns (assignment i32[T] in ORIGINAL order, load f32[W]) —
    semantics matching ops.leveled.place_graph_leveled's core outputs.
    ``axis`` is accepted for dryrun-era compatibility; the shared engine
    splits every wave over ALL mesh axes.
    """
    from distributed_tpu.ops.leveled import (
        place_graph_leveled_sharded as _engine,
    )

    res = _engine(mesh, packed, nthreads, occupancy0, running)
    return res.assignment, res.occupancy

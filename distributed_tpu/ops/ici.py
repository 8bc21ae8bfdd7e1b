"""ICI-class device data plane: mesh-native all-to-all shuffle.

The reference's UCX backend (comm/ucx.py:211) moves GPU buffers
worker-to-worker without a host copy.  The TPU-native equivalent is NOT
a socket backend: data resident on a device mesh moves between chips
over ICI via XLA collectives.  This module provides the building block —
a jitted hash-partition + ``lax.all_to_all`` exchange under
``shard_map`` — so a shuffle whose partitions already live on a mesh
never touches the host, msgpack, or TCP at all.

The same primitive is the foundation for all-to-all sequence/context
parallelism (DeepSpeed-Ulysses style: exchange sequence shards for head
shards), and ``ring_exchange`` below is the ``ppermute`` step ring
attention builds on.

Capacity contract: each (src device -> dst device) block is padded to a
static ``capacity`` (jit needs static shapes).  Callers size it with
headroom (rows are ~uniform under the hash) and MUST check the returned
counts — the TRUE counts travel with the data, so a count above
capacity means truncation, detected at both ends, never silent.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def _mix32(x: jnp.ndarray) -> jnp.ndarray:
    """Vectorized 32-bit finalizer (murmur3): deterministic, jit-safe
    without the x64 flag."""
    z = x.astype(jnp.uint32)
    z ^= z >> jnp.uint32(16)
    z *= jnp.uint32(0x85EBCA6B)
    z ^= z >> jnp.uint32(13)
    z *= jnp.uint32(0xC2B2AE35)
    z ^= z >> jnp.uint32(16)
    return z


@functools.lru_cache(maxsize=64)
def _shuffle_program(mesh: Mesh, axis: str, n_dev: int, B: int,
                     masked: bool):
    """Build + jit the exchange once per (mesh, axis, capacity): a fresh
    closure per call would defeat jit's function-identity cache and
    recompile every shuffle."""

    def local(keys_l, vals_l, *rest):
        # per-device: bucket rows by destination, pad to [n_dev, B]
        n = keys_l.shape[0]
        dest = (_mix32(keys_l) % jnp.uint32(n_dev)).astype(jnp.int32)
        if masked:
            # invalid rows (ragged-partition padding) route to the
            # discard row n_dev of the send buffer and count nowhere
            dest = jnp.where(rest[0], dest, jnp.int32(n_dev))
        order = jnp.argsort(dest)
        sdest = dest[order]
        counts = jnp.bincount(dest, length=n_dev)
        starts = jnp.concatenate(
            [jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]]
        )
        within = jnp.arange(n) - starts[jnp.minimum(sdest, n_dev - 1)]
        # truncated rows are reported via counts; masked rows (sdest ==
        # n_dev) always land in the discard row
        in_cap = (within < B) & (sdest < jnp.int32(n_dev))
        dst_rows = jnp.where(in_cap, sdest, n_dev)
        dst_cols = jnp.where(in_cap, within, 0)
        send_k = jnp.zeros((n_dev + 1, B), keys_l.dtype)
        send_k = send_k.at[dst_rows, dst_cols].set(keys_l[order])[:n_dev]
        send_v = jnp.zeros((n_dev + 1, B) + vals_l.shape[1:], vals_l.dtype)
        send_v = send_v.at[dst_rows, dst_cols].set(vals_l[order])[:n_dev]

        # the ICI exchange: block i of this device goes to device i.
        # TRUE counts travel too (not clamped): a receiver seeing
        # count > capacity knows that block was truncated
        recv_k = lax.all_to_all(send_k, axis, 0, 0, tiled=False)
        recv_v = lax.all_to_all(send_v, axis, 0, 0, tiled=False)
        recv_c = lax.all_to_all(counts[:, None], axis, 0, 0, tiled=False)[:, 0]
        sent_c = counts  # pre-exchange view, for detection at the source
        return recv_k, recv_v, recv_c, sent_c

    in_specs = (P(axis), P(axis)) + ((P(axis),) if masked else ())
    shard = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(axis), P(axis), P(axis), P(axis)),
        check_vma=False,
    )
    return jax.jit(shard)


def shuffle_on_mesh(
    mesh: Mesh,
    keys: Any,
    values: Any,
    axis: str = "shuffle",
    capacity: int | None = None,
    valid: Any = None,
):
    """Device-native hash shuffle: row (k, v) moves to device
    ``hash(k) % n_devices`` entirely over the mesh interconnect.

    keys: int array [N] sharded over ``axis``; values: [N, ...] sharded
    the same way.  Returns ``(keys_out, values_out, counts, sent)``:
    per-device ``[n_dev, capacity]`` receive buffers (flattened over the
    mesh axis) plus the TRUE per-block counts on both ends — mask valid
    rows with ``min(count, capacity)``; a count above capacity means
    that block was truncated.

    ``valid``: optional bool [N] sharded like keys — False rows (the
    padding of ragged partitions) are dropped instead of exchanged.
    """
    n_dev = mesh.shape[axis]
    n_local = keys.shape[0] // n_dev
    if capacity is None:
        # 2x headroom over the uniform expectation, at least 16
        capacity = max(16, (2 * n_local + n_dev - 1) // n_dev)
    prog = _shuffle_program(mesh, axis, n_dev, int(capacity), valid is not None)
    if valid is not None:
        return prog(keys, values, valid)
    return prog(keys, values)


def compact_shuffle_output(keys_out, values_out, counts, n_dev: int):
    """Host-side helper: strip padding from the receive buffers; returns
    per-destination-device (keys, values) pairs (tests / host consumers;
    on-device consumers use the counts as a mask directly).

    Enforces the capacity contract: a true count above the buffer
    capacity means that block was truncated on the wire — raises rather
    than silently returning short partitions."""
    keys_out = np.asarray(keys_out)
    values_out = np.asarray(values_out)
    counts = np.asarray(counts).reshape(n_dev, n_dev)
    B = keys_out.shape[1]
    if (counts > B).any():
        over = np.argwhere(counts > B)[0]
        raise ValueError(
            f"shuffle block truncated: count {counts[tuple(over)]} > "
            f"capacity {B} for (dst, src)={tuple(over)}; re-run "
            f"shuffle_on_mesh with capacity >= {int(counts.max())}"
        )
    keys_out = keys_out.reshape(n_dev, n_dev, B)
    values_out = values_out.reshape(n_dev, n_dev, B, *values_out.shape[2:])
    out = []
    for d in range(n_dev):
        kparts, vparts = [], []
        for src in range(n_dev):
            c = int(counts[d, src])
            kparts.append(keys_out[d, src, :c])
            vparts.append(values_out[d, src, :c])
        out.append((np.concatenate(kparts), np.concatenate(vparts)))
    return out


@functools.lru_cache(maxsize=64)
def _ring_program(mesh: Mesh, axis: str, shift: int):
    n_dev = mesh.shape[axis]
    perm = [(i, (i + shift) % n_dev) for i in range(n_dev)]

    def local(x_l):
        return lax.ppermute(x_l, axis, perm)

    shard = jax.shard_map(
        local, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
        check_vma=False,
    )
    return jax.jit(shard)


def ring_exchange(mesh: Mesh, x: Any, axis: str = "shuffle", shift: int = 1):
    """One ring step: every device hands its shard to its neighbor
    (``ppermute``) — the primitive ring attention iterates to stream
    KV blocks around the mesh without host involvement."""
    return _ring_program(mesh, axis, shift)(x)


def make_mesh_1d(n: int | None = None, axis: str = "shuffle") -> Mesh:
    devs = jax.devices()
    n = n or len(devs)
    if n > len(devs):
        raise ValueError(
            f"requested a {n}-device mesh but only {len(devs)} devices "
            f"are available"
        )
    return Mesh(np.asarray(devs[:n]), (axis,))

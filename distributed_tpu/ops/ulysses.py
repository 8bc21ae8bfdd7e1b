"""All-to-all (Ulysses-style) sequence parallelism.

The second context-parallel strategy next to ``ops.ring_attention``:
instead of rotating K/V around a ring, ONE ``lax.all_to_all`` re-shards
the activations from sequence-sharded to head-sharded, every device runs
plain full attention over the whole sequence for its heads, and a second
all-to-all restores sequence sharding (DeepSpeed-Ulysses; public pattern,
see PAPERS.md).  Two collectives total — cheaper than the ring's n_dev
hops when heads >= devices and the sequence fits per-device once the
head dimension is split; the ring wins when even one head's full
sequence is too large.  Both ride ICI under one jitted program.

Local attention is the same online-softmax math; for long sequences the
per-head block can run through the pallas flash kernel (ops/flash.py).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from distributed_tpu.ops.ring_attention import reference_attention


def _local_attention(q, k, v, causal: bool, scale: float):
    """Full-sequence attention for this device's head group: the flash
    kernel when the sequence divides by its blocks (O(block) memory —
    the long-context regime this module exists for), else the plain
    O(N^2) einsum for small/ragged shapes.  Shapes are static under
    jit, so this branch resolves at trace time."""
    n = q.shape[0]
    if n % min(128, n) == 0:
        from distributed_tpu.ops.flash import flash_attention

        return flash_attention(q, k, v, causal=causal, scale=scale)
    return reference_attention(q, k, v, causal=causal, scale=scale)


@functools.lru_cache(maxsize=32)
def _ulysses_program(mesh: Mesh, axis: str, causal: bool, scale: float):
    n_dev = mesh.shape[axis]

    def local(ql, kl, vl):
        # [n_local, H, D] seq-sharded -> [N, H/n_dev, D] head-sharded:
        # split the head axis into n_dev groups and exchange, so each
        # device receives ALL sequence positions for its head group
        def seq_to_heads(x):
            n_local, h, d = x.shape
            hg = h // n_dev
            x = x.reshape(n_local, n_dev, hg, d)
            # tiled: the split axis shrinks n_dev->1, sequence chunks
            # from every device concatenate on axis 0
            x = lax.all_to_all(
                x, axis, split_axis=1, concat_axis=0, tiled=True
            )  # [N, 1, hg, d]
            return x.reshape(n_local * n_dev, hg, d)

        def heads_to_seq(x):
            # inverse exchange: heads come back, sequence re-shards
            n, hg, d = x.shape
            x = x.reshape(n_dev, n // n_dev, hg, d)
            x = lax.all_to_all(
                x, axis, split_axis=0, concat_axis=2, tiled=True
            )  # [1, n_local, hg*n_dev, d]
            return x.reshape(n // n_dev, hg * n_dev, d)

        q = seq_to_heads(ql)
        k = seq_to_heads(kl)
        v = seq_to_heads(vl)
        out = _local_attention(q, k, v, causal, scale)
        return heads_to_seq(out)

    shard = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )
    return jax.jit(shard)


def ulysses_attention(
    mesh: Mesh,
    q: Any,
    k: Any,
    v: Any,
    axis: str = "sp",
    causal: bool = False,
    scale: float | None = None,
):
    """Exact attention with the sequence sharded over ``mesh[axis]`` via
    two all-to-alls (sequence<->head re-sharding).

    q, k, v: ``[seq, heads, dim]``; ``heads`` must divide by the axis
    size (each device owns ``heads/n_dev`` full-sequence heads in the
    middle phase).  Returns ``[seq, heads, dim]`` sharded like the input.
    """
    n_dev = mesh.shape[axis]
    if q.shape[1] % n_dev:
        raise ValueError(
            f"heads ({q.shape[1]}) must divide by the mesh axis ({n_dev}); "
            f"use ring_attention for head counts below the device count"
        )
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    return _ulysses_program(mesh, axis, bool(causal), float(scale))(q, k, v)

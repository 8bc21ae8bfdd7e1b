"""Ring attention: exact attention over sequences sharded across a mesh.

Long-context is first-class in this framework: a sequence too long for
one chip's HBM lives sharded over the mesh's sequence axis, and
attention runs as a ring — each device keeps its Q shard resident while
K/V shards rotate neighbor-to-neighbor over ICI (``lax.ppermute``, the
``ops.ici.ring_exchange`` primitive), accumulating exact softmax
attention with the online (flash) recurrence.  Communication overlaps
compute by construction: every step is one local block-attention plus
one neighbor hop, and XLA pipelines the ppermute with the einsums.

The recurrence keeps, per query row, the running max ``m``, the running
sum-of-exponentials ``l``, and the UNNORMALIZED accumulator
``acc = sum(exp(s - m) @ v)``; merging a new block rescales by
``exp(m_old - m_new)``.  This is the standard flash/ring-attention
math (Liu et al. ring attention; Dao et al. flash attention), laid out
mesh-first rather than kernel-first.

No reference counterpart: the reference framework (dask/distributed)
has no attention/sequence-parallel layer (SURVEY §5.7); this module is
the TPU-native capability the survey calls out as the structural
analogue of its all-to-all shuffle, built on the same mesh primitives.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

_NEG = -1e30  # finite "-inf": keeps exp() NaN-free for fully-masked rows


def _block_attn(q, k, v, m, l, acc, qoff, koff, scale, causal):
    """One online-softmax step: fold K/V block (koff) into the carry.

    q: [nq, H, D]; k, v: [nk, H, D]; m, l: [H, nq]; acc: [nq, H, D].
    """
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale  # [H, nq, nk]
    if causal:
        qpos = qoff + jnp.arange(q.shape[0])
        kpos = koff + jnp.arange(k.shape[0])
        mask = qpos[:, None] >= kpos[None, :]  # [nq, nk]
        s = jnp.where(mask[None], s, _NEG)
    m_new = jnp.maximum(m, s.max(axis=-1))  # [H, nq]
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[:, :, None])  # [H, nq, nk]
    l_new = l * alpha + p.sum(axis=-1)
    acc_new = acc * alpha.T[:, :, None] + jnp.einsum("hqk,khd->qhd", p, v)
    return m_new, l_new, acc_new


@functools.lru_cache(maxsize=32)
def _ring_program(mesh: Mesh, axis: str, causal: bool, scale: float):
    n_dev = mesh.shape[axis]
    fwd = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def local(ql, kl, vl):
        nq = ql.shape[0]
        H = ql.shape[1]
        idx = lax.axis_index(axis)
        qoff = idx * nq

        def step(carry, i):
            k, v, m, l, acc = carry
            # after i hops this device holds the block that started on
            # device (idx - i): global positions follow the owner
            koff = ((idx - i) % n_dev) * k.shape[0]
            m, l, acc = _block_attn(
                ql, k, v, m, l, acc, qoff, koff, scale, causal
            )
            k = lax.ppermute(k, axis, fwd)
            v = lax.ppermute(v, axis, fwd)
            return (k, v, m, l, acc), None

        m0 = jnp.full((H, nq), _NEG, jnp.float32)
        l0 = jnp.zeros((H, nq), jnp.float32)
        acc0 = jnp.zeros(ql.shape, jnp.float32)
        (k, v, m, l, acc), _ = lax.scan(
            step, (kl, vl, m0, l0, acc0), jnp.arange(n_dev)
        )
        out = acc / jnp.maximum(l, 1e-30).T[:, :, None]
        return out.astype(ql.dtype)

    shard = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )
    return jax.jit(shard)


def ring_attention(
    mesh: Mesh,
    q: Any,
    k: Any,
    v: Any,
    axis: str = "sp",
    causal: bool = False,
    scale: float | None = None,
):
    """Exact multi-head attention with the sequence sharded over
    ``mesh[axis]``.

    q, k, v: ``[seq, heads, dim]``, seq divisible by the axis size.
    Returns ``[seq, heads, dim]`` sharded the same way.  K/V shards
    rotate around the ring; peak per-device memory is
    ``O(seq/n_dev)`` — sequences any single chip could never hold.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    return _ring_program(mesh, axis, bool(causal), float(scale))(q, k, v)


def reference_attention(q, k, v, causal=False, scale=None):
    """O(N^2)-memory single-device oracle for tests."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    if causal:
        n, nk = q.shape[0], k.shape[0]
        mask = jnp.arange(n)[:, None] >= jnp.arange(nk)[None, :]
        s = jnp.where(mask[None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v).astype(q.dtype)

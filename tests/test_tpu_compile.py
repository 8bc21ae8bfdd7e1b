"""The co-processor's main-path kernels, compiled for a described (not
attached) TPU v5e at the widths users run.

This is the only file that compiles for the TPU.  Nothing runs: a test
passes when the chip's compiler accepts the program and the program's
own memory fits one chip's 16 GiB.  The topology is described inside a
module fixture (never at import), because only one process at a time may
hold the TPU library and every xdist worker imports every test file.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

#: one v5e chip's device memory
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip can be written to the persistent
    # cache but never read back without the chip: keep it out
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2"
                )
            except Exception as e:  # noqa: BLE001 - any failure means "cannot"
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def dag_1m_shapes():
    """(Tp, Lp, runs) of bench.py's dag_1m graph as the streamed driver
    plans it: padded task rows, padded wave count, fused (F, K) runs."""
    import bench
    from distributed_tpu.ops.leveled import (
        _bucket,
        _compute_pad,
        _plan_runs,
        pack_graph,
    )

    packed = pack_graph(*bench.build_graph(np.random.default_rng(0)),
                        bandwidth=bench.BANDWIDTH)
    runs = _plan_runs(packed.offsets)
    Tp = packed.n + _compute_pad(packed.n, runs, packed.offsets)
    Lp = _bucket(packed.n_levels + 1, floor=64)
    return Tp, Lp, [(F, _bucket(len(w), floor=1)) for F, w in runs]


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled) -> None:
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used <= HBM_BYTES, used


def _place_run_args(Tp, Lp, K, W, sharding):
    f16 = lambda n: _spec((n,), jnp.float16, sharding)  # noqa: E731
    i32 = lambda n: _spec((n,), jnp.int32, sharding)  # noqa: E731
    return (
        f16(Tp), i32(Tp), i32(Tp), f16(Tp), f16(Tp), f16(Tp),
        i32(Tp), i32(Tp), _spec((W,), jnp.float32, sharding),
        _spec((Lp,), jnp.float32, sharding),
        i32(K), i32(K), i32(K),
        i32(W), _spec((W,), jnp.bool_, sharding),
        _spec((W,), jnp.float32, sharding),
    )


@pytest.mark.parametrize("which", ["largest", "fused-small"])
def test_place_run_dag_1m(one_chip, dag_1m_shapes, which):
    """The leveled engine's wave program at dag_1m's largest wave bucket
    and at its fused small-wave shape, on 512 workers."""
    from distributed_tpu.ops.leveled import SMALL_WAVE, _place_run

    Tp, Lp, runs = dag_1m_shapes
    if which == "largest":
        F, K = max(runs)
    else:
        F, K = max((r for r in runs if r[0] == SMALL_WAVE),
                   key=lambda r: r[1])
    args = _place_run_args(Tp, Lp, K, 512, one_chip)
    compiled = _place_run.lower(
        *args, F=F, K=K, uniform=True, fmt="f16"
    ).compile()
    _fits(compiled)


def test_steal_rounds_512_workers(one_chip):
    from distributed_tpu.ops.stealing import _steal_rounds
    from distributed_tpu.scheduler.stealing import WorkStealing

    T, W = WorkStealing.DEVICE_MAX_TASKS, 512
    args = (
        _spec((T,), jnp.int32, one_chip), _spec((T,), jnp.int32, one_chip),
        _spec((T,), jnp.float32, one_chip), _spec((T,), jnp.float32, one_chip),
        _spec((W,), jnp.float32, one_chip), _spec((W,), jnp.int32, one_chip),
        _spec((W,), jnp.bool_, one_chip), _spec((W,), jnp.bool_, one_chip),
    )
    _fits(_steal_rounds.lower(*args, K=8).compile())


def test_drop_rounds_512_workers(one_chip):
    from distributed_tpu.ops.amm import MAX_ROWS, _drop_rounds

    R, W = MAX_ROWS, 512
    args = (
        _spec((R, W), jnp.bool_, one_chip), _spec((R, W), jnp.bool_, one_chip),
        _spec((R,), jnp.float32, one_chip), _spec((R,), jnp.int32, one_chip),
        _spec((W,), jnp.float32, one_chip), _spec((), jnp.int32, one_chip),
    )
    _fits(_drop_rounds.lower(*args).compile())


def test_partition_kernel_dense_limit(one_chip):
    """The live planner's partitioner at the largest dense score matrix
    it accepts: a pow2 task bucket times worker-thread lanes within
    DENSE_LIMIT."""
    from distributed_tpu.ops.partition import DENSE_LIMIT, partition_kernel

    T = 131072
    lanes = DENSE_LIMIT // T // 2 * 2  # two threads per worker
    E = 2 * T
    args = (
        _spec((T,), jnp.float32, one_chip), _spec((E,), jnp.float32, one_chip),
        _spec((E,), jnp.int32, one_chip), _spec((E,), jnp.int32, one_chip),
        _spec((T,), jnp.int32, one_chip),
    )
    assert T * lanes <= DENSE_LIMIT
    _fits(partition_kernel.lower(*args, n_workers=lanes).compile())


def test_sharded_run_fn_four_chips(topo):
    """The sharded engine's fused-run program over a 2x2 engine mesh of
    the described chips, at dag_10m's widths: 4096 mirror-sharded
    workers and a 10M-row replicated carry."""
    from distributed_tpu.ops.leveled import _sharded_run_fn
    from distributed_tpu.ops.partition import make_engine_mesh

    mesh = make_engine_mesh(4, devices=list(topo.devices))
    names = tuple(mesh.axis_names)
    Tp, Lp, W, Fl, K = 10_500_000, 64, 4096, 1 << 20, 1
    F = Fl * 4
    tile = NamedSharding(mesh, P(None, names))
    rep = NamedSharding(mesh, P(None))
    fleet = NamedSharding(mesh, P("workers"))
    args = (
        _spec((K, F), jnp.float16, tile), _spec((K, F), jnp.int32, tile),
        _spec((K, F), jnp.int32, tile), _spec((K, F), jnp.float16, tile),
        _spec((K, F), jnp.float16, tile), _spec((K, F), jnp.float16, tile),
        _spec((Tp,), jnp.int32, rep), _spec((Tp,), jnp.int32, rep),
        _spec((W,), jnp.float32, rep), _spec((Lp,), jnp.float32, rep),
        _spec((K,), jnp.int32, rep), _spec((K,), jnp.int32, rep),
        _spec((K,), jnp.int32, rep),
        _spec((W,), jnp.int32, fleet), _spec((W,), jnp.bool_, fleet),
        _spec((W,), jnp.float32, fleet),
    )
    fn = _sharded_run_fn(mesh, Fl, K, W, True, True)
    compiled = fn.lower(*args).compile()
    _fits(compiled)
    text = compiled.as_text()
    assert "all-gather" in text or "all-reduce" in text

"""chip_smoke.py's phases on the CPU at tiny sizes (the chip runs them at
full size), and its refusal to report anything without a TPU."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke
from distributed_tpu import config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _graph(n_tasks: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    durations = rng.uniform(0.01, 1.0, n_tasks).astype(np.float32)
    out_bytes = rng.uniform(1e3, 1e7, n_tasks).astype(np.float32)
    n_deps = rng.integers(0, 3, n_tasks)
    n_deps[0] = 0
    dst = np.repeat(np.arange(n_tasks), n_deps).astype(np.int32)
    src = (rng.random(len(dst)) * np.maximum(dst, 1)).astype(np.int32)
    return durations, out_bytes, src, dst


def test_phase_whole_graph_tiny():
    out = chip_smoke.phase_whole_graph(_graph(20_000), 16, min_stream=1)
    assert out["assigned"] == 20_000
    assert out["fmt"] == "f16"
    # the "device" here is the CPU, so the comparison is with itself
    assert out["agreement"] == 1.0
    assert out["compiles_timed"] == 0


def test_phase_live_cluster_tiny(monkeypatch):
    """8 workers instead of 64: the periodic floor and the device
    minimums come down with the size, so every device path the chip run
    requires still runs."""
    from distributed_tpu.scheduler.amm import ReduceReplicas
    from distributed_tpu.scheduler.stealing import WorkStealing

    monkeypatch.setattr(WorkStealing, "DEVICE_MIN_TASKS", 1)
    monkeypatch.setattr(ReduceReplicas, "DEVICE_MIN_TASKS", 1)
    with config.set({"scheduler.jax.periodic-min-workers": 8}):
        out = chip_smoke.phase_live_cluster(
            n_workers=8, n_tasks=3000, n_replicate=32, timeout=120
        )
    assert out["plans_computed"] >= 1
    assert out["mirror_platforms"] == ["cpu"]
    assert out["steal_cycles_device"] >= 1
    assert out["amm_drop_plans"] >= 1
    assert out["precompiled"] > 0
    assert out["second_compiles"] == 0


@pytest.mark.parametrize("n_tasks,n_workers", [(20_000, 64), (1_000_000, 512)])
def test_phase_four_chips(n_tasks, n_workers):
    """The sharded engine places exactly as the single-device one.  At
    1M tasks / 512 workers a psum of per-shard wave-load partials
    re-associated the f32 sums and moved 2 tasks on this 4-device CPU
    mesh (10M / 4096 on four v5e chips: 37 % of the tasks)."""
    out = chip_smoke.phase_four_chips(
        _graph(n_tasks), n_workers, n_devices=4, shuffle_rows=4096,
        min_stream=1,
    )
    assert out["mesh"] == "2x2"
    assert len(out["shards"]) == 4
    assert out["agreement"] == 1.0


@pytest.mark.parametrize("change", ["one-off", "piled"])
def test_parity_rejects_any_difference(change):
    from distributed_tpu.ops.leveled import LeveledResult

    n = 1000
    ref = LeveledResult(
        assignment=np.arange(n, dtype=np.int32) % 10,
        start_time=np.zeros(n, np.float32),
        occupancy=np.ones(10, np.float32),
        n_waves=1, level=np.zeros(n, np.int32),
        choice=np.full(n, 2, np.int8),
    )
    assert chip_smoke.parity(ref, ref, 10)["agreement"] == 1.0
    other = ref.assignment.copy()
    if change == "one-off":
        other[5] = (other[5] + 1) % 10
    else:
        other[:] = 0
    with pytest.raises(AssertionError, match="differ"):
        chip_smoke.parity(ref, ref._replace(assignment=other), 10)


def test_main_refuses_a_non_tpu_platform(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_fails_alone_in_a_directory(tmp_path):
    """Without the rest of the repo beside it, the script fails and
    prints no result line."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert not any(
        line.startswith("{") and json.loads(line).get("ok")
        for line in proc.stdout.splitlines()
    )


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_directory(tmp_path, env_dir):
    """Programs land in JAX_COMPILATION_CACHE_DIR when it is set, and in
    <checkout>/.jax_cache otherwise (the min compile time is lowered in
    the child only so that a tiny CPU program is written at all)."""
    from distributed_tpu.ops.compile_cache import DEFAULT_DIR

    assert DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
    code = (
        "import jax, jax.numpy as jnp\n"
        "from distributed_tpu.ops.compile_cache import enable_compile_cache\n"
        "d = enable_compile_cache()\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()\n"
        "print(d)\n"
    )
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    proc = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    used = proc.stdout.strip().splitlines()[-1]
    assert used == (str(tmp_path) if env_dir else DEFAULT_DIR)
    assert os.listdir(used)


def test_precompile_covers_every_live_shape():
    """Once the planner's precompile job for a fleet capacity is done,
    the mirror's dirty-row sync, the steal kernel and the AMM kernel
    compile nothing, whatever the load up to their bounds."""
    from distributed_tpu.ops.amm import DropBatch, plan_drops
    from distributed_tpu.ops.stealing import StealBatch, plan_steals
    from distributed_tpu.scheduler.jax_placement import JaxPlacement
    from distributed_tpu.scheduler.state import SchedulerState
    from distributed_tpu.scheduler.stealing import WorkStealing

    with config.set({"scheduler.jax.periodic-min-workers": 8}):
        placement = JaxPlacement(min_workers=0)
        state = SchedulerState(placement=placement)
        workers = [
            state.add_worker_state(f"tcp://w:{i}", nthreads=2,
                                   memory_limit=2**30)
            for i in range(8)
        ]
    try:
        assert placement.precompiled.result(timeout=300) > 0
        mirror = state.mirror
        W = mirror.cap
        rng = np.random.default_rng(0)
        with chip_smoke.CompileCounter() as counter:
            dv = mirror.device_view(WorkStealing.DEVICE_FIELDS)
            for k in (1, 3, 8):
                for ws in workers[:k]:
                    mirror.mark(ws)
                dv = mirror.device_view(WorkStealing.DEVICE_FIELDS)
            for T in (1, 100, WorkStealing.DEVICE_MAX_TASKS):
                plan_steals(StealBatch(
                    rng.integers(0, W, T).astype(np.int32),
                    np.arange(T, dtype=np.int32),
                    np.full(T, 0.01, np.float32),
                    np.ones(T, np.float32),
                    dv["occupancy"], dv["nthreads"], dv["idle"],
                    dv["running"],
                ))
            for R, K in ((1, 1), (100, 3), (5000, 2)):
                holders = rng.random((R, W)) < 0.5
                holders[:, 0] = True
                plan_drops(DropBatch(
                    holders, np.zeros((R, W), bool),
                    np.ones(R, np.float32), np.full(R, K, np.int32),
                    np.ones(W, np.float32),
                ))
        assert counter.n == 0, counter.log
    finally:
        placement.close()

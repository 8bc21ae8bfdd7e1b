"""Test harness configuration.

Tests run JAX on a virtual 8-device CPU mesh (multi-chip shardings are
validated without TPU hardware, like the reference validates multi-node
behavior with in-process clusters, utils_test.py:865).  Must run before any
jax import.
"""

import os

# Tests run on the CPU, whatever the host has attached: the TPU path is
# covered by tests/test_tpu_compile.py (compiled for a described v5e, not
# run) and by chip_smoke.py on the chip.  jax.config.update works as long
# as no backend is initialized yet; jax_num_cpu_devices gives the virtual
# 8-device mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import asyncio  # noqa: E402
import functools  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


def gen_test(timeout: float = 120):
    """Run an async test on a fresh event loop (reference utils_test.py:708)."""

    def decorator(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            async def run():
                return await asyncio.wait_for(fn(*args, **kwargs), timeout)

            return asyncio.run(run())

        return wrapper

    return decorator



# ------------------------------------------------ hashseed sweep harness
#
# Cross-process determinism (docs/determinism.md) is proven empirically
# by re-running the same work in subprocesses under several
# PYTHONHASHSEEDs and demanding bit-identical results.  Every hashseed
# test in the suite goes through these two helpers so the seed list and
# the failure report stay uniform.

import subprocess  # noqa: E402
import sys  # noqa: E402

#: the default sweep: three seeds, none of them the hash-randomization
#: default, chosen to have caught real bugs historically (1 and 6/7)
HASHSEEDS = ("1", "7", "13")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sweep_hashseed_pytest(node: str, seeds=HASHSEEDS, timeout: float = 240):
    """Run one pytest node in a subprocess per hash seed; each must pass.

    For scenario tests that assert their own determinism internally
    (digest equality between twin runs) — the sweep proves the property
    holds whatever allocation/hash layout the interpreter starts with.
    """
    for seed in seeds:
        env = dict(os.environ, PYTHONHASHSEED=str(seed),
                   JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, "-m", "pytest", node, "-q",
             "-p", "no:randomly", "-p", "no:cacheprovider"],
            capture_output=True, timeout=timeout, env=env, cwd=_REPO_ROOT,
        )
        assert r.returncode == 0, (
            f"PYTHONHASHSEED={seed}: " + r.stdout.decode()[-1500:]
        )


def sweep_hashseed_stdout(code: str, seeds=HASHSEEDS,
                          timeout: float = 240) -> str:
    """Run ``python -c code`` once per hash seed; stdout must be
    bit-identical across the sweep.  Returns the common output so the
    caller can pin further expectations on it."""
    outs: dict[str, str] = {}
    for seed in seeds:
        env = dict(os.environ, PYTHONHASHSEED=str(seed),
                   JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True,
            timeout=timeout, env=env, cwd=_REPO_ROOT,
        )
        assert r.returncode == 0, (
            f"PYTHONHASHSEED={seed}: " + r.stderr.decode()[-1500:]
        )
        outs[seed] = r.stdout.decode()
    distinct = set(outs.values())
    assert len(distinct) == 1, (
        "output diverged across hash seeds:\n"
        + "\n".join(f"--- seed {s} ---\n{o}" for s, o in outs.items())
    )
    return outs[next(iter(outs))]

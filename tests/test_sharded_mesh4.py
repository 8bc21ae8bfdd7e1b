"""The scheduler's four-chip placement path on exactly four devices: a
mirror-backed ``SchedulerState`` fleet placed through
``place_graph_streamed`` over ``make_engine_mesh(4)`` (2x2), as
``JaxPlacement._plan_from_arrays`` calls it on a v5e 2x2 host, against
the benchmark's plain numpy reference (``benchmark/reference.py``).

Each case runs in a child process that sees four virtual CPU devices,
the device count of the host it stands for (the test process has
eight)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = r"""
import json, sys

import jax

from benchmark import graphs, reference
from distributed_tpu.ops.leveled import _plan_streamed, place_graph_streamed
from distributed_tpu.ops.partition import make_engine_mesh
from distributed_tpu.scheduler.state import SchedulerState

shape, T, W = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
with open(f"benchmark/traffic/{shape}.json") as f:
    traffic = json.load(f)
g = graphs.make_graph(traffic, T, 2**31 + 11, 0)
state = SchedulerState()
for i in range(W):
    state.add_worker_state(f"tcp://w:{i}", nthreads=2)
fv = state.mirror.fleet_view()
mesh = make_engine_mesh(4)
fleet_dev = state.mirror.sharded_device_view(mesh)
stats, tm = {}, {}
packed, res = place_graph_streamed(
    *g, fv.nthreads.copy(), fv.occupancy.copy(), fv.running.copy(),
    bandwidth=100e6, latency=0.001, mesh=mesh, fleet_dev=fleet_dev,
    stats=stats, timings=tm, min_stream=1,
)
want = reference.place(*g, W, 2, 100e6, 0.001)[0]
print(json.dumps({
    "devices": len(jax.devices()),
    "mesh": dict(mesh.shape),
    "misplaced": int((res.assignment != want).sum()),
    "n_shards": stats["n_shards"],
    "runs": stats["runs"],
    "planned_runs": len(_plan_streamed(packed.offsets, mesh)[1]),
    "fleet_devices": sorted(
        len({s.device for s in a.addressable_shards})
        for a in fleet_dev.values()),
    "timings": {k: v for k, v in tm.items() if k.endswith("_s")},
    "tile_threads": tm["tile_threads"],
}))
"""


@pytest.mark.parametrize("shape", ["random", "stencil"])
def test_mesh4_placement_equals_the_reference(shape):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "-c", CHILD, shape, "20000", "64"], cwd=REPO,
        env=env, capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    assert out["mesh"] == {"tasks": 2, "workers": 2}
    assert out["misplaced"] == 0
    assert out["n_shards"] == 4
    assert out["runs"] == out["planned_runs"] > 0
    # every fleet array of the mirror is split over all four devices
    assert out["fleet_devices"] == [4, 4, 4]
    tm = out["timings"]
    assert tm["tiles_s"] > 0 and tm["dispatch_s"] > 0
    assert out["tile_threads"] >= 1

"""The plain references and the controls of ``correct`` at a size a test
run holds: the program equals the reference, and every control fails
the comparison (benchmark/control.py runs the same at full size on the
chip)."""

from __future__ import annotations

import numpy as np
import pytest

from benchmark import control, graphs, reference, run


@pytest.mark.parametrize("seed", [0, 2**31 + 11])
@pytest.mark.parametrize("shape", ["random", "stencil"])
def test_reference_equals_the_program(shape, seed):
    from distributed_tpu.ops.leveled import place_graph_streamed

    traffic = run.load_json("benchmark", "traffic", f"{shape}.json")
    g = graphs.make_graph(traffic, 30_000, seed, 0)
    W = 24
    fleet = (np.full(W, 2, np.int32), np.zeros(W, np.float32),
             np.ones(W, bool))
    packed, res = place_graph_streamed(*g, *fleet, bandwidth=1e8,
                                       latency=0.001, min_stream=1)
    want, level = reference.place(*g, W, 2, 1e8, 0.001)
    assert (level == res.level).all()
    assert (want == res.assignment).all()


@pytest.mark.parametrize("cell", ["dag_1m_512w.random",
                                  "dag_1m_512w.stencil"])
def test_placement_control_fails(cell):
    spec = run.resolve(cell, rehearse=True)
    for seed in (1, 2, 3):
        out = control.placement_readings(spec["config"], spec["traffic"],
                                         seed)
        assert out["misplaced_tasks"] == 0
        assert out["control_misplaced_tasks"] > 0


@pytest.mark.parametrize("cell", ["live_64w.dag", "live_64w.flood"])
def test_live_control_fails(cell):
    import jax

    spec = run.resolve(cell, rehearse=True)
    driver = run.load_module("drivers", spec["entry"])
    out = control.live_readings(driver, spec["config"], spec["traffic"], 3,
                                1.0, jax.devices()[:1])
    assert out["wrong_results"] == 0
    assert out["control_wrong_results"] > 0

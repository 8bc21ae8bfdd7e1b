"""The four-chip cell ``dag_10m_4096w.mesh``: its comparison fails when
an assignment is altered, its driver stops rather than place on any
path but the sharded engine, and its readers read the mesh's spans and
programs (and nothing, on a program that has none of them).  Runs are
driven in-process past the harness's look for a chip, on four of the
test process's CPU devices."""

from __future__ import annotations

import json

import pytest

from benchmark import run
from distributed_tpu.ops import leveled
from distributed_tpu.scheduler.mirror import SchedulerMirror

CELL = "dag_10m_4096w.mesh"


@pytest.fixture
def devices(monkeypatch):
    """The devices the run is handed: four, unless a test cuts them."""
    import jax

    given = {"n": 4}
    monkeypatch.setattr(run, "start_jax",
                        lambda chips, rehearse: jax.devices()[:given["n"]])
    return given


def _run(capsys) -> dict:
    run.main(["--workload", CELL, "--seed", str(2**31 + 5), "--seconds",
              "1", "--trace", "0", "--rehearse"])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _alter_one(finalize):
    def broken(*a, **kw):
        res = finalize(*a, **kw)
        a = res.assignment.copy()
        a[len(a) // 3] = (a[len(a) // 3] + 1) % (a.max() + 1)
        return res._replace(assignment=a)

    return broken


@pytest.mark.parametrize("fault", ["answer_altered"])
def test_mesh_fault_is_not_correct(fault, devices, monkeypatch, capsys):
    monkeypatch.setattr(leveled, "_finalize", _alter_one(leveled._finalize))
    out = _run(capsys)
    assert out["device"]["count"] == 4
    assert out["correct"] is False
    assert out["checks"]["misplaced_tasks"]["value"] > 0


def _single_device(place):
    def fallback(*a, mesh=None, fleet_dev=None, stats=None, **kw):
        return place(*a, **kw)

    return fallback


@pytest.mark.parametrize("fault", ["fewer_devices", "no_sharded_view",
                                   "single_device_path"])
def test_mesh_driver_refuses(fault, devices, monkeypatch, capsys):
    if fault == "fewer_devices":
        devices["n"] = 2
    elif fault == "no_sharded_view":
        monkeypatch.setattr(SchedulerMirror, "sharded_device_view",
                            lambda self, mesh, fields=(): None)
    else:
        monkeypatch.setattr(leveled, "place_graph_streamed",
                            _single_device(leveled.place_graph_streamed))
    with pytest.raises(RuntimeError, match="mesh|sharded"):
        run.main(["--workload", CELL, "--seed", "1", "--seconds", "1",
                  "--trace", "0", "--rehearse"])
    assert not [ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("{")]


PEAKS = run.load_json("benchmark", "peaks.json")["devices"]["TPU v5 lite"]


def _measured(programs_s, **kw):
    trace = {"window_s": 4.0, "busy_s": 2.0, "programs_s": programs_s,
             "collective_s": 0.5, "breakdown": {}}
    return run.Measurements(trace=trace, traced_ops=2, shards=4,
                            bytes_per_op=10e6 * 28 + 40 * 4096 * 8,
                            tasks_per_op=10_000_000, peaks=PEAKS, **kw)


def _read(name, m):
    return run.load_module("metrics", name).read(m)


def test_mesh_readers():
    m = _measured({"_place_run_sharded": 3.0, "_shrink_window": 0.01},
                  tiles_s=[0.1, 0.3], topo_s=[0.4, 0.4])
    assert _read("tiles_ms", m) == pytest.approx(200.0)
    assert _read("shard_wave_ms", m) == pytest.approx(1500.0)
    least = (10e6 * 28 + 40 * 4096 * 8) / PEAKS["hbm_bytes_per_s"] / 4
    assert _read("shard_roofline", m) == pytest.approx(least / 1.5 * 100)
    assert _read("collective_ms", m) == pytest.approx(250.0)
    assert _read("device_idle_pct.mesh", m) == pytest.approx(50.0)
    # the single-device program is not the sharded one
    assert _read("wave_device_ms", m) is None


def test_mesh_readers_on_a_program_without_the_span_or_the_name():
    """A program that names its sharded wave program otherwise and times
    no ``place.tiles`` span: the new readers read nothing."""
    m = _measured({"local": 3.0}, tiles_s=[], topo_s=[0.4])
    for name in ("tiles_ms", "shard_wave_ms", "shard_roofline"):
        assert _read(name, m) is None
    assert _read("pack_ms", m) == pytest.approx(400.0)


def test_collective_ms_reads_zero_on_a_trace_without_collectives():
    """A trace with no collective in it reads 0.0, not nothing: a change
    that takes the all-gathers away shows as 0."""
    m = _measured({"_place_run_sharded": 3.0}, tiles_s=[0.1], topo_s=[0.4])
    m["trace"]["collective_s"] = 0.0
    assert _read("collective_ms", m) == 0.0
    assert _read("collective_ms", run.Measurements(trace=None)) is None

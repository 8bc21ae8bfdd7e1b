"""The benchmark finds every configuration, traffic mix, driver and
metric reader by name, from files alone, and BENCHMARK.json keeps the
shape its contract gives it."""

from __future__ import annotations

import importlib.util
import json
import os
import re
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _bench(root=REPO) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _run_module(root):
    spec = importlib.util.spec_from_file_location(
        f"bench_run_{abs(hash(str(root)))}",
        os.path.join(root, "benchmark", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_contract_shape():
    b = _bench()
    assert list(b) == ["command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"]
    assert 1 <= b["run_seconds"] <= 51
    for p in b["paths"]:
        assert os.path.isdir(os.path.join(REPO, p))
    configs = {c["name"]: c for c in b["configs"]}
    metrics = b["end_to_end"] + b["per_layer"]
    names = [*configs, *(w["name"] for w in b["workloads"]),
             *(m["name"] for m in metrics)]
    assert all(NAME.match(n) for n in names)
    assert len({c["name"] for c in b["configs"]}) == len(b["configs"])
    assert len({w["name"] for w in b["workloads"]}) == len(b["workloads"])
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(b["workloads"]) // 2)
    pairs = {(w["config"], w["traffic"]) for w in b["workloads"]}
    assert len(pairs) == len(b["workloads"])
    for c in b["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        assert any(w["config"] == c["name"] for w in b["workloads"])
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", cells)) <= set(moved)
    for w in b["workloads"]:
        reported = [m for m in b["end_to_end"]
                    if w["name"] in m.get("workloads", cells)]
        assert len(reported) >= 2
        assert any(w["name"] in m.get("workloads", cells)
                   for m in b["per_layer"])


def test_every_name_resolves_to_files():
    run = _run_module(REPO)
    b = _bench()
    for w in b["workloads"]:
        spec = run.resolve(w["name"])
        assert spec["config"]["chips"] == w["chips"]
        assert callable(run.load_module("drivers", spec["entry"]).run)
        for m in spec["end_to_end"] + spec["per_layer"]:
            assert callable(run.load_module("metrics", m["name"]).read)


def test_existing_cells_keep_their_drivers():
    """A traffic mix may name its own driver; the cells that came before
    that still run their configuration's."""
    run = _run_module(REPO)
    drivers = {"dag_1m_512w.random": "place", "live_64w.dag": "live",
               "dag_1m_512w.stencil": "place",
               "dag_10m_4096w.mesh": "place_mesh",
               "live_64w.flood": "flood"}
    for cell, entry in drivers.items():
        spec = run.resolve(cell)
        assert spec["entry"] == entry
        if cell != "live_64w.flood":
            assert "entry" not in spec["traffic"]
            assert spec["entry"] == spec["config"]["entry"]


def test_readers_return_nothing_without_input():
    """A reader that finds nothing to read returns None, never 0."""
    run = _run_module(REPO)
    b = _bench()
    for m in b["end_to_end"] + b["per_layer"]:
        assert run.load_module("metrics", m["name"]).read(
            run.Measurements()) is None


def test_a_new_cell_is_found_from_new_files_alone(tmp_path):
    """A later PR adds a configuration, a traffic mix and a metric by
    adding files and BENCHMARK.json entries; no existing file changes."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: (tmp_path / p).read_bytes()
              for p in ("benchmark/run.py", "benchmark/drivers/place.py")}
    cfg = json.loads((tmp_path / "benchmark/configs/dag_1m_512w.json")
                     .read_text())
    cfg["workers"] = 1024
    (tmp_path / "benchmark/configs/dag_1m_1024w.json").write_text(
        json.dumps(cfg))
    (tmp_path / "benchmark/traffic/wide.json").write_text(json.dumps({
        "kind": "graphs", "shape": "random", "graphs": 1,
        "shape_params": {"max_deps": 8, "duration_s": [0.1, 0.2],
                         "out_bytes": [1.0, 2.0]},
        "trace_seconds": 1.0, "why": "many dependencies",
    }))
    (tmp_path / "benchmark/metrics/waves_per_s.py").write_text(
        "def read(m):\n    return m.get('completed')\n")
    b = _bench(tmp_path)
    b["configs"].append({"name": "dag_1m_1024w", "source": "x",
                         "file": "benchmark/configs/dag_1m_1024w.json",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "dag_1m_1024w.wide",
                           "config": "dag_1m_1024w", "traffic": "wide",
                           "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "waves_per_s", "unit": "1/s",
                           "better": "higher", "source": "program_counter",
                           "layer": "device", "moves": "place_ms",
                           "workloads": ["dag_1m_1024w.wide"]})
    b["end_to_end"][1]["workloads"].append("dag_1m_1024w.wide")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    run = _run_module(tmp_path)
    spec = run.resolve("dag_1m_1024w.wide")
    assert spec["config"]["workers"] == 1024
    assert spec["traffic"]["shape_params"]["max_deps"] == 8
    assert [m["name"] for m in spec["per_layer"]] == ["waves_per_s"]
    reader = run.load_module("metrics", "waves_per_s")
    assert reader.read(run.Measurements(completed=3)) == 3
    assert all((tmp_path / p).read_bytes() == v for p, v in before.items())
    with pytest.raises(run.Refused):
        run.resolve("no.such_cell")
    with pytest.raises(run.Refused):
        run.load_module("metrics", "no_such_metric")


def test_new_traffic_brings_its_own_driver(tmp_path):
    """A later PR adds a traffic mix with its own ``entry`` on an existing
    configuration, and that driver, as new files and BENCHMARK.json
    entries alone: the harness's files and the configuration's driver do
    not change."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    kept = [os.path.relpath(os.path.join(d, f), tmp_path)
            for d, _, fs in os.walk(tmp_path / "benchmark") for f in fs]
    before = {p: (tmp_path / p).read_bytes() for p in kept}
    (tmp_path / "benchmark/traffic/burst.json").write_text(json.dumps({
        "kind": "live", "entry": "burst", "clients": 2,
        "why": "bursts of submissions",
    }))
    (tmp_path / "benchmark/drivers/burst.py").write_text(
        "TASK = None\n\n\ndef run(**kw):\n    return kw['traffic']\n")
    b = _bench(tmp_path)
    b["workloads"].append({"name": "live_64w.burst", "config": "live_64w",
                           "traffic": "burst", "chips": 1, "why": "x"})
    b["end_to_end"][-1]["workloads"].append("live_64w.burst")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    run = _run_module(tmp_path)
    spec = run.resolve("live_64w.burst")
    assert spec["entry"] == "burst"
    assert spec["config"]["entry"] == "live"
    assert spec["config"]["workers"] == 64
    driver = run.load_module("drivers", spec["entry"])
    assert driver.run(traffic=spec["traffic"])["clients"] == 2
    assert [m["name"] for m in spec["end_to_end"]] == ["setup_s",
                                                       "tasks_per_s"]
    assert run.resolve("live_64w.dag")["entry"] == "live"
    assert all((tmp_path / p).read_bytes() == v for p, v in before.items())

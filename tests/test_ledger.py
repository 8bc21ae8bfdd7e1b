"""Decision–outcome ledger + critical-path attribution (ledger.py,
diagnostics/critical_path.py; docs/observability.md "Decision ledger &
critical-path").

The deterministic core of the ISSUE-12 acceptance surface:

- same-seed simulator runs produce bit-identical ledger digests and
  leave ZERO unjoined/open rows at quiesce (the virtual clock makes
  every decision→outcome join exact);
- ``sim.run_ab`` reports per-arm regret + critical-path attribution,
  with identical digests for identical overrides and real deltas for
  steal on/off;
- on a telemetry-seeded NON-UNIFORM fleet the measured-shadow model's
  aggregate |regret| beats the constants' — the artifact ROADMAP
  item 1's input swap will gate on;
- critical-path attribution sums to the run's virtual makespan within
  1% (``critical_path.check``).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from distributed_tpu import config
from distributed_tpu.diagnostics.critical_path import (
    check,
    critical_path,
    deps_from_dump,
    to_records,
)
from distributed_tpu.ledger import (
    LEDGER_SCHEMA_VERSION,
    ROW_FIELDS,
    DecisionLedger,
)


# --------------------------------------------------------------- unit


def _file(led, key, worker="tcp://w0", src="", n_deps=0, dep_bytes=0,
          pred_c=0.0, pred_m=0.0, kind="placement", supersede=-1):
    return led.file(
        kind, key, "pfx", worker, "stim", pred_c, pred_m, False,
        dep_bytes, n_deps, 0.01, src, "", supersede=supersede,
    )


def test_file_join_basics():
    led = DecisionLedger(size=64, enabled=True)
    t = [0.0]
    led.clock = lambda: t[0]
    h = _file(led, "a", n_deps=1, dep_bytes=1000, pred_c=0.5, pred_m=0.1)
    assert led.filed_total == 1 and led.open_rows == 1
    t[0] = 1.0
    assert led.join_row(h, "memory", "tcp://w0", None, 0.3, None)
    assert led.open_rows == 0 and led.joined_total == 1
    row = led.tail()[-1]
    assert row["type"] == "ledger-row"
    assert row["v"] == LEDGER_SCHEMA_VERSION
    assert row["outcome"] == "memory"
    assert row["compute"] == 0.3
    # regret = (t_join - t_dec - compute) - pred = 0.7 - pred
    assert abs(row["regret_constant"] - 0.2) < 1e-12
    assert abs(row["regret_measured"] - 0.6) < 1e-12
    # a stale handle is a no-op
    assert not led.join_row(h, "memory")


def test_dep_free_rows_skip_regret_fold():
    """Dep-free decisions predict 0 transfer in BOTH models: their rows
    join (realized window intact for the critical path) but observe no
    regret — the aggregates stay a pure transfer-prediction audit."""
    led = DecisionLedger(size=64, enabled=True)
    h = _file(led, "a")  # n_deps=0
    assert led.join_row(h, "memory", "tcp://w0", None, 0.001, None)
    assert led.joined_total == 1
    assert led.summary()["kinds"] == {}
    assert led.tail()[-1]["outcome"] == "memory"


def test_supersede_and_worker_mismatch():
    led = DecisionLedger(size=64, enabled=True)
    h1 = _file(led, "a", worker="tcp://victim")
    h2 = _file(led, "a", worker="tcp://thief", kind="steal",
               supersede=h1)
    assert led.superseded_total == 1
    assert led.tail()[0]["outcome"] == "superseded"
    # the victim finished first: the steal row must NOT absorb the
    # victim's realization
    assert led.join_row(h2, "memory", worker="tcp://victim")
    assert led.outcomes["overtaken"] == 1
    assert led.summary()["kinds"] == {}  # no regret observed


def test_ring_wrap_counts_unjoined():
    led = DecisionLedger(size=4, enabled=True)
    for i in range(10):
        _file(led, f"k{i}")
    assert led.unjoined_total == 10 - 4
    assert led.open_rows == 4
    assert all(r["outcome"] == "" for r in led.tail())


def test_resolve_worker_closes_open_rows():
    led = DecisionLedger(size=64, enabled=True)
    _file(led, "a", worker="tcp://dead")
    keep = _file(led, "b", worker="tcp://alive")
    led.file_amm("amm-repl", "c", "tcp://dead", "s", nbytes=10)
    assert led.resolve_worker("tcp://dead") == 2
    assert led.open_rows == 1
    assert led.outcomes["worker-removed"] == 2
    assert led.join_row(keep, "memory", "tcp://alive")


def test_amm_rows_join_by_key_worker():
    led = DecisionLedger(size=64, enabled=True)
    t = [0.0]
    led.clock = lambda: t[0]
    led.file_amm("amm-repl", "k", "tcp://w1", "s",
                 pred_constant=0.2, pred_measured=0.1, nbytes=100,
                 src="tcp://w0")
    t[0] = 0.5
    assert not led.join_amm("k", "tcp://w2", "replicated")
    assert led.join_amm("k", "tcp://w1", "replicated")
    kinds = led.summary()["kinds"]
    assert kinds["amm-repl"]["count"] == 1
    assert abs(kinds["amm-repl"]["regret_mean_constant"] - 0.3) < 1e-12


def test_metric_lines_unique_and_labeled():
    from distributed_tpu.http.server import ledger_metric_lines

    led = DecisionLedger(size=64, enabled=True)
    h = _file(led, "a", src="tcp://s", n_deps=2, dep_bytes=100,
              pred_c=0.1, pred_m=0.2)
    led.join_row(h, "memory", "tcp://w0", None, 0.0, None)
    lines = ledger_metric_lines(led)
    samples = [
        ln for ln in lines if ln and not ln.startswith("#")
    ]
    assert len(samples) == len(set(s.rsplit(" ", 1)[0] for s in samples))
    assert any('kind="placement",model="constant"' in ln for ln in samples)
    assert any(
        ln.startswith("dtpu_ledger_link_regret_seconds_total")
        for ln in samples
    )


# ------------------------------------------------------ sim determinism


def _build_ab_sim(overrides=None, seed=7):
    """Telemetry-seeded non-uniform fleet: slow, heavily jittered links
    (the constants price them ~5-50x wrong) with the scheduler's link
    EWMAs pre-seeded from the same profile — the regime ROADMAP item 1
    swaps the kernel inputs for."""
    from distributed_tpu.sim import ClusterSim, SyntheticDag
    from distributed_tpu.sim.links import LinkProfile

    links = LinkProfile(bandwidth=2e7, jitter=0.9, seed=seed)
    sim = ClusterSim(
        12, nthreads=2, seed=seed, links=links, validate=True,
        ledger_size=65536, config_overrides=overrides,
    )
    sim.install_digest()
    rows = []
    addrs = list(sim.workers)
    for src in addrs:
        for dst in addrs:
            if src == dst:
                continue
            bw, lat = links._edge(src, dst)
            nb = 10_000_000
            rows.append([src, dst, nb, nb / bw + lat, 4])
    sim.state.telemetry.fold_rows(rows, reporter="")
    trace = SyntheticDag(
        n_layers=6, layer_width=18, fanin=2, seed=seed,
        layers_per_chunk=3, duration_range=(0.001, 0.005),
        nbytes_range=(256_000, 2_000_000),
    )
    return sim, trace


def test_sim_ledger_deterministic_and_fully_joined():
    """Same seed => bit-identical ledger digests; every decision row
    joins by quiesce (zero unjoined, zero open) — the virtual clock
    makes decision→outcome joins exact."""
    reports = []
    digests = []
    for _ in range(2):
        sim, trace = _build_ab_sim()
        trace.start(sim)
        reports.append(sim.run())
        digests.append(sim.state.ledger.digest())
    assert digests[0] == digests[1]
    for rep in reports:
        led = rep["ledger"]
        assert led["filed"] > 0
        assert led["unjoined"] == 0, led
        assert led["open"] == 0, led
        assert led["outcomes"].get("memory", 0) > 0
    assert reports[0]["ledger"] == reports[1]["ledger"]


def test_sim_measured_shadow_regret_beats_constants():
    """THE ROADMAP item 1 calibration artifact: on the telemetry-seeded
    non-uniform fleet the measured-shadow cost model's aggregate
    |regret| is lower than the constants' — the checked input-swap
    gate."""
    sim, trace = _build_ab_sim()
    trace.start(sim)
    rep = sim.run()
    reg = rep["ledger"]["regret_abs_mean"]
    assert reg["measured"] is not None
    assert reg["measured"] < reg["constant"], reg
    # and the rows that priced with measured links say so
    used = [
        r for r in sim.state.ledger.tail()
        if r["outcome"] == "memory" and r["used_measured"]
    ]
    assert used, "no decision was priced over a measured link"


def test_sim_critical_path_sums_to_makespan():
    sim, trace = _build_ab_sim()
    trace.start(sim)
    rep = sim.run()
    cp = sim.critical_path()
    assert cp is not None
    check(cp, tolerance=0.01)
    # t0=0.0 anchors the walk at the virtual epoch, so the path's
    # makespan IS the run's virtual makespan
    assert abs(cp["makespan"] - rep["virtual_makespan_s"]) <= (
        0.01 * rep["virtual_makespan_s"]
    )
    assert cp["attribution"]["compute"] > 0
    assert cp["attribution"]["transfer"] > 0
    # records round-trip for the Perfetto exporter
    recs = to_records(cp)
    assert recs[0]["type"] == "cp-summary"
    segs = [r for r in recs if r["type"] == "cp-segment"]
    assert segs
    for r in segs:
        assert r["t1"] >= r["t0"]


def test_run_ab_reports_regret_and_cp_deltas():
    """run_ab: identical overrides => identical digests AND identical
    ledger reports; steal on/off shows regret + critical-path deltas."""
    from distributed_tpu.sim.ab import run_ab

    def factory():
        # fanin=1 chains cluster hard onto their few input holders:
        # real imbalance, so the steal-on arm reliably steals (the
        # test_sim A/B shape)
        from distributed_tpu.sim import SyntheticDag

        return SyntheticDag(
            n_layers=8, layer_width=40, fanin=1, n_roots=4, seed=9,
        )

    same = run_ab(10, factory, None, None, seed=9, validate=True,
                  ledger_size=65536)
    assert same["a"]["digest"] == same["b"]["digest"]
    assert same["a"]["ledger"] == same["b"]["ledger"]
    assert same["diff"]["virtual_makespan_s"] == 0.0
    assert same["diff"]["regret_abs_mean_constant"] in (0.0, None)
    cp_diff = same["diff"]["critical_path"]
    assert cp_diff is not None
    assert all(abs(v) < 1e-12 for v in cp_diff.values())

    ab = run_ab(
        10, factory,
        {"scheduler.work-stealing": True},
        {"scheduler.work-stealing": False},
        seed=9, validate=True, ledger_size=65536,
    )
    assert ab["a"]["digest"] != ab["b"]["digest"]
    assert ab["a"]["steals"] > 0 and ab["b"]["steals"] == 0
    assert ab["diff"]["critical_path"] is not None
    # per-arm regret reports exist (the steal-on arm has steal-kind
    # regret rows; the steal-off arm has none)
    assert "steal" in ab["a"]["ledger"]["kinds"]
    assert "steal" not in ab["b"]["ledger"]["kinds"]


def test_sim_ab_arm_critical_path_in_report():
    from distributed_tpu.sim.ab import run_policy

    def factory():
        from distributed_tpu.sim import SyntheticDag

        return SyntheticDag(
            n_layers=4, layer_width=12, fanin=2, seed=1,
            layers_per_chunk=2,
        )

    rep = run_policy(8, factory, seed=1, validate=True,
                     ledger_size=65536)
    cp = rep["critical_path"]
    assert cp is not None
    assert cp["makespan"] > 0 and cp["n_tasks"] > 0
    assert set(cp["attribution"]) == {
        "compute", "transfer", "queue", "scheduler",
    }


# --------------------------------------------------------- state joins


def test_state_flood_joins_every_placement():
    from distributed_tpu.graph.spec import TaskSpec
    from distributed_tpu.scheduler.state import SchedulerState

    state = SchedulerState(validate=True)
    for i in range(4):
        state.add_worker_state(
            f"tcp://w{i}", nthreads=2, memory_limit=2**30, name=f"w{i}"
        )
    tasks = {f"t-{i}": TaskSpec(len, ((),)) for i in range(40)}
    deps: dict = {f"t-{i}": set() for i in range(40)}
    tasks["d-0"] = TaskSpec(len, ((),))
    deps["d-0"] = {"t-0", "t-1"}
    state.update_graph_core(
        tasks, deps, list(tasks), client="c", stimulus_id="s"
    )
    rounds = 0
    while True:
        batch = [
            (ts.key, ws.address, f"fin-{ts.key}", {"nbytes": 512})
            for ws in state.workers.values()
            for ts in list(ws.processing)
        ]
        if not batch:
            break
        state.stimulus_tasks_finished_batch(batch)
        rounds += 1
        assert rounds < 1000
    led = state.ledger
    assert led.filed_total == 41
    assert led.outcomes["memory"] == 41
    assert led.open_rows == 0 and led.unjoined_total == 0


def test_remove_worker_prunes_open_rows():
    from distributed_tpu.graph.spec import TaskSpec
    from distributed_tpu.scheduler.state import SchedulerState

    state = SchedulerState(validate=True)
    for i in range(2):
        state.add_worker_state(
            f"tcp://w{i}", nthreads=1, memory_limit=2**30, name=f"w{i}"
        )
    tasks = {f"t-{i}": TaskSpec(len, ((),)) for i in range(4)}
    state.update_graph_core(
        tasks, {k: set() for k in tasks}, list(tasks),
        client="c", stimulus_id="s",
    )
    led = state.ledger
    dead = next(iter(state.workers))
    open_before = led.open_rows
    assert open_before > 0
    state.remove_worker_state(dead, stimulus_id="rm", safe=False)
    assert led.outcomes.get("worker-removed", 0) > 0
    # the cascade re-placed the dead worker's tasks on the survivor:
    # no row may still point at the departed address
    for row in led.tail():
        if row["outcome"] == "":
            assert row["worker"] != dead


def test_erred_task_joins_as_erred():
    from distributed_tpu.graph.spec import TaskSpec
    from distributed_tpu.scheduler.state import SchedulerState

    state = SchedulerState(validate=True)
    state.add_worker_state(
        "tcp://w0", nthreads=1, memory_limit=2**30, name="w0"
    )
    state.update_graph_core(
        {"t": TaskSpec(len, ((),))}, {"t": set()}, ["t"],
        client="c", stimulus_id="s",
    )
    state.stimulus_tasks_erred_batch([
        ("t", "tcp://w0", "err-stim", {
            "exception": "boom", "exception_text": "boom",
        })
    ])
    assert state.ledger.outcomes.get("erred") == 1
    assert state.ledger.open_rows == 0


def _inc(x):
    return x + 1


def _sum(xs):
    return sum(xs)


def test_local_cluster_joins_every_decision():
    """A live two-worker cluster over TCP: a 60-task flood and a
    dependent graph leave every decision joined to its outcome, with
    regret observed, and ``get_ledger`` serves the summary; the
    critical path of the cluster's dump sums to its own makespan."""
    import asyncio

    from distributed_tpu.client.client import Client
    from distributed_tpu.deploy.local import LocalCluster
    from distributed_tpu.diagnostics.cluster_dump import DumpArtefact
    from distributed_tpu.graph.spec import Graph, TaskRef, TaskSpec

    async def run():
        async with LocalCluster(n_workers=2, threads_per_worker=1) as cl:
            async with Client(cl.scheduler_address) as c:
                await c.gather(c.map(_inc, range(60)))
                g = Graph()
                for i in range(16):
                    g.tasks[f"src-{i}"] = TaskSpec(_inc, (i,))
                    g.tasks[f"dep-{i}"] = TaskSpec(
                        _inc, (TaskRef(f"src-{i}"),))
                g.tasks["root"] = TaskSpec(
                    _sum, ([TaskRef(f"dep-{i}") for i in range(16)],))
                futs = c.compute_graph(g, ["root"])
                assert await futs["root"].result() == sum(range(16)) + 32
                summary = cl.scheduler.state.ledger.summary()
                rpc = await c.scheduler.get_ledger(n=10)
                dump = await c.dump_cluster_state()
        return summary, rpc, dump

    summary, rpc, dump = asyncio.run(asyncio.wait_for(run(), 120))
    assert summary["joined"] >= 60, summary
    assert summary["unjoined"] == 0 and summary["open"] == 0, summary
    assert summary["outcomes"].get("memory", 0) >= 60, summary
    assert sum(k["count"] for k in summary["kinds"].values()) > 0, summary
    assert rpc and rpc[0]["type"] == "ledger-summary"
    cp = DumpArtefact(dump).critical_path()
    assert cp is not None
    check(cp, tolerance=0.01)


# ------------------------------------------------------ offline tooling


def test_critical_path_cli_check_and_perfetto(tmp_path):
    """End-to-end offline loop: sim run -> ledger JSONL + deps JSON ->
    critical_path CLI --check/--out -> flight_recorder --ledger renders
    the path track."""
    sim, trace = _build_ab_sim()
    trace.start(sim)
    sim.run()
    from distributed_tpu.tracing import dump_journal

    ledger_path = tmp_path / "ledger.jsonl"
    deps_path = tmp_path / "deps.json"
    dump_journal(sim.state.ledger.tail(), str(ledger_path))
    deps = {
        k: [d.key for d in ts.dependencies]
        for k, ts in sim.state.tasks.items()
    }
    deps_path.write_text(json.dumps(deps))

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out_path = tmp_path / "cp.jsonl"
    proc = subprocess.run(
        [
            sys.executable, "-m",
            "distributed_tpu.diagnostics.critical_path",
            "--ledger", str(ledger_path), "--deps", str(deps_path),
            "--t0", "0.0", "--check", "--out", str(out_path),
        ],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-2000:])
    assert "OK" in proc.stdout
    cp_records = [
        json.loads(line) for line in out_path.read_text().splitlines()
    ]
    assert cp_records[0]["type"] == "cp-summary"

    # Perfetto: ledger rows + cp segments render as their own tracks
    from distributed_tpu.diagnostics.flight_recorder import to_perfetto

    perf = to_perfetto(
        [], ledger=sim.state.ledger.tail(200) + cp_records
    )
    tracks = {
        e["args"]["name"] for e in perf["traceEvents"]
        if e.get("ph") == "M"
    }
    assert "ledger (decision joins)" in tracks
    assert "critical path" in tracks
    assert any(
        e.get("ph") == "X" and e.get("cat") == "critical-path"
        for e in perf["traceEvents"]
    )
    assert any(
        e.get("name") == "ledger regret seconds"
        for e in perf["traceEvents"]
    )


def test_deps_from_dump_both_shapes():
    dump = {
        "scheduler": {
            "tasks": {"a": {"dependencies": ["b"]}, "b": {}},
        }
    }
    assert deps_from_dump(dump) == {"a": ["b"], "b": []}
    assert deps_from_dump({"a": ["b"]}) == {"a": ["b"]}


def test_critical_path_telescopes_manual_rows():
    """Hand-built chain: attribution telescopes exactly to the span."""
    rows = []
    t = 0.0
    for i, key in enumerate(("a", "b", "c")):
        rows.append({
            "type": "ledger-row", "seq": i, "kind": "placement",
            "key": key, "prefix": "p", "worker": "w", "src": "",
            "stim": f"s{i}", "plan_stim": "",
            "t_decision": t + 0.1, "outcome": "memory",
            "t_join": t + 1.0, "compute": 0.5, "transfer": 0.2,
            "n_deps": 1, "dep_bytes": 10,
        })
        t += 1.0
    deps = {"a": [], "b": ["a"], "c": ["b"]}
    res = critical_path(rows, deps, t0=0.0)
    assert res is not None
    assert res["n_tasks"] == 3
    assert abs(res["makespan"] - 3.0) < 1e-9
    check(res, tolerance=1e-6)
    assert abs(res["attribution"]["compute"] - 1.5) < 1e-9
    assert abs(res["attribution"]["transfer"] - 0.6) < 1e-9
    # scheduler latency: 0.1s per hop
    assert abs(res["attribution"]["scheduler"] - 0.3) < 1e-9


def test_dump_artefact_ledger_and_critical_path():
    from distributed_tpu.diagnostics.cluster_dump import DumpArtefact

    sim, trace = _build_ab_sim()
    trace.start(sim)
    sim.run()
    led = sim.state.ledger
    cp_live = sim.critical_path()
    dump = {
        "scheduler": {
            "tasks": {
                k: {
                    "state": ts.state,
                    "dependencies": [d.key for d in ts.dependencies],
                }
                for k, ts in sim.state.tasks.items()
            },
            "ledger": {
                "rows": led.tail(),
                "summary": led.summary(),
            },
        }
    }
    art = DumpArtefact(dump)
    assert art.ledger and art.ledger_summary["joined"] > 0
    assert art.ledger_rows(outcome="memory")
    cp = art.critical_path()
    assert cp is not None
    # the dump walk anchors at the first path task's own decision (no
    # t0, unrestricted terminal): attribution still sums to ITS makespan
    check(cp, tolerance=0.01)
    assert cp["terminal"] in dump["scheduler"]["tasks"]
    assert cp_live is not None  # the sim's own (terminal-pinned) walk

    # precomputed summary short-circuits
    dump["scheduler"]["ledger"]["critical_path"] = {"makespan": 42.0}
    art2 = DumpArtefact(dump)
    assert art2.critical_path() == {"makespan": 42.0}
    assert art2.critical_path(full=True)["makespan"] != 42.0


def test_ledger_snapshot_shape():
    sim, trace = _build_ab_sim()
    trace.start(sim)
    sim.run()
    snap = sim.state.ledger.snapshot(5)
    assert snap[0]["type"] == "ledger-summary"
    assert snap[0]["digest"] == sim.state.ledger.digest()
    rows = snap[1:]
    assert len(rows) == 5
    assert all(set(ROW_FIELDS) <= set(r) for r in rows)


def test_ledger_disabled_is_inert():
    with config.set({"scheduler.ledger.enabled": False}):
        led = DecisionLedger()
    assert led.file("placement", "k", "p", "w", "s") == -1
    assert led.filed_total == 0
    led.file_amm("amm-repl", "k", "w", "s")
    assert led.open_rows == 0


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))

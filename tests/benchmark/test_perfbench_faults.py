"""The comparison that decides ``correct`` fails when the timed path is
broken underneath: a run driven in-process past the harness's look for a
chip, once for each fault a cell can have.  The controls and the
reference are checked in test_perfbench_control.py."""

from __future__ import annotations

import json

import pytest

from benchmark import run
from distributed_tpu.ops import leveled


@pytest.fixture
def no_chip_check(monkeypatch):
    """The rest of a run, on this process's CPU devices and its jax
    settings (no persistent cache switched on for the test process)."""
    import jax

    monkeypatch.setattr(run, "start_jax",
                        lambda chips, rehearse: jax.devices()[:chips])


def _correct(capsys, cell: str, seconds: float = 1) -> bool:
    run.main(["--workload", cell, "--seed", "5", "--seconds", str(seconds),
              "--trace", "0", "--rehearse"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return out["correct"]


def _skip_all_waves(self, bufs, F, waves, last):
    self._maybe_segment(int(self.packed.offsets[waves[-1] + 1]), last)


def _half_of_each_wave(place_run):
    def broken(*a, **kw):
        a = list(a)
        a[11] = a[11] // 2  # fs: the true size of each wave of the run
        return place_run(*a, **kw)

    return broken


def _alter_one(finalize):
    def broken(*a, **kw):
        res = finalize(*a, **kw)
        a = res.assignment.copy()
        a[len(a) // 3] = (a[len(a) // 3] + 1) % (a.max() + 1)
        return res._replace(assignment=a)

    return broken


PLACEMENT_FAULTS = {
    # every wave skipped: the state comes back as it went in
    "state_unchanged": (leveled._RunState, "dispatch",
                        lambda: _skip_all_waves),
    # the second half of every wave never placed
    "half_left_out": (leveled, "_place_run",
                      lambda: _half_of_each_wave(leveled._place_run)),
    "answer_altered": (leveled, "_finalize",
                       lambda: _alter_one(leveled._finalize)),
}


@pytest.mark.parametrize("cell", ["dag_1m_512w.random",
                                  "dag_1m_512w.stencil"])
def test_sound_placement_is_correct(cell, no_chip_check, capsys):
    assert _correct(capsys, cell)


@pytest.mark.parametrize("fault", sorted(PLACEMENT_FAULTS))
@pytest.mark.parametrize("cell", ["dag_1m_512w.random",
                                  "dag_1m_512w.stencil"])
def test_placement_fault_is_not_correct(cell, fault, no_chip_check,
                                        monkeypatch, capsys):
    owner, name, make = PLACEMENT_FAULTS[fault]
    monkeypatch.setattr(owner, name, make())
    assert not _correct(capsys, cell)


def _live_fault(monkeypatch, fault):
    from distributed_tpu.client.client import Client
    from distributed_tpu.graph.spec import TaskSpec

    if fault == "answer_altered":
        substitute = TaskSpec.substitute
        calls = [0]

        def broken(self, data):
            fn, args, kwargs = substitute(self, data)
            calls[0] += 1
            if calls[0] == 100:
                return (lambda *a, **k: fn(*a, **k) + 1), args, kwargs
            return fn, args, kwargs

        monkeypatch.setattr(TaskSpec, "substitute", broken)
    else:  # half of each graph's results never reach the client
        gather = Client.gather

        async def broken(self, futures, errors="raise"):
            got = await gather(self, futures, errors)
            return got[: len(got) // 2]

        monkeypatch.setattr(Client, "gather", broken)


@pytest.mark.parametrize("fault", [None, "answer_altered", "half_left_out"])
@pytest.mark.parametrize("cell", ["live_64w.dag", "live_64w.flood"])
def test_live_faults(cell, fault, no_chip_check, monkeypatch, capsys):
    if fault:
        _live_fault(monkeypatch, fault)
    # a window long enough to hold a device steal cycle and AMM round
    assert _correct(capsys, cell, 3) is (fault is None)


def test_flood_fails_with_the_planner_on(no_chip_check, monkeypatch,
                                         capsys):
    """The flood's bypass is checked: with a map above ``min-batch``
    (512, the program's default) the planner runs and the run is not
    correct."""
    resolve = run.resolve

    def planner_on(workload, rehearse=False):
        spec = resolve(workload, rehearse)
        spec["traffic"] = {**spec["traffic"], "map_tasks": 600}
        return spec

    monkeypatch.setattr(run, "resolve", planner_on)
    run.main(["--workload", "live_64w.flood", "--seed", "5", "--seconds",
              "3", "--trace", "0", "--rehearse"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is False
    assert out["checks"]["planner_plans"]["value"] > 0
    assert out["checks"]["wrong_results"]["value"] == 0

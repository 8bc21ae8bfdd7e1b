"""The controls of ``correct``: each cell's comparison run on something
that must fail it, beside the sound program on the same seeds.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--seconds s]

- placement cells: the program's own lower-precision path,
  ``place_graph_streamed(compact=True)`` (transfer costs as 8-bit log
  codes instead of float16), against the plain reference;
- the live cells (a driver with a ``TASK``, the function every task
  runs): every task computed in float32 instead of exact integers,
  through the same cluster and the cell's own driver.

Prints one line per seed and reading.  The benchmark's own runs never
run this; ``tests/benchmark`` runs it at a small size.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import graphs, reference, run  # noqa: E402


def placement_readings(config, traffic, seed: int) -> dict:
    """Misplaced tasks of graph 0 of ``seed``: the program as the cell
    runs it, and the control."""
    import numpy as np

    from benchmark.drivers.place import _fleet
    from distributed_tpu.ops.leveled import place_graph_streamed

    g = graphs.make_graph(traffic, config["tasks"], seed, 0)
    fleet = _fleet(config)
    kw = {"bandwidth": config["bandwidth"], "latency": config["latency_s"]}
    if "min_stream" in config:
        kw["min_stream"] = config["min_stream"]
    args = (config["workers"], config["threads"], config["bandwidth"],
            config["latency_s"])
    want = reference.place(*g, *args)[0]
    sound = place_graph_streamed(*g, *fleet, **kw)[1]
    control = place_graph_streamed(*g, *fleet, compact=True, **kw)[1]
    return {"seed": seed,
            "misplaced_tasks": int(np.sum(sound.assignment != want)),
            "control_misplaced_tasks": int(np.sum(control.assignment
                                                  != want))}


def live_readings(driver, config, traffic, seed: int, seconds: float,
                  devices) -> dict:
    """wrong_results of a short run of a live ``driver``, sound and with
    float32 tasks."""
    out = {"seed": seed}
    for label, task in (("", graphs.node), ("control_", float32_node)):
        driver.TASK = task
        try:
            res = driver.run(config, traffic, seed, seconds, False,
                             devices, 0.0, run.Measurements())
        finally:
            driver.TASK = graphs.node
        out[f"{label}wrong_results"] = dict(
            (n, v) for n, v, _ in res["checks"])["wrong_results"]
    return out


def float32_node(salt, *deps):
    """``graphs.node`` in float32: exact integers below 2**24 only."""
    import numpy as np

    f = np.float32
    return int((f(salt) * f(31) + f(sum(deps))) % f(graphs.MOD))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse", action="store_true")
    opts = ap.parse_args(argv)
    spec = run.resolve(opts.workload, opts.rehearse)
    devices = run.start_jax(spec["cell"]["chips"], opts.rehearse)
    print(json.dumps({"device": run.device_info(devices)}), flush=True)
    driver = run.load_module("drivers", spec["entry"])
    for seed in (int(s) for s in opts.seeds.split(",")):
        if hasattr(driver, "TASK"):
            out = live_readings(driver, spec["config"], spec["traffic"],
                                seed, opts.seconds, devices)
        else:
            out = placement_readings(spec["config"], spec["traffic"], seed)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data, found by name:

- the cell in ``BENCHMARK.json`` names a configuration and a traffic mix;
- the configuration is ``benchmark/configs/<config>.json``;
- the traffic mix is ``benchmark/traffic/<traffic>.json``;
- the driver is ``benchmark/drivers/<entry>.py``, ``entry`` being the
  traffic's own where it names one, else the configuration's (new
  traffic on an existing configuration brings its driver as a new file);
- each metric is read by ``benchmark/metrics/<metric>.py`` (or, for a
  split metric ``<base>.<part>``, by ``<base>.py``) from the
  measurements the driver leaves (see ``Measurements``).

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and ``checks`` last: each number compared beside its
limit).  The same checks are the last lines of standard error.  A run
that finds no TPU, fewer chips than the cell asks for, or no native
library exits non-zero and prints no result.  ``--rehearse`` (tests
only) accepts the CPU and takes the sizes from the configuration's and
the traffic's ``rehearsal`` entries.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up runs from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Refused(Exception):
    """The run cannot measure what the cell asks for: no result."""


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module.  A name split by a dot
    (``device_idle_pct.live``) that has no file of its own is read by the
    file of the part before the first dot (``device_idle_pct.py``)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        path = os.path.join(HERE, kind, f"{name.split('.', 1)[0]}.py")
    if not os.path.exists(path):
        raise Refused(f"no {kind} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_')}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str, rehearse: bool = False) -> dict:
    """The cell, its configuration, its traffic, its driver's ``entry``
    and its metrics, by name."""
    bench = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(cfg_entry["file"])
    traffic = load_json("benchmark", "traffic", f"{cell['traffic']}.json")
    if rehearse:
        config = {**config, **config.get("rehearsal", {})}
        traffic = {**traffic, **traffic.get("rehearsal", {})}

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "cell": cell, "config": config, "traffic": traffic,
        "entry": traffic.get("entry", config["entry"]),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


class Measurements(dict):
    """What a driver leaves for the metric readers.  Keys a driver may
    set (a reader returns ``None`` when its key is absent):

    setup_s, window_s            host clock, seconds
    latencies_s                  one per completed operation in the window
    completed                    operations completed in the window
    tasks_done                   tasks whose results reached the client
    topo_s                       host pack seconds, one per placement
    compiles_window              backend compiles inside the window
    wall_delta                   WallBudget self seconds per phase, window
    planner_delta                planner counter deltas over the window
    trace                        benchmark.trace.reduce() of the traced part
    traced_ops                   operations inside the traced part
    bytes_per_op, tasks_per_op   what one operation must move and compute
    peaks                        this device kind's row of peaks.json
    """


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        try:
            peak = max(peak, int(d.memory_stats()["peak_bytes_in_use"]))
        except Exception:  # a backend without memory stats (the CPU)
            pass
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }


def peaks_for(kind: str, rehearse: bool) -> dict:
    table = load_json("benchmark", "peaks.json")["devices"]
    if kind in table:
        return table[kind]
    if rehearse:
        return {}
    raise Refused(f"device kind {kind!r} is not in benchmark/peaks.json")


def start_jax(chips: int, rehearse: bool):
    """The accelerator, the compile cache and the native library, or
    ``Refused``."""
    # the cache lives at a fixed path inside this checkout; the program
    # takes its directory from this variable
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" and not rehearse:
        raise Refused(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX found "
                      f"{len(devices)}")
    from distributed_tpu import native
    from distributed_tpu.ops.compile_cache import enable_compile_cache

    enable_compile_cache()
    if native.load() is None:
        raise Refused("distributed_tpu.native.load() returned None")
    return devices[:chips]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description="distributed_tpu benchmark")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help=argparse.SUPPRESS)
    opts = ap.parse_args(argv)
    if opts.seed < 0:
        raise Refused("--seed must be a whole number >= 0")
    sys.path.insert(0, ROOT)
    spec = resolve(opts.workload, opts.rehearse)
    devices = start_jax(spec["cell"]["chips"], opts.rehearse)
    m = Measurements(peaks=peaks_for(devices[0].device_kind, opts.rehearse))
    driver = load_module("drivers", spec["entry"])
    out = driver.run(
        config=spec["config"], traffic=spec["traffic"], seed=opts.seed,
        seconds=opts.seconds, trace=bool(opts.trace), devices=devices,
        t_process=T_PROCESS, m=m,
    )
    # the peak never falls, and the references run on the host
    info = device_info(devices)
    if opts.trace:
        tr = m.get("trace")
        if not tr or tr["busy_s"] <= 0:
            raise Refused("the traced window holds no device operation")
        info["busy_s"] = tr["busy_s"]
        info["window_s"] = tr["window_s"]
    wanted = spec["per_layer"] if opts.trace else spec["end_to_end"]
    metrics = {}
    for metric in wanted:
        value = load_module("metrics", metric["name"]).read(m)
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
    checks = {name: {"value": v, "limit": lim}
              for name, v, lim in out["checks"]}
    correct = out["ok"] and all(c["value"] <= c["limit"]
                                for c in checks.values())
    result = {
        "correct": bool(correct),
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": metrics,
        "device": info,
    }
    if opts.trace:
        result["breakdown"] = m["trace"]["breakdown"]
    result["checks"] = checks
    if out.get("notes"):
        print(f"notes {json.dumps(out['notes'])}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"check correct = {result['correct']}", file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Refused as exc:
        print(f"refused: {exc}", file=sys.stderr)
        sys.exit(2)

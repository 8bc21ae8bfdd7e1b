"""Benchmark suite: the BASELINE.md configs plus the engine headlines.

Prints exactly ONE json line on stdout:

  {"metric": "...", "value": N, "unit": "...", "vs_baseline": N,
   "backend": "...", "configs": {...}}

The headline metric is BASELINE config 5 (the north star): place a
1M-task random DAG onto 512 simulated workers with the level-synchronous
device engine (`ops/leveled.py`) versus the stock pure-python
decide_worker loop (reference scheduler.py:8550, ~1 ms/task per
docs/source/efficiency.rst:48-50).  `configs` carries the other four
BASELINE configs (array-sum, rechunk+tensordot, steal-imbalance,
P2P shuffle) measured end-to-end on a live LocalCluster.

Every config runs in its own subprocess with a hard timeout, one after
another, so exactly one process at a time holds the chip and the parent
never imports jax.  A hang or crash in one config (a device error
included) yields an "error" entry for that config only; nothing falls
back to another backend.  The final JSON line is always printed.

Scheduler-cluster configs (1-4), dag_10m and sim_10k are pinned to the
CPU (``force_cpu``); moving them onto the chip is ROADMAP A.1.  dag_1m
runs on the default backend: the attached chip where there is one.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

# (name, timeout_s, force_cpu)
CONFIGS = [
    ("array_sum", 240.0, True),
    ("rechunk_tensordot", 420.0, True),
    ("steal", 240.0, True),
    ("shuffle", 420.0, True),
    ("dag_1m", 600.0, False),
    # the sharded engine headline: always on the 8-device CPU mesh (the
    # per-shard H2D/collective structure is what is measured; the box
    # has no multi-chip accelerator)
    ("dag_10m", 900.0, True),
    # sans-io cluster simulator headline (distributed_tpu/sim): 1M tasks
    # through the REAL scheduler engine + 10,000 REAL worker state
    # machines on a virtual clock, run twice — the virtual makespan and
    # whole-run digest must be bit-identical, so the reported number is
    # immune to the box's 2x wall drift
    ("sim_10k", 7200.0, True),
]


def _ensure_cpu_mesh_env(n: int = 8) -> None:
    """Force an ``n``-device CPU mesh BEFORE the first backend init —
    the same settings as tests/conftest.py."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)

BANDWIDTH = 100e6


# =====================================================================
# config 1: da.ones((10_000, 10_000), chunks=1000).sum()
# LocalCluster(processes=False), 4 workers  (BASELINE.md config 1)
# =====================================================================

def _np_ones(shape):
    import numpy as np

    return np.ones(shape, np.float64)


def _np_sum(a):
    return float(a.sum())


def _sum_list(xs):
    return sum(xs)


def _inc(x):
    return x + 1


async def cfg_array_sum():
    import numpy as np  # noqa: F401  (workers build numpy chunks)

    from distributed_tpu.client.client import Client
    from distributed_tpu.deploy.local import LocalCluster
    from distributed_tpu.graph.spec import Graph, TaskRef, TaskSpec

    g = Graph()
    partials = []
    for i in range(10):
        for j in range(10):
            ck = f"ones-{i}-{j}"
            g.tasks[ck] = TaskSpec(_np_ones, ((1000, 1000),))
            sk = f"sum-{i}-{j}"
            g.tasks[sk] = TaskSpec(_np_sum, (TaskRef(ck),))
            partials.append(sk)
    level, r = partials, 0
    while len(level) > 1:
        nxt = []
        for b in range(0, len(level), 8):
            k = f"agg-{r}-{b}"
            g.tasks[k] = TaskSpec(
                _sum_list, ([TaskRef(x) for x in level[b : b + 8]],)
            )
            nxt.append(k)
        level, r = nxt, r + 1
    root = level[0]
    n_tasks = len(g.tasks)

    async with LocalCluster(n_workers=4, threads_per_worker=2) as cluster:
        async with Client(cluster.scheduler_address) as c:
            t0 = time.perf_counter()
            futs = c.compute_graph(g, [root])
            result = await futs[root].result()
            wall = time.perf_counter() - t0
            assert result == 10_000 * 10_000, result

            # dedicated trivial-task probe: per-task end-to-end overhead
            # vs the reference's ~1 ms/task (docs/source/efficiency.rst)
            t0 = time.perf_counter()
            await c.gather(c.map(_inc, range(500)))
            owall = time.perf_counter() - t0

    overhead = owall / 500
    return {
        "desc": "ones((10000,10000),chunks=1000).sum(), 4 workers",
        "n_tasks": n_tasks,
        "wall_s": round(wall, 3),
        "tasks_per_s": round(n_tasks / wall),
        "overhead_us_per_task": round(overhead * 1e6),
        "vs_baseline": round(0.001 / overhead, 1),
    }


# =====================================================================
# config 2: rechunk + tensordot, ~50k tasks, 16 workers
# (BASELINE.md config 2) — tiny payloads so the SCHEDULER is measured;
# reports placement co-processor plan hit-rate with jax on vs off.
# =====================================================================

def _blk():
    import numpy as np

    return np.full((4, 4), 1.0)


def _quad(a, qi, qj):
    h = a.shape[0] // 2
    return a[qi * h : (qi + 1) * h, qj * h : (qj + 1) * h]


def _assemble(q00, q01, q10, q11):
    import numpy as np

    return np.block([[q00, q01], [q10, q11]])


def _mul(a, b):
    return a @ b


def _add_all(xs):
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return out


def _tensordot_graph(G, tag=""):
    """rechunk(A) then C = A' @ B blockwise: ~45k tasks at G=32."""
    from distributed_tpu.graph.spec import Graph, TaskRef, TaskSpec

    g = Graph()
    for i in range(G):
        for k in range(G):
            g.tasks[f"A{tag}-{i}-{k}"] = TaskSpec(_blk)
            g.tasks[f"B{tag}-{i}-{k}"] = TaskSpec(_blk)
    # rechunk stage: quarter every A chunk and reassemble (same tiling —
    # the graph SHAPE of a rechunk: split tasks + gather tasks)
    for i in range(G):
        for k in range(G):
            for qi in range(2):
                for qj in range(2):
                    g.tasks[f"Aq{tag}-{i}-{k}-{qi}{qj}"] = TaskSpec(
                        _quad, (TaskRef(f"A{tag}-{i}-{k}"), qi, qj)
                    )
            g.tasks[f"Ar{tag}-{i}-{k}"] = TaskSpec(
                _assemble,
                tuple(
                    TaskRef(f"Aq{tag}-{i}-{k}-{qi}{qj}")
                    for qi in range(2)
                    for qj in range(2)
                ),
            )
    # blockwise tensordot with tree reduction (fan-in 8)
    outs = []
    for i in range(G):
        for j in range(G):
            for k in range(G):
                g.tasks[f"mul{tag}-{i}-{j}-{k}"] = TaskSpec(
                    _mul, (TaskRef(f"Ar{tag}-{i}-{k}"), TaskRef(f"B{tag}-{k}-{j}"))
                )
            level = [f"mul{tag}-{i}-{j}-{k}" for k in range(G)]
            r = 0
            while len(level) > 1:
                nxt = []
                for b in range(0, len(level), 8):
                    key = f"red{tag}-{i}-{j}-{r}-{b}"
                    g.tasks[key] = TaskSpec(
                        _add_all, ([TaskRef(x) for x in level[b : b + 8]],)
                    )
                    nxt.append(key)
                level, r = nxt, r + 1
            outs.append(level[0])
    return g, outs


async def _run_tensordot(jax_enabled, G=32):
    """Steady-state measurement: a warm-up graph first (jit caches,
    connections, duration estimates), then an identically-shaped graph
    timed in the same cluster.

    ``jax_enabled=None`` runs the TRUE DEFAULT configuration (since the
    partitioner planner landed, the co-processor engages at 16 workers
    by default); ``False`` forces the pure-python oracle baseline."""
    from distributed_tpu import config
    from distributed_tpu.client.client import Client
    from distributed_tpu.deploy.local import LocalCluster

    overrides = {} if jax_enabled is None else {
        "scheduler.jax.enabled": jax_enabled,
        "scheduler.jax.min-workers": 0,
        "scheduler.jax.min-transfer-ratio": 0,
    }
    with config.set(overrides):
        async with LocalCluster(n_workers=16, threads_per_worker=1) as cluster:
            async with Client(cluster.scheduler_address) as c:
                wg, wouts = _tensordot_graph(G, tag="w")
                futs = c.compute_graph(wg, wouts)
                await c.gather([futs[k] for k in wouts])
                del futs
                placement = cluster.scheduler.state.placement
                if placement is not None:
                    placement.plan_hits = placement.plan_misses = 0
                    placement.plan_parks = 0
                    placement.plans_computed = 0
                    for k in placement.miss_reasons:
                        placement.miss_reasons[k] = 0
                    for k in placement.hint_drops:
                        placement.hint_drops[k] = 0

                g, outs = _tensordot_graph(G)
                n_tasks = len(g.tasks)
                t0 = time.perf_counter()
                futs = c.compute_graph(g, outs)
                await c.gather([futs[k] for k in outs])
                wall = time.perf_counter() - t0
                stats = (
                    {
                        "plans": placement.plans_computed,
                        "hits": placement.plan_hits,
                        "parks": placement.plan_parks,
                        "misses": placement.plan_misses,
                        "miss_reasons": dict(placement.miss_reasons),
                        "hint_drops": dict(placement.hint_drops),
                    }
                    if placement is not None
                    else None
                )
    return n_tasks, wall, stats


async def cfg_rechunk_tensordot():
    """Headline ``wall_s``: the TRUE DEFAULT configuration — since the
    partitioner planner (ops/partition.py) the co-processor engages at
    16 workers by default, tiles the graph, and the plan is consumed
    with deep home stacks + steal exemption.  ``wall_s_python_only`` is
    the forced-off oracle baseline measured in the same process;
    ``wall_s_jax_forced`` keeps its historical meaning (co-processor on)
    for round-over-round comparison — it now equals the default path."""
    n_tasks, wall_py, _ = await _run_tensordot(False)
    _, wall, stats = await _run_tensordot(None)
    forced = round(wall, 3)
    vs_py = round(wall_py / wall, 2)
    return {
        "desc": "rechunk+tensordot blockwise, 16 workers",
        "n_tasks": n_tasks,
        "wall_s": round(wall, 3),
        "wall_s_python_only": round(wall_py, 3),
        "wall_s_jax_forced": forced,
        "tasks_per_s": round(n_tasks / wall),
        "overhead_us_per_task": round(wall / n_tasks * 1e6),
        "plan_stats": stats,
        "vs_python_only": vs_py,
        "vs_baseline": round(0.001 / (wall / n_tasks), 1),
    }


# =====================================================================
# config 3: imbalanced slowinc + work stealing, 64 workers
# (BASELINE.md config 3; reference test_steal.py)
# =====================================================================

def _slowinc(i, x=0, delay=0.02):
    time.sleep(delay)
    return i + x


async def _run_steal(steal_enabled):
    from distributed_tpu import config
    from distributed_tpu.client.client import Client
    from distributed_tpu.deploy.local import LocalCluster

    n_tasks, n_workers, delay = 320, 64, 0.02
    mirror_stats = None
    with config.set(
        {
            "scheduler.work-stealing": steal_enabled,
            "scheduler.jax.enabled": False,
        }
    ):
        async with LocalCluster(
            n_workers=n_workers, threads_per_worker=1
        ) as cluster:
            async with Client(cluster.scheduler_address) as c:
                w0 = cluster.workers[0].address
                # prime the prefix duration estimate, then pin every task
                # to ONE worker with loose restrictions — only work
                # stealing can spread them (the reference's
                # test_steal.py steal-cheap-data-slow-computation shape)
                await c.submit(_slowinc, -1, delay=delay).result()
                t0 = time.perf_counter()
                futs = c.map(
                    _slowinc,
                    range(n_tasks),
                    delay=delay,
                    workers=[w0],
                    allow_other_workers=True,
                )
                await c.gather(futs)
                wall = time.perf_counter() - t0
                mirror = cluster.scheduler.state.mirror
                if mirror is not None:
                    mirror_stats = mirror.stats()
    ideal = n_tasks * delay / n_workers
    return wall, ideal, n_tasks, mirror_stats


def _host_canary_ms() -> float:
    """Milliseconds for a fixed pure-python workload: the steal config's
    walls swing with host load (this box drifts 2x+ through a day —
    PERF.md Rounds 5-6), so cross-round comparisons of
    ``balance_efficiency`` are only meaningful normalized by this
    canary, same role as ``stock_us_per_task`` in the dag_1m entry."""
    t0 = time.perf_counter()
    acc = 0
    for i in range(200_000):
        acc += i % 7
    return (time.perf_counter() - t0) * 1e3


async def cfg_steal():
    # median-of-N (N >= 3, odd): this box is a shared host and the wall
    # of an 0.1 s-ideal run swings 0.18-0.30 s with load (one of three
    # runs has been seen at 0.302 s vs 0.196 s).  The MEDIAN is robust to
    # a single loaded run while not hiding a real regression the way
    # min-of-N does; all runs plus their spread are reported so a
    # regression is distinguishable from noise.
    import statistics

    n_runs = max(int(os.environ.get("DTPU_BENCH_STEAL_RUNS", "3")), 3)
    n_runs += 1 - n_runs % 2  # odd, so the median is a real run
    canary = _host_canary_ms()
    walls = []
    ideal = n_tasks = None
    mirror_stats = None
    for _ in range(n_runs):
        wall, ideal, n_tasks, mstats = await _run_steal(True)
        walls.append(round(wall, 3))
        mirror_stats = mstats or mirror_stats
    wall = statistics.median(walls)
    # median-of-3 for the baseline too: a single noisy no-steal run
    # against a median steal run would misstate the benefit either way
    walls_off = []
    for _ in range(3):
        wall_off, _, _, _ = await _run_steal(False)
        walls_off.append(round(wall_off, 3))
    wall_off = statistics.median(walls_off)
    return {
        "desc": "imbalanced slowinc x320 from one worker's data, 64 workers",
        "n_tasks": n_tasks,
        "wall_s": wall,
        "wall_s_runs": walls,
        "wall_s_spread": round(max(walls) - min(walls), 3),
        "wall_s_no_steal": round(wall_off, 3),
        "wall_s_no_steal_runs": walls_off,
        "ideal_s": round(ideal, 3),
        "balance_efficiency": round(ideal / wall, 3),
        "host_canary_ms": round(canary, 2),
        "mirror": mirror_stats,
        "vs_baseline": round(wall_off / wall, 1),
    }


# =====================================================================
# config 4: P2P shuffle, 10M rows, columnar (BASELINE.md config 4)
# =====================================================================

def _reference_shuffle_dataplane_rows_per_s(n_rows=2_000_000, n_parts=16,
                                            nout=128):
    """The reference's P2P shuffle DATA PLANE re-run faithfully on this
    host: per input partition a pandas merge with the worker_for
    categorical, arrow conversion, sort_by destination, slicing into
    shards and buffer serialization; per output partition deserialize +
    concat + to_pandas (reference shuffle/_shuffle.py split_by_worker
    :490-533, _core.py add_partition/_fetch semantics, _arrow.py
    serialize_table/deserialize_table).  Scheduler, network and disk are
    all EXCLUDED — this measures only the rows/s ceiling of the
    reference's per-row machinery, which favors the reference.
    Subsampled (2M rows) and scaled: the per-row cost is flat in n.
    """
    from collections import defaultdict

    import numpy as np
    import pandas as pd
    import pyarrow as pa

    rows_per = n_rows // n_parts
    workers = [f"w{i}" for i in range(128)]
    worker_for = pd.Series(
        pd.Categorical([workers[i % 128] for i in range(nout)]),
        index=pd.RangeIndex(nout), name="_workers",
    )
    rng = np.random.default_rng(0)
    dfs = [
        pd.DataFrame({
            "key": rng.integers(0, nout, rows_per),
            "value": rng.random(rows_per),
        })
        for _ in range(n_parts)
    ]

    t0 = time.perf_counter()
    inbox: defaultdict[str, list] = defaultdict(list)
    codes = worker_for.cat.codes.rename("_worker")
    for df in dfs:
        # split_by_worker (reference _shuffle.py:490): merge the
        # destination codes in, convert to arrow, sort, slice
        df = df.merge(right=codes, left_on="key", right_index=True,
                      how="inner")
        t = pa.Table.from_pandas(df, preserve_index=True)
        t = t.sort_by("_worker")
        wcodes = np.asarray(t["_worker"])
        t = t.drop(["_worker"])
        splits = np.where(wcodes[1:] != wcodes[:-1])[0] + 1
        splits = np.concatenate([[0], splits, [len(wcodes)]])
        for a, b in zip(splits[:-1], splits[1:]):
            if b > a:
                shard = t.slice(offset=a, length=b - a)
                # the wire format (reference _arrow.py:133
                # serialize_table): one arrow IPC stream per shard
                stream = pa.BufferOutputStream()
                with pa.ipc.new_stream(stream, shard.schema) as writer:
                    writer.write_table(shard)
                inbox[workers[wcodes[a] % 128]].append(
                    stream.getvalue().to_pybytes()
                )
    for addr, blobs in inbox.items():
        tables = []
        for blob in blobs:
            with pa.ipc.open_stream(pa.py_buffer(blob)) as reader:
                tables.append(reader.read_all())
        out = pa.concat_tables(tables).to_pandas()
        assert len(out)
    wall = time.perf_counter() - t0
    return n_rows / wall


async def cfg_shuffle():
    import numpy as np

    from distributed_tpu.client.client import Client
    from distributed_tpu.deploy.local import LocalCluster

    try:
        from distributed_tpu.shuffle.api import p2p_shuffle_arrays
        columnar = True
    except ImportError:
        from distributed_tpu.shuffle.api import p2p_shuffle
        columnar = False

    n_rows = 10_000_000 if columnar else 1_000_000
    # BASELINE.md config 4: 128 workers (in-process on this one-core
    # host; a real deployment spreads them over machines)
    n_parts = 128
    n_workers = 128
    rows_per = n_rows // n_parts

    def make_part(i, n):
        rng = np.random.default_rng(i)
        return {
            "key": rng.integers(0, 1 << 30, n).astype(np.int64),
            "value": rng.random(n),
        }

    def make_part_records(i, n):
        rng = np.random.default_rng(i)
        keys = rng.integers(0, 1 << 30, n)
        vals = rng.random(n)
        return list(zip(keys.tolist(), vals.tolist()))

    async with LocalCluster(
        n_workers=n_workers, threads_per_worker=1
    ) as cluster:
        async with Client(cluster.scheduler_address) as c:
            maker = make_part if columnar else make_part_records
            parts = c.map(maker, range(n_parts), n=rows_per)
            await c.gather(parts, errors="raise")
            t0 = time.perf_counter()
            if columnar:
                outs = await p2p_shuffle_arrays(
                    c, parts, npartitions_out=n_parts, on="key"
                )
            else:
                outs = await p2p_shuffle(c, parts, npartitions_out=n_parts)
            sizes = await c.gather(
                c.map(
                    (lambda p: len(p["key"])) if columnar else len,
                    outs,
                )
            )
            wall = time.perf_counter() - t0
    assert sum(sizes) == n_rows, (sum(sizes), n_rows)
    # apples-to-apples: the reference cannot run e2e here (no dask in
    # the image), so compare DATA PLANE vs DATA PLANE — its pandas/arrow
    # split+serialize+concat loop vs our vectorized columnar one — and
    # report our full e2e wall alongside.
    ref_rows_per_s = _reference_shuffle_dataplane_rows_per_s()
    ours_rows_per_s = _our_shuffle_dataplane_rows_per_s()
    return {
        "desc": f"P2P shuffle {n_rows} rows, {n_parts} partitions, "
        f"{n_workers} workers ({'columnar' if columnar else 'records'})",
        "n_rows": n_rows,
        "wall_s": round(wall, 3),
        "rows_per_s": round(n_rows / wall),
        "dataplane_rows_per_s": round(ours_rows_per_s),
        "ref_dataplane_rows_per_s": round(ref_rows_per_s),
        "vs_baseline": round(ours_rows_per_s / ref_rows_per_s, 2),
    }


def _our_shuffle_dataplane_rows_per_s(n_rows=2_000_000, n_parts=16,
                                      nout=128):
    """Our columnar data plane on the same workload shape as the
    reference harness above: vectorized hash split into per-destination
    shards (shuffle/columnar.py split_arrays_by_hash), the frame
    serialization the comm layer applies (protocol.serialize numpy
    family, zero-copy), and per-output concat (concat_arrays)."""
    from collections import defaultdict

    import numpy as np

    from distributed_tpu.protocol.serialize import serialize, deserialize
    from distributed_tpu.shuffle.columnar import (
        concat_arrays,
        split_arrays_by_hash,
    )

    rows_per = n_rows // n_parts
    rng = np.random.default_rng(0)
    parts = [
        {
            "key": rng.integers(0, nout, rows_per).astype(np.int64),
            "value": rng.random(rows_per),
        }
        for _ in range(n_parts)
    ]
    t0 = time.perf_counter()
    inbox: defaultdict[int, list] = defaultdict(list)
    for part in parts:
        shards = split_arrays_by_hash(part, nout, on="key")
        for j, shard in shards.items():
            # wire cost parity: serialize each column like the comm
            # layer would (numpy family header + zero-copy frame)
            blob = {c: serialize(a) for c, a in shard.items()}
            inbox[j % 128].append(blob)
    for w, blobs in inbox.items():
        shards = [
            {c: deserialize(*sb) for c, sb in blob.items()} for blob in blobs
        ]
        out = concat_arrays(shards)
        assert len(out["key"])
    wall = time.perf_counter() - t0
    return n_rows / wall


# =====================================================================
# config 5 (north star): 1M-task DAG onto 512 simulated workers with the
# level-synchronous device engine vs the stock python placement loop
# =====================================================================

N_TASKS = 1_000_000
N_WORKERS = 512
N_EDGES_PER_TASK = 2
ORACLE_SUBSET = 2_000


def build_graph(rng):
    import numpy as np

    durations = rng.uniform(0.01, 1.0, N_TASKS).astype(np.float32)
    out_bytes = rng.uniform(1e3, 1e7, N_TASKS).astype(np.float32)
    # random DAG: each task depends on up to 2 uniformly-random earlier tasks
    n_deps = rng.integers(0, N_EDGES_PER_TASK + 1, N_TASKS)
    n_deps[0] = 0
    total = int(n_deps.sum())
    dst = np.repeat(np.arange(N_TASKS), n_deps).astype(np.int32)
    src = (rng.random(total) * np.maximum(dst, 1)).astype(np.int32)
    return durations, out_bytes, src, dst


def bench_device(durations, out_bytes, src, dst):
    import numpy as np

    from distributed_tpu.ops.leveled import (
        place_graph_streamed,
        validate_leveled,
    )

    nthreads = np.full(N_WORKERS, 2, np.int32)
    occ0 = np.zeros(N_WORKERS, np.float32)
    running = np.ones(N_WORKERS, bool)

    # warm up: builds the native library and compiles every wave bucket
    # (compile excluded from the measurement, like the reference excludes
    # interpreter startup)
    packed, res = place_graph_streamed(
        durations, out_bytes, src, dst, nthreads, occ0, running,
        bandwidth=BANDWIDTH,
    )

    # streamed driver: pack fill + H2D upload + waves pipeline; only the
    # topology phase is serial (reported as "pack")
    tm: dict = {}
    t0 = time.perf_counter()
    packed, res = place_graph_streamed(
        durations, out_bytes, src, dst, nthreads, occ0, running,
        bandwidth=BANDWIDTH, timings=tm,
    )
    t2 = time.perf_counter()
    t1 = t0 + tm.get("topo_s", 0.0)

    validate_leveled(packed, res, src, dst, running)
    counts = np.bincount(res.assignment, minlength=N_WORKERS)
    return t1 - t0, t2 - t1, res.n_waves, counts


def bench_stock_python(durations, out_bytes, src, dst, n=ORACLE_SUBSET,
                       n_workers=None):
    """Stock semantics: per-task min() over all workers of
    (occupancy/nthreads + missing_bytes/bandwidth, nbytes) — the
    reference's decide_worker/worker_objective python loop."""
    import numpy as np

    N_WORKERS = n_workers or globals()["N_WORKERS"]
    occ = np.zeros(N_WORKERS)
    wnbytes = np.zeros(N_WORKERS)
    nthreads = 2
    deps: list[list[int]] = [[] for _ in range(n)]
    for s, d in zip(src, dst):
        if d < n:
            deps[d].append(s)
    placed = {}
    t0 = time.perf_counter()
    for t in range(n):
        best = None
        best_key = None
        missing_cache = {}
        for w in range(N_WORKERS):
            missing = 0.0
            for dep in deps[t]:
                if placed.get(dep) != w:
                    missing += out_bytes[dep]
            key = (occ[w] / nthreads + missing / BANDWIDTH, wnbytes[w], w)
            if best_key is None or key < best_key:
                best_key = key
                best = w
                missing_cache[w] = missing
        placed[t] = best
        occ[best] += durations[t] + missing_cache.get(best, 0.0) / BANDWIDTH
        wnbytes[best] += out_bytes[t]
    elapsed = time.perf_counter() - t0
    return elapsed / n  # seconds per task


def cfg_dag_1m():
    import jax
    import numpy as np

    rng = np.random.default_rng(0)
    durations, out_bytes, src, dst = build_graph(rng)
    pack_s, place_s, n_waves, counts = bench_device(
        durations, out_bytes, src, dst
    )
    stock_per_task = bench_stock_python(durations, out_bytes, src, dst)
    stock_total = stock_per_task * N_TASKS
    total_s = pack_s + place_s
    print(
        f"# pack {pack_s*1e3:.1f} ms + device {place_s*1e3:.1f} ms, "
        f"{n_waves} waves, load imbalance "
        f"{counts.max() / max(counts.mean(), 1):.2f}x, "
        f"stock python {stock_per_task*1e6:.0f} us/task "
        f"(extrapolated {stock_total:.0f} s for 1M)",
        file=sys.stderr,
    )
    return {
        "desc": "1M-task DAG placed on 512 simulated workers, device engine",
        "backend": jax.default_backend(),
        "pack_ms": round(pack_s * 1e3, 1),
        "device_ms": round(place_s * 1e3, 1),
        "wall_s": round(total_s, 4),
        "decisions_per_s": round(N_TASKS / total_s),
        "stock_us_per_task": round(stock_per_task * 1e6),
        "vs_baseline": round(stock_total / total_s, 1),
    }


# =====================================================================
# config 6 (dag_10m): the sharded engine headline — 10M tasks onto 4096
# MIRROR-BACKED simulated workers, one partitioned XLA program over the
# 8-device CPU mesh, same-session canary-stamped A/B vs the
# single-device engine.  Fleet size becomes a device-count knob: the
# fleet SoA rows live sharded on the mesh (scheduler/mirror.py), each
# shard receives only its task tiles (per-shard H2D), and a fresh cycle
# ships ZERO fleet rows per shard (counter-asserted below).
# =====================================================================

N10_TASKS = 10_000_000
N10_WORKERS = 4096


def build_graph_10m(rng):
    import numpy as np

    durations = rng.uniform(0.01, 1.0, N10_TASKS).astype(np.float32)
    out_bytes = rng.uniform(1e3, 1e7, N10_TASKS).astype(np.float32)
    n_deps = rng.integers(0, N_EDGES_PER_TASK + 1, N10_TASKS)
    n_deps[0] = 0
    dst = np.repeat(np.arange(N10_TASKS), n_deps).astype(np.int32)
    src = (rng.random(int(n_deps.sum())) * np.maximum(dst, 1)).astype(
        np.int32
    )
    return durations, out_bytes, src, dst


def cfg_dag_10m():
    import jax
    import numpy as np

    from distributed_tpu.ops.leveled import (
        place_graph_leveled_sharded,
        place_graph_streamed,
        validate_leveled,
    )
    from distributed_tpu.ops.partition import make_engine_mesh
    from distributed_tpu.scheduler.state import SchedulerState

    n_dev = len(jax.devices())
    assert n_dev >= 2, (
        f"dag_10m needs the multi-device CPU mesh, got {jax.devices()}"
    )
    mesh = make_engine_mesh()  # 8 -> 4x2 (tasks x workers)

    canary0 = _host_canary_ms()
    rng = np.random.default_rng(0)
    durations, out_bytes, src, dst = build_graph_10m(rng)

    # mirror-backed fleet: 4096 registered workers; the engine consumes
    # the mirror's workers-axis device shards, so the fleet never
    # re-crosses the wire once resident
    state = SchedulerState()
    assert state.mirror is not None, "dag_10m needs the fleet mirror"
    for i in range(N10_WORKERS):
        state.add_worker_state(f"tcp://dag10m:{i}", nthreads=2,
                               memory_limit=2**30, name=f"w{i}")
    fv = state.mirror.fleet_view()
    nthreads = fv.nthreads.copy()
    occ0 = fv.occupancy.copy()
    running = fv.running.copy()
    fleet_dev = state.mirror.sharded_device_view(mesh)
    assert fleet_dev is not None

    # --- A: single-device engine (warm, then timed) -------------------
    a_args = (durations, out_bytes, src, dst, nthreads, occ0, running)
    packed, res_a = place_graph_streamed(*a_args, bandwidth=BANDWIDTH)
    tm_a: dict = {}
    t0 = time.perf_counter()
    packed, res_a = place_graph_streamed(
        *a_args, bandwidth=BANDWIDTH, timings=tm_a
    )
    wall_a = time.perf_counter() - t0

    # --- B: sharded engine (warm, then timed) -------------------------
    stats_w: dict = {}
    _, res_b = place_graph_streamed(
        *a_args, bandwidth=BANDWIDTH, mesh=mesh,
        fleet_dev=state.mirror.sharded_device_view(mesh), stats=stats_w,
    )
    shard_before = state.mirror.sharded_stats()
    stats_b: dict = {}
    tm_b: dict = {}
    t0 = time.perf_counter()
    _, res_b = place_graph_streamed(
        *a_args, bandwidth=BANDWIDTH, timings=tm_b, mesh=mesh,
        fleet_dev=state.mirror.sharded_device_view(mesh), stats=stats_b,
    )
    wall_b = time.perf_counter() - t0
    shard_after = state.mirror.sharded_stats()

    # fresh-cycle zero fleet H2D, PER SHARD: nothing mutated the fleet
    # between the warm and timed sharded runs, so no shard may have
    # received a row (and none may have been re-packed wholesale)
    assert shard_after["rows_uploaded"] == shard_before["rows_uploaded"], (
        shard_before, shard_after,
    )
    assert shard_after["full_packs"] == shard_before["full_packs"], (
        shard_before, shard_after,
    )

    validate_leveled(packed, res_b, src, dst, running)
    # every shard sums each wave's load terms in task order, as the
    # single-device engine does: the placements are identical at any
    # scale (a psum of per-shard partials was not: 0.73 here, PR 8)
    agreement = float((res_a.assignment == res_b.assignment).mean())
    assert agreement == 1.0, f"sharded placement differs: {agreement}"
    counts_a = np.bincount(res_a.assignment, minlength=len(nthreads))
    counts = np.bincount(res_b.assignment, minlength=len(nthreads))
    imb_a = float(counts_a.max() / max(counts_a.mean(), 1))
    imb_b = float(counts.max() / max(counts.mean(), 1))
    stock_per_task = bench_stock_python(
        durations, out_bytes, src, dst, n=500, n_workers=N10_WORKERS
    )
    canary1 = _host_canary_ms()
    print(
        f"# dag_10m: single-device {wall_a:.2f} s vs sharded "
        f"{wall_b:.2f} s over {stats_b.get('n_shards')} shards "
        f"({stats_b.get('runs')} fused runs), agreement "
        f"{agreement:.4f}, canary {canary0:.0f}/{canary1:.0f} ms",
        file=sys.stderr,
    )
    return {
        "desc": (
            "10M-task DAG onto 4096 mirror-backed workers: sharded "
            "engine over the device mesh vs single-device, same session"
        ),
        "backend": jax.default_backend(),
        "n_devices": n_dev,
        "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
        "single_wall_s": round(wall_a, 3),
        "sharded_wall_s": round(wall_b, 3),
        "wall_s": round(wall_b, 3),
        "sharded_topo_s": round(tm_b.get("topo_s", 0.0), 3),
        "decisions_per_s": round(N10_TASKS / wall_b),
        "agreement": round(agreement, 5),
        "load_imbalance_single": round(imb_a, 4),
        "load_imbalance": round(imb_b, 4),
        "engine_shards": stats_b.get("shards"),
        "mirror_shards": shard_after,
        "fleet_h2d_rows_fresh_cycle": sum(
            a - b
            for a, b in zip(
                shard_after["rows_uploaded"], shard_before["rows_uploaded"]
            )
        ),
        "stock_us_per_task": round(stock_per_task * 1e6),
        "host_canary_ms": round((canary0 + canary1) / 2, 1),
    }


def _sim_10k_once(seed: int, native: bool | None = None):
    """One 1M-task / 10k-virtual-worker run through the real engines on
    the virtual clock; returns (report, digest)."""
    from distributed_tpu.sim import ClusterSim, SyntheticDag

    sim = ClusterSim(
        10_000, nthreads=1, seed=seed, validate=False, native=native,
        # per-link telemetry would build ~10^5 native t-digests at this
        # fleet scale; the headline measures the engines, not telemetry
        config_overrides={"scheduler.telemetry.enabled": False},
    )
    sim.install_digest()
    trace = SyntheticDag(
        n_layers=50, layer_width=20_000, fanin=2, seed=seed,
        layers_per_chunk=2, n_roots=10_000,
        # independent chunk-graphs: completed chunks FORGET, so
        # resident TaskStates stay bounded at a few chunks instead of
        # pinning the whole 1M chain (docs/simulator.md)
        linked_chunks=False,
    )
    t0 = time.perf_counter()
    trace.start(sim)
    report = sim.run()
    report["wall_s"] = round(time.perf_counter() - t0, 1)
    report["n_tasks"] = trace.n_tasks
    report["engine_wall_s"] = round(
        sim.state.wall.totals.get("engine.drain", 0.0), 1
    )
    if sim.state.native is not None:
        report["native"] = sim.state.native.counters()
    digest = sim.digest()
    # quiesce-clean proof at the 1M-task scale (docs/observability.md
    # "State census & retention"): release everything, drain, require
    # zero retained TaskStates and zero non-allowlisted residue across
    # the scheduler + all 10k worker censuses — the bounded-memory
    # oracle the ROADMAP 5(b) fuzzer asserts.  AFTER digest capture:
    # the teardown cascade folds into the running digest.
    from distributed_tpu.sim.validate import check_census_clean

    report["census"] = check_census_clean(sim)
    return report, digest


def cfg_sim_10k():
    """Simulator headline (ROADMAP item 1): place-and-run a 1M-task
    layered graph on 10,000 REAL WorkerState machines + the REAL
    scheduler engine with steal + AMM cycles, single process, virtual
    clock — twice with the same seed: run 1 with the native transition
    engine attached (the config default), run 2 forced onto the pure-
    python oracle.  The virtual makespan and the whole-run transition
    digest must be BIT-IDENTICAL between the two runs — the same-seed
    determinism contract now doubles as the native engine's at-scale
    parity gate (docs/native_engine.md)."""
    rep1, digest1 = _sim_10k_once(seed=0, native=True)
    assert rep1.get("native"), (
        "run 1 did not attach the native engine — the parity gate "
        "would compare oracle against oracle"
    )
    rep2, digest2 = _sim_10k_once(seed=0, native=False)
    assert digest1 == digest2, (
        f"sim_10k native-vs-oracle digests diverged: {digest1} vs "
        f"{digest2}"
    )
    assert rep1["virtual_makespan_s"] == rep2["virtual_makespan_s"], (
        rep1["virtual_makespan_s"], rep2["virtual_makespan_s"],
    )
    assert rep1["keys_done"] >= rep1["keys_wanted"] > 0, rep1
    transitions = (
        rep1["scheduler_transitions"] + rep1["worker_transitions"]
    )
    return {
        "n_tasks": rep1["n_tasks"],
        "n_workers": rep1["n_workers"],
        "virtual_makespan_s": rep1["virtual_makespan_s"],
        "wall_s": [rep1["wall_s"], rep2["wall_s"]],
        "transitions": transitions,
        # transitions/s is the headline the native engine is judged on
        # (ROADMAP item 4); decisions_per_s is the same value under its
        # pre-existing name (one shared local, so they cannot drift)
        "transitions_per_s": (tps := round(transitions / rep1["wall_s"])),
        "scheduler_engine_wall_s": [
            rep1["engine_wall_s"], rep2["engine_wall_s"],
        ],
        "native": rep1.get("native"),
        "decisions_per_s": tps,
        "steals": rep1["steals"],
        "amm_cycles": rep1["counters"].get("amm_cycles", 0),
        "steal_cycles": rep1["counters"].get("steal_cycles", 0),
        "events": rep1["events"],
        "digest": digest1,
        "deterministic": True,
        # the 1M-task quiesce-clean proof (both runs pass or raise)
        "census": rep1["census"],
        "host_canary_ms": _host_canary_ms(),
    }


# =====================================================================
# smoke mode: seconds-scale, CPU-pinned miniatures of the live-path and
# placement-path configs, run by a tier-1 test on every PR so the perf
# plumbing (batched transition engine, coalesced streams, chunked
# pack/upload) is exercised continuously instead of only in full bench
# rounds.  Unlike the headline harness this RAISES on failure — it is a
# CI gate, not a measurement round.
# =====================================================================

SMOKE_TASKS = 120
SMOKE_DAG_TASKS = 6_000


async def _smoke_cluster() -> dict:
    from distributed_tpu.client.client import Client
    from distributed_tpu.deploy.local import LocalCluster
    from distributed_tpu.graph.spec import Graph, TaskRef, TaskSpec

    async with LocalCluster(n_workers=2, threads_per_worker=1) as cluster:
        async with Client(cluster.scheduler_address) as c:
            # trivial-task flood: exercises task-finished batch dispatch
            # and the payload-boundary send coalescer
            t0 = time.perf_counter()
            await c.gather(c.map(_inc, range(SMOKE_TASKS)))
            flood_wall = time.perf_counter() - t0
            # small dependent graph: compute-task batches + free/release
            g = Graph()
            for i in range(24):
                g.tasks[f"src-{i}"] = TaskSpec(_inc, (i,))
                g.tasks[f"dep-{i}"] = TaskSpec(_inc, (TaskRef(f"src-{i}"),))
            level = [f"dep-{i}" for i in range(24)]
            g.tasks["root"] = TaskSpec(
                _sum_list, ([TaskRef(k) for k in level],)
            )
            t0 = time.perf_counter()
            futs = c.compute_graph(g, ["root"])
            result = await futs["root"].result()
            graph_wall = time.perf_counter() - t0
            assert result == sum(range(24)) + 48, result
    return {
        "n_tasks": SMOKE_TASKS + len(g.tasks),
        "flood_wall_s": round(flood_wall, 3),
        "graph_wall_s": round(graph_wall, 3),
        "overhead_us_per_task": round(flood_wall / SMOKE_TASKS * 1e6),
    }


def _smoke_placement() -> dict:
    import numpy as np

    from distributed_tpu.ops.leveled import (
        place_graph_streamed,
        validate_leveled,
    )

    rng = np.random.default_rng(0)
    T, W = SMOKE_DAG_TASKS, 32
    durations = rng.uniform(0.01, 1.0, T).astype(np.float32)
    out_bytes = rng.uniform(1e3, 1e7, T).astype(np.float32)
    n_deps = rng.integers(0, 3, T)
    n_deps[0] = 0
    dst = np.repeat(np.arange(T), n_deps).astype(np.int32)
    src = (rng.random(len(dst)) * np.maximum(dst, 1)).astype(np.int32)
    nthreads = np.full(W, 2, np.int32)
    occ0 = np.zeros(W, np.float32)
    running = np.ones(W, bool)
    t0 = time.perf_counter()
    packed, res = place_graph_streamed(
        durations, out_bytes, src, dst, nthreads, occ0, running,
        bandwidth=BANDWIDTH, chunk_rows=2048, min_stream=1,
    )
    wall = time.perf_counter() - t0
    validate_leveled(packed, res, src, dst, running)
    return {
        "n_tasks": T,
        "wall_s": round(wall, 3),
        "n_waves": int(res.n_waves),
    }


def _smoke_mirror() -> dict:
    """Mirror-fed steal + AMM cycle on a 64-worker synthetic fleet: the
    persistent SoA mirror (scheduler/mirror.py) feeds both device
    kernels with zero from-scratch Python packs; raises if a cycle fell
    back to the oracle pack or re-uploaded the whole fleet."""
    from distributed_tpu.scheduler.amm import (
        ActiveMemoryManagerExtension,
        ReduceReplicas,
    )
    from distributed_tpu.scheduler.state import SchedulerState
    from distributed_tpu.scheduler.stealing import WorkStealing
    from distributed_tpu.utils.test import StubScheduler

    state = SchedulerState(validate=True)
    assert state.mirror is not None, "mirror disabled in smoke config"
    sched = StubScheduler(state)
    for i in range(64):
        state.add_worker_state(f"tcp://smoke:{i}", nthreads=1,
                               memory_limit=2**30, name=f"w{i}")
    # after the fleet exists: WorkStealing's init registers the per-
    # worker stealable levels for current workers
    stealing_ext = WorkStealing(sched)
    amm = ActiveMemoryManagerExtension(
        sched, policies=[ReduceReplicas()], register=False, start=False
    )
    workers = list(state.workers.values())
    w0 = workers[0]
    # steal half: a 200-task pile pinned to w0 (loose restrictions)
    from distributed_tpu.graph.spec import TaskSpec

    state.new_task_prefix("smk").add_duration(0.05)
    tasks = {f"smk-{i}": TaskSpec(_inc, (i,)) for i in range(200)}
    state.update_graph_core(
        tasks, {k: set() for k in tasks}, list(tasks), client="smoke",
        annotations_by_key={
            k: {"workers": [w0.address], "allow_other_workers": True}
            for k in tasks
        },
        stimulus_id="smoke-steal",
    )
    idle = [ws for ws in state.idle.values() if ws in state.running]
    t0 = time.perf_counter()
    stealing_ext._balance_device(idle)  # no loop: plans inline
    steal_wall = time.perf_counter() - t0
    n_steals = len(stealing_ext.in_flight)
    assert n_steals > 0, "device balance planned no steals"
    # AMM half: 72 over-replicated keys -> device drop selection
    for i in range(72):
        key = f"rep-{i}"
        state.new_task(key, None).priority = (0,)
        state._transition(key, "memory", "smoke-amm", nbytes=1_000,
                          worker=w0.address)
        for ws in workers[1 + i % 8: 4 + i % 8]:
            state.add_replica(state.tasks[key], ws)
    t0 = time.perf_counter()
    amm.run_once()
    amm_wall = time.perf_counter() - t0
    n_drops = sum(
        len(msg.get("keys", ()))
        for _, wmsgs in sched.sent
        for msgs in wmsgs.values()
        for msg in msgs
        if msg.get("op") == "remove-replicas"
    )
    assert n_drops > 0, "AMM device round dropped nothing"
    stats = state.mirror.stats()
    assert stats["oracle_packs"] == 0, stats
    assert stats["oracle_failures"] == 0, stats
    # device residency: at most the one initial whole-cache upload
    assert stats["full_uploads"] <= 1, stats
    state.mirror.verify()
    return {
        "n_workers": 64,
        "n_steals": n_steals,
        "n_drops": n_drops,
        "steal_cycle_s": round(steal_wall, 3),
        "amm_cycle_s": round(amm_wall, 3),
        "mirror": stats,
    }


def _smoke_mesh() -> dict:
    """Sharded-engine gate on the 8-device CPU mesh (the same
    ``xla_force_host_platform_device_count`` fallback conftest uses):

    - the 1x1 mesh must reproduce the single-device engine
      BIT-IDENTICALLY (the sharded path is the identity refactor there);
    - the full mesh, fed the MIRROR's workers-axis fleet shards, must
      agree with the single-device placements;
    - a fresh second cycle must ship ZERO fleet rows on every shard and
      must not re-pack any shard wholesale.

    Raises on any violation — this is the CI gate for the dag_10m
    architecture at seconds scale.
    """
    import jax
    import numpy as np

    from distributed_tpu.ops.leveled import (
        pack_graph,
        place_graph_leveled,
        place_graph_leveled_sharded,
        validate_leveled,
    )
    from distributed_tpu.ops.partition import make_engine_mesh
    from distributed_tpu.scheduler.state import SchedulerState

    assert len(jax.devices()) >= 2, (
        f"mesh smoke needs the multi-device CPU mesh, got {jax.devices()}"
    )
    T, W = SMOKE_DAG_TASKS, 64
    rng = np.random.default_rng(5)
    durations = rng.uniform(0.01, 1.0, T).astype(np.float32)
    out_bytes = rng.uniform(1e3, 1e7, T).astype(np.float32)
    n_deps = rng.integers(0, 3, T)
    n_deps[0] = 0
    dst = np.repeat(np.arange(T), n_deps).astype(np.int32)
    src = (rng.random(len(dst)) * np.maximum(dst, 1)).astype(np.int32)
    packed = pack_graph(durations, out_bytes, src, dst,
                        bandwidth=BANDWIDTH)

    state = SchedulerState()
    assert state.mirror is not None, "mirror disabled in smoke config"
    for i in range(W):
        state.add_worker_state(f"tcp://mesh:{i}", nthreads=2,
                               memory_limit=2**30, name=f"w{i}")
    fv = state.mirror.fleet_view()
    nthreads = fv.nthreads.copy()
    occ0 = fv.occupancy.copy()
    running = fv.running.copy()

    res_1d = place_graph_leveled(packed, nthreads, occ0, running)

    # identity refactor: 1x1 mesh, bit-identical
    mesh1 = make_engine_mesh(layout="1x1")
    r11 = place_graph_leveled_sharded(mesh1, packed, nthreads, occ0,
                                      running)
    assert np.array_equal(r11.assignment, res_1d.assignment), (
        "1x1 sharded engine is not the identity refactor"
    )
    assert np.array_equal(r11.choice, res_1d.choice)

    # full mesh, mirror-resident fleet
    mesh = make_engine_mesh()
    stats: dict = {}
    t0 = time.perf_counter()
    r_sh = place_graph_leveled_sharded(
        mesh, packed, nthreads, occ0, running,
        fleet_dev=state.mirror.sharded_device_view(mesh), stats=stats,
    )
    wall = time.perf_counter() - t0
    validate_leveled(packed, r_sh, src, dst, running)
    agreement = float((r_sh.assignment == res_1d.assignment).mean())
    assert agreement == 1.0, (
        f"sharded/single-device parity divergence: {agreement:.4f}"
    )

    # fresh cycle: zero fleet H2D per shard, no wholesale re-pack
    before = state.mirror.sharded_stats()
    r_sh2 = place_graph_leveled_sharded(
        mesh, packed, nthreads, occ0, running,
        fleet_dev=state.mirror.sharded_device_view(mesh),
    )
    after = state.mirror.sharded_stats()
    assert after["rows_uploaded"] == before["rows_uploaded"], (
        f"fresh cycle scattered fleet rows per shard: {before} -> {after}"
    )
    assert after["full_packs"] == before["full_packs"], (
        f"fresh cycle re-packed a shard wholesale: {before} -> {after}"
    )
    assert np.array_equal(r_sh2.assignment, r_sh.assignment)

    return {
        "n_tasks": T,
        "n_workers": W,
        "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
        "wall_s": round(wall, 3),
        "agreement": round(agreement, 5),
        "identity_1x1": True,
        "engine_shards": stats.get("shards"),
        "mirror_shards": after,
    }


async def _smoke_wire() -> dict:
    """Wire microbench: loopback TCP echo round trips at 1 KB / 64 KB /
    8 MB frames through the real comm stack, next to a join-copy
    baseline writer over the same streams.  Raises if the zero-copy
    send contract breaks (any payload copy recorded) or the pool never
    gets a hit."""
    import numpy as np

    from distributed_tpu.comm.core import connect, listen
    from distributed_tpu.protocol.buffers import WIRE
    from distributed_tpu.protocol.serialize import Serialize

    async def echo(comm):
        try:
            while True:
                msg = await comm.read()
                await comm.write({"op": "ack", "n": msg["n"]})
        except Exception:
            pass

    listener = listen("tcp://127.0.0.1:0", echo)
    await listener.start()
    comm = await connect(listener.contact_address)
    out: dict = {"mb_s": {}}
    try:
        before = WIRE.snapshot()
        for label, size, reps in (
            ("1KB", 1024, 60), ("64KB", 65536, 30), ("8MB", 8 * 2**20, 3)
        ):
            payload = np.random.default_rng(0).integers(
                0, 256, size, dtype=np.uint8
            )
            await comm.write({"n": size, "data": Serialize(payload)})
            await comm.read()  # warm
            t0 = time.perf_counter()
            for _ in range(reps):
                await comm.write({"n": size, "data": Serialize(payload)})
                await comm.read()
            wall = time.perf_counter() - t0
            out["mb_s"][label] = round(size * reps / wall / 2**20, 1)
        after = WIRE.snapshot()
    finally:
        await comm.close()
        listener.stop()
    out["payload_copies"] = after["payload_copies"] - before["payload_copies"]
    out["pool_hits"] = after["pool_hits"] - before["pool_hits"]
    out["wire_mb"] = round((after["bytes_sent"] - before["bytes_sent"]) / 2**20, 1)
    assert out["payload_copies"] == 0, (
        f"zero-copy send contract broken: {out['payload_copies']} payload "
        f"copies on a tcp round trip"
    )
    assert out["pool_hits"] > 0, "receive pool recorded no reuse"
    return out


def _smoke_trace() -> dict:
    """Flight-recorder gate (tracing.py; docs/observability.md): floods
    the batched engine traced-on vs traced-off on identical synthetic
    states (same-session A/B, min-of-N, canary-stamped) and raises if

    - traced-on overhead exceeds 5%,
    - the fast-path ``emit`` allocates (``sys.getallocatedblocks``
      delta over a 20k-emit burst), or
    - a recorded stimulus journal replayed through the batched engine
      does not reproduce the identical transition stream.
    """
    import sys as _sys

    from distributed_tpu import config as dtpu_config
    from distributed_tpu.diagnostics.flight_recorder import (
        replay_stimulus_trace,
        transition_stream,
    )
    from distributed_tpu.graph.spec import TaskSpec
    from distributed_tpu.scheduler.state import SchedulerState

    # REPS 7: the min-per-pair estimator needs one CLEAN pair; on a
    # degraded box phase 5 pairs sometimes all read 5-15% high with
    # the feature OFF too (measured), while a real overhead shows in
    # every pair — more pairs only reduce false alarms
    N_WORKERS, N_TASKS, REPS = 16, 2000, 7

    def build(enabled):
        with dtpu_config.set({"scheduler.trace.enabled": enabled}):
            state = SchedulerState(validate=False)
            for i in range(N_WORKERS):
                state.add_worker_state(
                    f"tcp://trace:{i}", nthreads=2, memory_limit=2**30,
                    name=f"t{i}",
                )
            tasks = {f"trc-{i}": TaskSpec(_inc, (i,)) for i in range(N_TASKS)}
            state.update_graph_core(
                tasks, {k: set() for k in tasks}, list(tasks),
                client="smoke", stimulus_id="smoke-trace-graph",
            )
        return state

    def flood(state) -> float:
        """Drive every task to memory via task-finished floods, one
        batched engine pass per 'stream payload' (the processing set)."""
        t0 = time.perf_counter()
        rounds = 0
        while True:
            batch = [
                (ts.key, ws.address, f"smk-fin-{ts.key}", {"nbytes": 8})
                for ws in state.workers.values()
                for ts in list(ws.processing)
            ]
            if not batch:
                break
            state.stimulus_tasks_finished_batch(batch)
            rounds += 1
            assert rounds < 10 * N_TASKS, "flood did not converge"
        return time.perf_counter() - t0

    # A/B: one untimed warmup per arm first (the process's first flood
    # pays allocator/code warmup — without this the arm that happens to
    # run first eats it as fake overhead), then back-to-back pairs.
    # Estimator: the MINIMUM per-pair on/off ratio — a real overhead
    # shows up in every adjacent pair, while this box's one-sided floor
    # noise (±7% between two 0.1s runs, PERF.md "2x drift") does not,
    # so min-of-ratios is the drift-robust gate (min-of-walls flaked)
    flood(build(True))
    flood(build(False))
    on_walls, off_walls = [], []
    for _ in range(REPS):
        on_walls.append(flood(build(True)))
        off_walls.append(flood(build(False)))
    min_ratio = min(on / off for on, off in zip(on_walls, off_walls))
    overhead_pct = max(0.0, (min_ratio - 1.0) * 100)
    assert overhead_pct < 5.0, (
        f"traced-on overhead {overhead_pct:.1f}% exceeds the 5% budget "
        f"(on={on_walls}, off={off_walls})"
    )

    # allocation contract on the fast path: steady-state emits allocate
    # nothing (ints/floats replaced in place net to ~0 blocks).  Warm a
    # FULL ring wrap first: the first pass retires each slot's shared
    # initial 0.0 for a resident float, which is one-time ring capacity
    # cost, not per-event allocation.
    tr = build(True).trace
    for _ in range(len(tr) + tr._mask + 2):
        tr.emit("engine", "alloc-check", "smoke-alloc")
    b0 = _sys.getallocatedblocks()
    for _ in range(20_000):
        tr.emit("engine", "alloc-check", "smoke-alloc")
    alloc_delta = _sys.getallocatedblocks() - b0
    assert alloc_delta < 50, (
        f"fast-path emit allocated ({alloc_delta} blocks over 20k events)"
    )

    # record-then-replay parity: journal a flood, re-feed it through the
    # batched engine on an identically-built state, require the
    # identical transition stream (key, start, finish, stimulus, order)
    rec_state = build(True)
    mark = len(rec_state.transition_log)
    rec_state.trace.journal_start()
    flood(rec_state)
    records = list(rec_state.trace.journal)
    assert records, "journal captured nothing in record mode"
    rep_state = build(True)
    mark_b = len(rep_state.transition_log)
    replay_stimulus_trace(rep_state, records)
    recorded = transition_stream(rec_state, mark)
    replayed = transition_stream(rep_state, mark_b)
    assert recorded == replayed, (
        "replayed transition stream diverged from the recording "
        f"(recorded {len(recorded)} rows, replayed {len(replayed)})"
    )

    n_events = rec_state.trace.total
    assert n_events > 0, "traced run emitted no flight-recorder events"
    return {
        "n_workers": N_WORKERS,
        "n_tasks": N_TASKS,
        "traced_on_s": [round(w, 3) for w in on_walls],
        "traced_off_s": [round(w, 3) for w in off_walls],
        "overhead_pct": round(overhead_pct, 2),
        "alloc_delta_blocks": alloc_delta,
        "replay_match": True,
        "replay_rows": len(recorded),
        "n_events": n_events,
        "host_canary_ms": _host_canary_ms(),
    }


async def _smoke_stall_watchdog() -> dict:
    """Deterministic stall-watchdog half of the selfprofile gate: a
    synthetic loop block (a tight busy-wait INSIDE a coroutine, well
    past the threshold) must produce EXACTLY ONE stall capture whose
    traceback names the blocking frame, plus a flight-recorder
    ``stall`` event — and a recovered loop must re-arm cleanly."""
    import asyncio
    import threading

    from distributed_tpu.diagnostics.selfprofile import LoopWatchdog
    from distributed_tpu.tracing import FlightRecorder

    tr = FlightRecorder(enabled=True, ring_size=64)
    wd = LoopWatchdog(trace=tr, interval=0.02, stall_threshold=0.12)
    wd.start(threading.get_ident())

    async def ticker():
        while True:
            wd.tick()
            await asyncio.sleep(0.02)

    tick_task = asyncio.create_task(ticker())
    try:
        await asyncio.sleep(0.1)  # healthy baseline ticks

        def _block_loop():
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < 0.35:
                pass  # the synthetic stall: the loop thread is pinned here

        _block_loop()
        await asyncio.sleep(0.3)  # recovery window: watchdog re-arms
    finally:
        tick_task.cancel()
        wd.stop()
    assert wd.stalls_total == 1, (
        f"expected exactly one stall capture, got {wd.stalls_total}"
    )
    stall = wd.stalls[0]
    assert "_block_loop" in stall["traceback"], stall["traceback"]
    stall_events = [e for e in tr.tail() if e["cat"] == "stall"]
    assert len(stall_events) == 1 and "_block_loop" in stall_events[0]["key"]
    assert wd.hist_lag.count > 0
    return {
        "stall_events": wd.stalls_total,
        "stall_lag_s": stall["lag_s"],
        "stall_frame_named": True,
        "ticks": wd.ticks_total,
    }


def _smoke_selfprofile() -> dict:
    """Control-plane self-profiler gate (diagnostics/selfprofile.py;
    docs/observability.md "Self-profiling"): floods the batched engine
    with the always-on control-plane sampler ON vs OFF on identical
    synthetic states (same-session A/B, min-per-pair-ratio estimator —
    the drift-robust gate from the trace smoke) and raises if

    - sampling-on overhead exceeds 5% (the always-on contract),
    - the sampled tree carries no phase-stamped samples or the wall
      budget recorded no ``engine.drain`` seconds,
    - arm attribution (opt-in) produces no per-arm rows, or
    - the deterministic stall-watchdog scenario above fails.
    """
    import asyncio
    import threading

    from distributed_tpu.diagnostics.selfprofile import ControlPlaneProfiler
    from distributed_tpu import config as dtpu_config
    from distributed_tpu.graph.spec import TaskSpec
    from distributed_tpu.scheduler.state import SchedulerState

    # REPS 7: the min-per-pair estimator needs one CLEAN pair (see the
    # trace smoke's rationale)
    N_WORKERS, N_TASKS, REPS = 16, 2000, 7

    def build():
        state = SchedulerState(validate=False)
        for i in range(N_WORKERS):
            state.add_worker_state(
                f"tcp://prof:{i}", nthreads=2, memory_limit=2**30,
                name=f"p{i}",
            )
        tasks = {f"prf-{i}": TaskSpec(_inc, (i,)) for i in range(N_TASKS)}
        state.update_graph_core(
            tasks, {k: set() for k in tasks}, list(tasks),
            client="smoke", stimulus_id="smoke-selfprofile-graph",
        )
        return state

    def flood(state) -> float:
        t0 = time.perf_counter()
        rounds = 0
        while True:
            batch = [
                (ts.key, ws.address, f"prf-fin-{ts.key}", {"nbytes": 8})
                for ws in state.workers.values()
                for ts in list(ws.processing)
            ]
            if not batch:
                break
            state.stimulus_tasks_finished_batch(batch)
            rounds += 1
            assert rounds < 10 * N_TASKS, "flood did not converge"
        return time.perf_counter() - t0

    main_ident = threading.get_ident()

    def run(profiled: bool) -> float:
        state = build()
        prof = None
        if profiled:
            # default config rate: the gate measures the ALWAYS-ON cost
            prof = ControlPlaneProfiler(
                idents=lambda: [main_ident], wall=state.wall
            )
            prof.start()
        try:
            return flood(state)
        finally:
            if prof is not None:
                prof.stop()

    run(True)   # untimed warmup per arm (allocator/code warm)
    run(False)
    on_walls, off_walls = [], []
    for _ in range(REPS):
        on_walls.append(run(True))
        off_walls.append(run(False))
    min_ratio = min(on / off for on, off in zip(on_walls, off_walls))
    overhead_pct = max(0.0, (min_ratio - 1.0) * 100)
    assert overhead_pct < 5.0, (
        f"sampling-on overhead {overhead_pct:.1f}% exceeds the 5% budget "
        f"(on={on_walls}, off={off_walls})"
    )

    # attribution probe: a dense-rate profiled flood must produce
    # phase-stamped samples and nonzero engine.drain wall
    probe = build()
    prof = ControlPlaneProfiler(
        idents=lambda: [main_ident], wall=probe.wall, interval=0.002
    )
    prof.start()
    flood(probe)
    prof.stop()
    wall = probe.wall.snapshot()
    assert wall.get("engine.drain", 0.0) > 0.0, wall
    assert prof.total_samples > 0
    tree = prof.get_profile()
    phase_nodes = [
        k for k in tree["children"] if k.startswith("phase:engine.drain")
    ]
    assert phase_nodes, list(tree["children"])
    assert any(ph == "engine.drain" for _, ph, _s in prof.samples)

    # opt-in arm attribution: per-arm rows exist and cover most of the
    # engine wall (the sim.profile_run artifact's property); its cost
    # is REPORTED here, gated only by the profile_run tier-1 test
    with dtpu_config.set({"scheduler.profile.arm-attribution": True}):
        arm_state = build()
    arm_wall = flood(arm_state)
    totals = arm_state.wall.snapshot()
    arms = {
        k: v for k, v in totals.items()
        if k.startswith("engine.scalar-arm:")
    }
    assert arms, "arm attribution produced no per-arm rows"
    engine_wall = totals.get("engine.drain", 0.0) + sum(arms.values())
    arm_share = sum(arms.values()) / engine_wall if engine_wall else 0.0

    out = asyncio.run(_smoke_stall_watchdog())
    out.update({
        "n_workers": N_WORKERS,
        "n_tasks": N_TASKS,
        "sampling_on_s": [round(w, 3) for w in on_walls],
        "sampling_off_s": [round(w, 3) for w in off_walls],
        "overhead_pct": round(overhead_pct, 2),
        "samples": prof.total_samples,
        "engine_drain_wall_s": round(wall["engine.drain"], 4),
        "arm_rows": len(arms),
        "arm_share": round(arm_share, 3),
        "arm_flood_s": round(arm_wall, 3),
        "host_canary_ms": _host_canary_ms(),
    })
    return out


async def _smoke_telemetry_links() -> dict:
    """Measured-link half of the telemetry gate (telemetry.py): a tcp
    echo through the real comm stack files per-round-trip link samples
    through the REAL collector class workers use, and the collector's
    EWMA bandwidth must land within 2x of the bench's own observed
    MB/s.  The measured/constant ratio is reported as the Round 4
    artifact — the loopback truth vs the 100 MB/s `scheduler.bandwidth`
    constant — and must diverge by >1.5x (the "constant is ~10x off"
    finding, reproduced and checked on every PR)."""
    import numpy as np

    from distributed_tpu import config as dtpu_config
    from distributed_tpu.comm.core import connect, listen
    from distributed_tpu.protocol.serialize import Serialize
    from distributed_tpu.telemetry import LinkTelemetry
    from distributed_tpu.utils.misc import time as mono

    async def echo(comm):
        try:
            while True:
                msg = await comm.read()
                await comm.write({"op": "ack", "data": msg["data"]})
        except Exception:
            pass

    listener = listen("tcp://127.0.0.1:0", echo)
    await listener.start()
    comm = await connect(listener.contact_address)
    collector = LinkTelemetry(enabled=True)
    src, dst = listener.contact_address, "tcp://smoke-requester"
    size, reps = 4 * 2**20, 4
    payload = np.random.default_rng(0).integers(0, 256, size, dtype=np.uint8)
    try:
        await comm.write({"data": Serialize(payload)})
        await comm.read()  # warm
        t0 = time.perf_counter()
        for _ in range(reps):
            m0 = mono()
            await comm.write({"data": Serialize(payload)})
            await comm.read()
            # one round trip moves the payload BOTH ways; file the echo
            # leg as one link sample, exactly as _gather_dep files a
            # fetch (payload bytes over the full round trip)
            collector.record(src, dst, size, mono() - m0)
        wall = time.perf_counter() - t0
    finally:
        await comm.close()
        listener.stop()
    bench_bw = size * reps / wall  # bytes/s, same numerator as samples
    link = collector.links[(src, dst)]
    measured_bw = link.bandwidth.value
    n_samples = link.bandwidth.count
    assert n_samples == reps and link.bytes_total == size * reps, (
        "tcp echo produced no/short link samples"
    )
    assert bench_bw / 2 <= measured_bw <= bench_bw * 2, (
        f"collector EWMA bandwidth {measured_bw / 2**20:.1f} MB/s not "
        f"within 2x of the bench's observed {bench_bw / 2**20:.1f} MB/s"
    )
    constant = float(dtpu_config.get("scheduler.bandwidth"))
    constant_ratio = measured_bw / constant
    assert constant_ratio > 1.5 or constant_ratio < 1 / 1.5, (
        f"loopback measured bandwidth {measured_bw / 2**20:.1f} MB/s "
        f"does not diverge >1.5x from the scheduler.bandwidth constant "
        f"({constant / 2**20:.1f} MB/s) — the Round 4 artifact "
        f"disappeared; re-examine the constant"
    )
    # heartbeat-delta encode/fold round trip stays intact
    rows = collector.rows(collector.take())
    assert rows and rows[0][4] == reps
    return {
        "n_link_samples": n_samples,
        "measured_mb_s": round(measured_bw / 2**20, 1),
        "bench_mb_s": round(bench_bw / 2**20, 1),
        "bw_within_2x": True,
        "constant_ratio": round(constant_ratio, 2),
    }


def _smoke_telemetry() -> dict:
    """Telemetry gate (telemetry.py; docs/observability.md): measured
    link samples off a real tcp echo (above), plus the shadow-monitor
    overhead contract — telemetry-on vs -off engine floods on identical
    synthetic states, gated <5% with the MIN PER-PAIR RATIO estimator
    (the drift-robust A/B from the trace smoke)."""
    import asyncio

    from distributed_tpu import config as dtpu_config
    from distributed_tpu.graph.spec import TaskSpec
    from distributed_tpu.scheduler.state import SchedulerState

    out = asyncio.run(_smoke_telemetry_links())

    # REPS 7: the min-per-pair estimator needs one CLEAN pair; on a
    # degraded box phase 5 pairs sometimes all read 5-15% high with
    # the feature OFF too (measured), while a real overhead shows in
    # every pair — more pairs only reduce false alarms
    N_WORKERS, N_TASKS, REPS = 16, 2000, 7
    addrs = [f"tcp://tel:{i}" for i in range(N_WORKERS)]

    def build(enabled):
        with dtpu_config.set({"scheduler.telemetry.enabled": enabled}):
            state = SchedulerState(validate=False)
        for i, a in enumerate(addrs):
            state.add_worker_state(
                a, nthreads=2, memory_limit=2**30, name=f"t{i}"
            )
        if enabled:
            # measured links exist so the shadow evals take the
            # real (per-dep link scan) path, not the cheap fallback
            state.telemetry.fold_rows(
                [[addrs[i], addrs[(i + 1) % N_WORKERS],
                  1_000_000_000, 1.0, 4] for i in range(N_WORKERS)],
                reporter="",
            )
        tasks = {f"tlm-{i}": TaskSpec(_inc, (i,)) for i in range(N_TASKS)}
        deps: dict = {f"tlm-{i}": set() for i in range(N_TASKS)}
        for i in range(0, N_TASKS, 4):
            tasks[f"tld-{i}"] = TaskSpec(_inc, (i,))
            deps[f"tld-{i}"] = {f"tlm-{i}", f"tlm-{(i + 1) % N_TASKS}"}
        state.update_graph_core(
            tasks, deps, list(tasks), client="smoke",
            stimulus_id="smoke-telemetry-graph",
        )
        return state

    def flood(state) -> float:
        t0 = time.perf_counter()
        rounds = 0
        while True:
            batch = [
                (ts.key, ws.address, f"tel-fin-{ts.key}", {"nbytes": 8})
                for ws in state.workers.values()
                for ts in list(ws.processing)
            ]
            if not batch:
                break
            state.stimulus_tasks_finished_batch(batch)
            rounds += 1
            assert rounds < 10 * N_TASKS, "flood did not converge"
        return time.perf_counter() - t0

    flood(build(True))   # untimed warmup per arm (allocator/code warm)
    flood(build(False))
    on_walls, off_walls = [], []
    for _ in range(REPS):
        on_walls.append(flood(build(True)))
        off_walls.append(flood(build(False)))
    min_ratio = min(on / off for on, off in zip(on_walls, off_walls))
    overhead_pct = max(0.0, (min_ratio - 1.0) * 100)
    assert overhead_pct < 5.0, (
        f"telemetry-on overhead {overhead_pct:.1f}% exceeds the 5% "
        f"budget (on={on_walls}, off={off_walls})"
    )
    probe = build(True)
    flood(probe)
    assert probe.telemetry.shadow_evals > 0, (
        "telemetry-on flood performed no shadow evaluations"
    )
    assert probe.telemetry.hist_divergence.count > 0
    out.update({
        "n_workers": N_WORKERS,
        "n_tasks": N_TASKS,
        "telemetry_on_s": [round(w, 3) for w in on_walls],
        "telemetry_off_s": [round(w, 3) for w in off_walls],
        "overhead_pct": round(overhead_pct, 2),
        "shadow_evals": probe.telemetry.shadow_evals,
        "shadow_measured": probe.telemetry.shadow_measured,
        "host_canary_ms": _host_canary_ms(),
    })
    return out


def _smoke_sim() -> dict:
    """Simulator gate (distributed_tpu/sim; docs/simulator.md): the
    tier-1 miniature of ``sim_10k``.  Raises if

    - two same-seed runs (48 virtual workers, ~1k tasks, steal + AMM
      cycles live) do not produce BIT-IDENTICAL whole-run digests,
      transition-stream digests, and virtual makespans — the
      determinism contract every sim-based perf gate rests on;
    - a worker-death chaos run loses a key or leaves the replica model
      disagreeing with the fleet;
    - a journal recorded from a simulated run does not replay through
      the batched engine to the identical transition stream (the
      sim <-> live replay-format contract, docs/observability.md).
    """
    from distributed_tpu.diagnostics.flight_recorder import (
        replay_stimulus_trace,
        transition_stream,
    )
    from distributed_tpu.sim import ClusterSim, SyntheticDag
    from distributed_tpu.sim.chaos import scenario_worker_death
    from distributed_tpu.sim.validate import check_no_lost_keys

    N_WORKERS, LAYERS, WIDTH = 48, 12, 90

    def build(run_periodics=True, layers=LAYERS, chunk=3):
        sim = ClusterSim(
            N_WORKERS, seed=0, validate=True,
            steal_interval=None if run_periodics else 0,
            amm_interval=None if run_periodics else 0,
            find_missing_interval=1.0 if run_periodics else 0,
        )
        sim.install_digest()
        trace = SyntheticDag(
            n_layers=layers, layer_width=WIDTH, fanin=2, seed=0,
            layers_per_chunk=chunk,
        )
        return sim, trace

    t0 = time.perf_counter()
    sim1, tr1 = build()
    tr1.start(sim1)
    rep1 = sim1.run()
    wall = time.perf_counter() - t0
    check_no_lost_keys(sim1)
    sim2, tr2 = build()
    tr2.start(sim2)
    rep2 = sim2.run()
    check_no_lost_keys(sim2)
    assert sim1.digest() == sim2.digest(), (
        f"same-seed sim digests diverged: {sim1.digest()} {sim2.digest()}"
    )
    assert rep1["virtual_makespan_s"] == rep2["virtual_makespan_s"], (
        rep1["virtual_makespan_s"], rep2["virtual_makespan_s"],
    )

    # chaos mini: deterministic worker death converges with no lost keys
    _csim, crep = scenario_worker_death(seed=1, n_workers=12)
    assert crep["keys_done"] >= crep["keys_wanted"], crep

    # record -> replay parity: a sim-captured stimulus journal re-fed
    # through the batched engine reproduces the identical stream.
    # Single-chunk workload: the journal records ENGINE stimuli, so the
    # replay state must be structurally identical up front — chunked
    # submission materializes tasks mid-run, outside the contract
    # (docs/observability.md "replayable stimulus-trace format")
    rsim, rtrace = build(run_periodics=False, layers=5, chunk=5)
    rtrace.start(rsim)
    mark = len(rsim.state.transition_log)
    rsim.journal_start()
    rsim.run()
    records = rsim.journal()
    assert records, "sim journal captured nothing"
    psim, ptrace = build(run_periodics=False, layers=5, chunk=5)
    ptrace.start(psim)
    mark_p = len(psim.state.transition_log)
    replay_stimulus_trace(psim.state, records)
    recorded = transition_stream(rsim.state, mark)
    replayed = transition_stream(psim.state, mark_p)
    assert recorded == replayed, (
        f"sim journal replay diverged ({len(recorded)} vs "
        f"{len(replayed)} rows)"
    )

    transitions = rep1["scheduler_transitions"] + rep1["worker_transitions"]
    return {
        "n_workers": N_WORKERS,
        "n_tasks": LAYERS * WIDTH,
        "virtual_makespan_s": rep1["virtual_makespan_s"],
        "wall_s": round(wall, 2),
        "transitions": transitions,
        "decisions_per_s": round(transitions / wall),
        "steals": rep1["steals"],
        "digest": sim1.digest(),
        "deterministic": True,
        "chaos_death_lost": crep["keys_lost"],
        "replay_match": True,
        "replay_rows": len(recorded),
    }


async def _hard_kill_scheduler(s) -> None:
    """Crash, not close: abort every stream/comm/callback WITHOUT the
    graceful protocol (no close-worker ops, no final durability
    snapshot) — the durable image is whatever already hit disk.  The
    in-process approximation of kill -9 on the scheduler."""
    from distributed_tpu.rpc.core import Status

    s.status = Status.closing  # stops the comm loops mid-read
    for pc in s.periodic_callbacks.values():
        pc.stop()
    s.periodic_callbacks.clear()
    if s.watchdog is not None:
        s.watchdog.stop()
    if s.cp_profiler is not None:
        s.cp_profiler.stop()
    for listener in s.listeners:
        listener.stop()
    for bs in list(s.stream_comms.values()):
        bs.abort()
    s.stream_comms.clear()
    for bs in list(s.client_comms.values()):
        bs.abort()
    s.client_comms.clear()
    for comm in list(s._comms):
        try:
            comm.abort()
        except Exception:
            pass
    await s._ongoing_background_tasks.stop()
    await s.rpc.close()
    if s.http_server is not None:
        await s.http_server.stop()
    s.status = Status.closed
    s._event_finished.set()


async def _smoke_restart_live() -> dict:
    """Live half of the restart gate (scheduler/durability.py;
    docs/durability.md): a real TCP cluster computes 40 keys, the
    scheduler snapshots and is then HARD-bounced (comms aborted, no
    graceful close); a fresh scheduler process-equivalent restarts on
    the same port from snapshot + journal tail, the workers reconnect
    with backoff+jitter carrying their held keys, and the gate asserts

    - ZERO lost completed keys: every pre-bounce memory key is memory
      with a live worker replica on the restarted scheduler;
    - recovery under budget: restore + full worker re-registration
      completes within the (generous, hang-guarding) RTO deadline;
    - liveness: a fresh client computes new work against the restarted
      scheduler.
    """
    import asyncio
    import shutil
    import tempfile

    from distributed_tpu import config as dtpu_config
    from distributed_tpu.client.client import Client
    from distributed_tpu.scheduler.server import Scheduler
    from distributed_tpu.worker.server import Worker

    tmp = tempfile.mkdtemp(prefix="dtpu-smoke-restart-")
    overrides = {
        "scheduler.jax.enabled": False,
        "scheduler.durability.directory": tmp,
        "scheduler.durability.snapshot-interval": "500ms",
        "scheduler.durability.flush-interval": "50ms",
        "scheduler.durability.grace": "15s",
        "worker.reconnect-attempts": 40,
        "worker.register.base-delay": "50ms",
        "worker.register.max-delay": "250ms",
    }
    N = 40
    workers: list = []
    s2 = None
    c = None
    try:
        with dtpu_config.set(overrides):
            s1 = Scheduler(listen_addr="tcp://127.0.0.1:0", validate=True)
            await s1.start()
            addr = s1.address
            for i in range(2):
                w = Worker(addr, name=f"rw{i}", nthreads=1, validate=True,
                           listen_addr="tcp://127.0.0.1:0")
                await w.start()
                workers.append(w)
            c = Client(addr)
            await c.__aenter__()
            futs = c.map(_inc, range(N))
            res = await c.gather(futs)
            assert res == list(range(1, N + 1)), res[:5]
            # one explicit epoch now, then MORE completed work so the
            # crash leaves a real journal tail: the second batch's graph
            # intake and completions are durable only as tail records
            s1.durability.snapshot()
            futs2 = c.map(_inc, range(N, N + 10))
            res2 = await c.gather(futs2)
            assert res2 == list(range(N + 1, N + 11)), res2
            pre_keys = sorted(
                k for k, ts in s1.state.tasks.items()
                if ts.state == "memory"
            )
            assert len(pre_keys) >= N + 10, pre_keys
            s1.durability.flush_journal()
            t_kill = time.perf_counter()
            await _hard_kill_scheduler(s1)
            s1.durability.sink.drain()  # queued writes had hit disk pre-crash

            # restart on the SAME port: the workers' reconnect loop is
            # already probing it with backoff + jitter
            s2 = Scheduler(listen_addr=addr, validate=True)
            await s2.start()
            restore_s = s2.durability.stats.restore_seconds
            assert restore_s > 0, "restart did not restore from the sink"
            assert s2.durability.stats.replay_records > 0, (
                "the bounce left no journal tail — the gate must "
                "exercise snapshot + TAIL replay, not snapshot alone"
            )
            worker_addrs = {w.address for w in workers}
            deadline = time.perf_counter() + 30
            lost: list = list(pre_keys)
            while time.perf_counter() < deadline:
                lost = [
                    k for k in pre_keys
                    if (ts := s2.state.tasks.get(k)) is None
                    or ts.state != "memory" or not ts.who_has
                ]
                reregistered = worker_addrs <= set(s2.stream_comms)
                if not lost and reregistered:
                    break
                await asyncio.sleep(0.05)
            rto_live = time.perf_counter() - t_kill
            assert not lost, (
                f"{len(lost)} completed keys lost across the bounce: "
                f"{lost[:5]}"
            )
            assert worker_addrs <= set(s2.stream_comms), (
                "workers never re-registered", sorted(s2.stream_comms)
            )
            assert rto_live < 30, f"recovery took {rto_live:.1f}s"
            # liveness: fresh work through the restarted control plane
            async with Client(addr) as c2:
                res2 = await c2.gather(c2.map(_inc, range(100, 110)))
                assert res2 == list(range(101, 111)), res2
            return {
                "pre_keys": len(pre_keys),
                "lost_completed_keys": 0,
                "rto_live_s": round(rto_live, 3),
                "restore_s": round(restore_s, 4),
                "replay_records": s2.durability.stats.replay_records,
                "torn_records": s2.durability.stats.torn_records,
                "workers_reregistered": len(worker_addrs),
                "liveness_ok": True,
            }
    finally:
        if c is not None:
            try:
                await asyncio.wait_for(c.close(), 5)
            except Exception:
                pass
        for w in workers:
            try:
                await w.close(report=False)
            except Exception:
                pass
        if s2 is not None:
            await s2.close()
        shutil.rmtree(tmp, ignore_errors=True)


def _smoke_restart_capture() -> dict:
    """Synthetic half of the restart gate: steady-state capture
    overhead + the measured-RTO curve.

    - **Overhead**: durability armed (dirty tracker + journal-segment
      capture — the always-on, every-flood cost) vs off on identical
      engine floods, min-per-pair-ratio (the drift-robust estimator
      from the trace smoke) must stay under 5%.  Snapshot ENCODE cost
      is deliberately off the timed path here: it is the periodic
      O(changed-rows) cost, measured and reported below, and amortized
      by the snapshot-interval (default 5s) in production — the
      reported ``amortized_snapshot_pct`` pins that claim.
    - **RTO curve**: the same flood captured at three snapshot
      cadences (many deltas / few deltas / base-only) restores into a
      fresh state — fold + rebuild + digest-verify + tail replay —
      and each point reports (epochs, tail records, restore seconds),
      with the restored state digest-identical to the original.
    """
    from distributed_tpu import config as dtpu_config
    from distributed_tpu.graph.spec import TaskSpec
    from distributed_tpu.scheduler.durability import (
        DurabilityManager,
        MemorySink,
        state_digest,
    )
    from distributed_tpu.scheduler.state import SchedulerState

    N_WORKERS, N_TASKS, REPS = 16, 2000, 7

    def build(enabled):
        with dtpu_config.set({"scheduler.trace.enabled": False}):
            state = SchedulerState(validate=False)
            for i in range(N_WORKERS):
                state.add_worker_state(
                    f"tcp://restart:{i}", nthreads=2, memory_limit=2**30,
                    name=f"r{i}",
                )
            tasks = {
                f"rst-{i}": TaskSpec(_inc, (i,)) for i in range(N_TASKS)
            }
            state.update_graph_core(
                tasks, {k: set() for k in tasks}, list(tasks),
                client="smoke", stimulus_id="smoke-restart-graph",
            )
        mgr = None
        if enabled:
            mgr = DurabilityManager(
                state, MemorySink(), full_every=10**6, state_digests=True,
            )
            mgr.attach()
        return state, mgr

    def flood(state, mgr=None, cadence=0) -> float:
        t0 = time.perf_counter()
        rounds = 0
        while True:
            batch = [
                (ts.key, ws.address, f"smk-fin-{ts.key}", {"nbytes": 8})
                for ws in state.workers.values()
                for ts in list(ws.processing)
            ]
            if not batch:
                break
            state.stimulus_tasks_finished_batch(batch)
            rounds += 1
            if mgr is not None and cadence and rounds % cadence == 0:
                mgr.snapshot()
            assert rounds < 10 * N_TASKS, "flood did not converge"
        return time.perf_counter() - t0

    # A/B: untimed warmup per arm, then adjacent pairs; min-of-ratios
    flood(*build(True))
    flood(build(False)[0])
    on_walls, off_walls = [], []
    for _ in range(REPS):
        s, m = build(True)
        on_walls.append(flood(s, m))
        off_walls.append(flood(build(False)[0]))
    min_ratio = min(on / off for on, off in zip(on_walls, off_walls))
    overhead_pct = max(0.0, (min_ratio - 1.0) * 100)
    assert overhead_pct < 5.0, (
        f"steady-state durability capture overhead {overhead_pct:.1f}% "
        f"exceeds the 5% budget (on={on_walls}, off={off_walls})"
    )

    # measured-RTO curve: snapshot cadence (rounds per epoch) x journal
    # tail length -> restore seconds, each point digest-verified
    rto_curve = []
    snap_seconds_per_epoch = 0.0
    for cadence in (2, 8, 10**9):
        s, m = build(True)
        flood(s, m, cadence)
        m.flush_journal()
        fresh = SchedulerState(validate=False)
        t0 = time.perf_counter()
        info = DurabilityManager.restore_into(fresh, m.sink)
        restore_s = time.perf_counter() - t0
        assert state_digest(fresh) == state_digest(s), (
            f"cadence={cadence}: restored state diverged from original"
        )
        st = m.stats
        if cadence == 8:
            snap_seconds_per_epoch = st.snapshot_seconds / max(st.epochs, 1)
        rto_curve.append({
            "cadence_rounds": min(cadence, 10**6),
            "epochs": st.epochs,
            "snapshot_rows": st.snapshot_rows,
            "snapshot_s": round(st.snapshot_seconds, 4),
            "tail_records": info["tail_records"],
            "restore_s": round(restore_s, 4),
            "digest_ok": True,
        })
    # shorter tails must not come from serializing the world every
    # epoch: the deltas stay O(changed) — total rows across ALL the
    # fine-cadence epochs stay within a small multiple of the table
    fine = rto_curve[0]
    assert fine["snapshot_rows"] < 6 * N_TASKS, fine
    # production amortization: one delta epoch per snapshot-interval
    default_interval = dtpu_config.parse_timedelta(
        dtpu_config.get("scheduler.durability.snapshot-interval")
    )
    amortized_pct = 100.0 * snap_seconds_per_epoch / default_interval
    assert amortized_pct < 5.0, (
        f"snapshot encode {snap_seconds_per_epoch:.3f}s/epoch is "
        f"{amortized_pct:.1f}% of the default {default_interval}s cadence"
    )
    return {
        "capture_on_s": [round(w, 3) for w in on_walls],
        "capture_off_s": [round(w, 3) for w in off_walls],
        "overhead_pct": round(overhead_pct, 2),
        "snapshot_s_per_epoch": round(snap_seconds_per_epoch, 4),
        "amortized_snapshot_pct": round(amortized_pct, 3),
        "rto_curve": rto_curve,
        "host_canary_ms": _host_canary_ms(),
    }


def _smoke_restart() -> dict:
    """Scheduler-durability gate: live hard-bounce restart + synthetic
    capture-overhead / RTO-curve halves (scheduler/durability.py;
    docs/durability.md; gated in tests/test_bench_smoke.py)."""
    import asyncio

    out = asyncio.run(_smoke_restart_live())
    out.update(_smoke_restart_capture())
    return out


async def _smoke_ledger_live() -> dict:
    """Join-correctness half of the ledger gate on a SMALL LIVE
    cluster: a real flood + a dependent graph over real tcp must leave
    every placement decision joined to a realized outcome (ledger.py)
    with regret observed — the live counterpart of the simulator's
    exact-join tests."""
    from distributed_tpu.client.client import Client
    from distributed_tpu.deploy.local import LocalCluster
    from distributed_tpu.graph.spec import Graph, TaskRef, TaskSpec

    async with LocalCluster(n_workers=2, threads_per_worker=1) as cluster:
        async with Client(cluster.scheduler_address) as c:
            await c.gather(c.map(_inc, range(60)))
            g = Graph()
            for i in range(16):
                g.tasks[f"lsrc-{i}"] = TaskSpec(_inc, (i,))
                g.tasks[f"ldep-{i}"] = TaskSpec(
                    _inc, (TaskRef(f"lsrc-{i}"),)
                )
            g.tasks["lroot"] = TaskSpec(
                _sum_list, ([TaskRef(f"ldep-{i}") for i in range(16)],)
            )
            futs = c.compute_graph(g, ["lroot"])
            result = await futs["lroot"].result()
            assert result == sum(range(16)) + 32, result
            led = cluster.scheduler.state.ledger
            summary = led.summary()
            # ...and the RPC/HTTP surface serves the same snapshot
            rpc_snap = await c.scheduler.get_ledger(n=10)
    assert summary["joined"] >= 60, summary
    assert summary["unjoined"] == 0, summary
    assert summary["open"] == 0, summary
    assert summary["outcomes"].get("memory", 0) >= 60, summary
    n_regret = sum(k["count"] for k in summary["kinds"].values())
    assert n_regret > 0, summary
    assert rpc_snap and rpc_snap[0]["type"] == "ledger-summary"
    return {
        "live_joined": summary["joined"],
        "live_unjoined": summary["unjoined"],
        "live_regret_rows": n_regret,
    }


def _smoke_ledger() -> dict:
    """Decision-ledger gate (ledger.py, diagnostics/critical_path.py;
    docs/observability.md "Decision ledger & critical-path").  Raises if

    - ledger-on vs -off engine-flood overhead exceeds 5% (min-per-pair-
      ratio estimator, the drift-robust A/B from the trace smoke),
    - the steady-state file+join hot path allocates (PR 6's
      ``sys.getallocatedblocks`` gate pattern),
    - a small LIVE cluster leaves any decision unjoined (above),
    - on a telemetry-seeded NON-UNIFORM simulated fleet the measured-
      shadow model's aggregate |regret| is not lower than the
      constants' — the ROADMAP item 1 calibration artifact,
    - critical-path attribution does not sum to the sim run's virtual
      makespan within 1% (``critical_path.check``).
    """
    import asyncio
    import sys as _sys

    from distributed_tpu import config as dtpu_config
    from distributed_tpu.graph.spec import TaskSpec
    from distributed_tpu.scheduler.state import SchedulerState

    N_WORKERS, N_TASKS, REPS = 16, 2000, 7

    def build(enabled):
        with dtpu_config.set({"scheduler.ledger.enabled": enabled}):
            state = SchedulerState(validate=False)
        for i in range(N_WORKERS):
            state.add_worker_state(
                f"tcp://led:{i}", nthreads=2, memory_limit=2**30,
                name=f"l{i}",
            )
        tasks = {f"led-{i}": TaskSpec(_inc, (i,)) for i in range(N_TASKS)}
        deps: dict = {f"led-{i}": set() for i in range(N_TASKS)}
        for i in range(0, N_TASKS, 4):
            tasks[f"ldp-{i}"] = TaskSpec(_inc, (i,))
            deps[f"ldp-{i}"] = {f"led-{i}", f"led-{(i + 1) % N_TASKS}"}
        state.update_graph_core(
            tasks, deps, list(tasks), client="smoke",
            stimulus_id="smoke-ledger-graph",
        )
        return state

    # live task-finished messages ALWAYS carry startstops (the worker
    # stamps every compute): the flood includes them so the baseline is
    # the real ingest path — prefix duration folds, group timing — not
    # an artificially thin engine pass
    SS = ({"action": "compute", "start": 0.0, "stop": 0.005},)

    def flood(state) -> float:
        t0 = time.perf_counter()
        rounds = 0
        while True:
            batch = [
                (
                    ts.key, ws.address, f"led-fin-{ts.key}",
                    {"nbytes": 8, "startstops": SS},
                )
                for ws in state.workers.values()
                for ts in list(ws.processing)
            ]
            if not batch:
                break
            state.stimulus_tasks_finished_batch(batch)
            rounds += 1
            assert rounds < 10 * N_TASKS, "flood did not converge"
        return time.perf_counter() - t0

    flood(build(True))   # untimed warmup per arm (allocator/code warm)
    flood(build(False))
    on_walls, off_walls = [], []
    for _ in range(REPS):
        on_walls.append(flood(build(True)))
        off_walls.append(flood(build(False)))
    min_ratio = min(on / off for on, off in zip(on_walls, off_walls))
    overhead_pct = max(0.0, (min_ratio - 1.0) * 100)
    assert overhead_pct < 5.0, (
        f"ledger-on overhead {overhead_pct:.1f}% exceeds the 5% budget "
        f"(on={on_walls}, off={off_walls})"
    )

    # allocation contract on the file+join hot path: steady-state
    # decision rows allocate nothing net (preallocated slots + dict
    # insert/pop pairs).  Warm a FULL ring wrap first — the first pass
    # retires each slot's shared initial constants — plus the aggregate
    # dicts (prefix/link/kind/histogram entries are one-time).
    import gc

    from distributed_tpu.ledger import DecisionLedger

    led = DecisionLedger(size=16384, enabled=True)
    keys = [f"alloc-{i}" for i in range(64)]
    wraps = (led._mask + 2) // len(keys) + 2

    def cycle():
        for k in keys:
            h = led.file(
                "placement", k, "alloc", "tcp://led:0", "smk",
                0.001, 0.002, True, 1024, 1, 0.01, "tcp://led:1", "",
            )
            led.join_row(h, "memory", "tcp://led:0", None, 0.005, None)

    for _ in range(wraps):
        cycle()
    # the A/B floods above leave reference cycles whose lazy collection
    # would otherwise land inside the measured window; collect, then
    # re-warm so the window starts from a settled allocator
    gc.collect()
    for _ in range(32):
        cycle()
    b0 = _sys.getallocatedblocks()
    for _ in range(20_000 // len(keys)):
        cycle()
    alloc_delta = _sys.getallocatedblocks() - b0
    assert alloc_delta < 50, (
        f"ledger file+join allocated ({alloc_delta} blocks over 20k "
        "decision cycles)"
    )

    # regret artifact + critical-path gate on the deterministic sim:
    # telemetry-seeded non-uniform fleet — the measured shadow must
    # out-predict the constants, and attribution must sum to the
    # virtual makespan within 1%
    from distributed_tpu.diagnostics.critical_path import check
    from distributed_tpu.sim import ClusterSim, SyntheticDag
    from distributed_tpu.sim.links import LinkProfile

    links = LinkProfile(bandwidth=2e7, jitter=0.9, seed=7)
    sim = ClusterSim(
        12, nthreads=2, seed=7, links=links, validate=True,
        ledger_size=65536,
    )
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
    SyntheticDag(
        n_layers=6, layer_width=18, fanin=2, seed=7, layers_per_chunk=3,
        duration_range=(0.001, 0.005), nbytes_range=(256_000, 2_000_000),
    ).start(sim)
    rep = sim.run()
    lsum = rep["ledger"]
    assert lsum["unjoined"] == 0 and lsum["open"] == 0, lsum
    reg = lsum["regret_abs_mean"]
    assert reg["measured"] < reg["constant"], (
        "measured-shadow aggregate regret did not beat the constants "
        f"on the telemetry-seeded non-uniform fleet: {reg}"
    )
    cp = sim.critical_path()
    assert cp is not None
    check(cp, tolerance=0.01)
    assert abs(cp["makespan"] - rep["virtual_makespan_s"]) <= (
        0.01 * rep["virtual_makespan_s"]
    ), (cp["makespan"], rep["virtual_makespan_s"])

    out = asyncio.run(_smoke_ledger_live())
    out.update({
        "n_workers": N_WORKERS,
        "n_tasks": N_TASKS,
        "ledger_on_s": [round(w, 3) for w in on_walls],
        "ledger_off_s": [round(w, 3) for w in off_walls],
        "overhead_pct": round(overhead_pct, 2),
        "alloc_delta_blocks": alloc_delta,
        "regret_abs_constant": round(reg["constant"], 6),
        "regret_abs_measured": round(reg["measured"], 6),
        "measured_beats_constant": True,
        "cp_makespan_s": round(cp["makespan"], 6),
        "cp_check_ok": True,
        "sim_joined": lsum["joined"],
        "host_canary_ms": _host_canary_ms(),
    })
    return out


def _smoke_engine() -> dict:
    """Native transition-engine gate (native/engine.cpp +
    scheduler/native_engine.py; docs/native_engine.md): a randomized
    dependency flood driven through the compiled engine must

    - be BIT-IDENTICAL to the pure-python oracle (final states, per-key
      stories, per-destination message multisets),
    - absorb the four compiled arms natively (escape rate < 10% of
      transitions — the sim_10k trace measures ~0%),
    - DEFER: a no-introspection flood hydrates zero tape rows inside
      the stimulus call (the authoritative-SoA contract — python truth
      materializes at the next read, outside the engine plane),
    - hold a same-session speedup >= 10x on the engine plane (the
      stimulus_tasks_finished_batch calls alone, batch building and
      deferred hydration excluded) and >= 1.3x on the whole flood loop
      including the python-side batch building + replay, both
      best-of-pairs (one-sided box-phase noise shrinks single pairs; a
      real regression drops EVERY pair — PERF.md Round 12), and
    - allocate nothing per flood in the bridge's steady state (stale-
      completion floods: prep + native drain + tape apply with no state
      growth, the PR 6 getallocatedblocks pattern).
    """
    import random as _random
    import sys as _sys

    from distributed_tpu import config as dtpu_config
    from distributed_tpu.scheduler.state import SchedulerState

    N_WORKERS, WIDTH, LAYERS, REPS = 32, 64, 10, 5
    OVR = {
        "scheduler.trace.enabled": False,
        "scheduler.telemetry.enabled": False,
        "scheduler.native-engine.enabled": False,  # explicit attach
        "scheduler.native-engine.min-flood": 0,
    }

    class _Spec:
        __slots__ = ()

    spec = _Spec()

    def build(native_on, seed=0):
        with dtpu_config.set(OVR):
            state = SchedulerState(validate=False)
            if native_on:
                assert state.attach_native(build=True), (
                    "native toolchain unavailable (engine smoke needs "
                    "the on-demand g++ build this image carries)"
                )
            for i in range(N_WORKERS):
                state.add_worker_state(
                    f"sim://w{i}", nthreads=1, memory_limit=2**30,
                    name=f"w{i}",
                )
            rng = _random.Random(seed)
            addrs = list(state.workers)
            prev = []
            for i in range(WIDTH):
                k = f"root-{i}"
                state.client_desires_keys([k], "c")
                recs, cm, wm = state._transition(
                    k, "memory", "scatter", nbytes=65536,
                    worker=addrs[i % len(addrs)],
                )
                state._transitions(recs, cm, wm, "scatter")
                prev.append(k)
            tasks, deps, prios = {}, {}, {}
            rank = 0
            for j in range(LAYERS):
                layer = [f"L{j}-{i}" for i in range(WIDTH)]
                for k in layer:
                    deps[k] = {
                        prev[rng.randrange(len(prev))] for _ in range(2)
                    }
                    tasks[k] = spec
                    prios[k] = (rank,)
                    rank += 1
                prev = layer
            state.update_graph_core(
                tasks, deps, prev, client="c", priorities=prios,
                stimulus_id="graph",
            )
        return state

    def flood(state, collect=False):
        """Drive to quiescence.  Returns (wall_total, wall_engine,
        hydrations_in_timer, rounds_out): wall_engine times ONLY the
        stimulus_tasks_finished_batch calls — the batch-plane engine
        wall the >=10x gate measures.  Batch building (list(ws.
        processing), which hydrates the previous flood's deferred
        segments) stays outside the engine timer, and the hydration
        counter is sampled around each timed call so the gate can
        assert the engine plane itself hydrates nothing."""
        rounds, out = 0, []
        eng, hyd = 0.0, 0
        ne = getattr(state, "native", None)
        t_all = time.perf_counter()
        with dtpu_config.set(OVR):
            while True:
                batch = [
                    (
                        ts.key, ws.address, f"f{rounds}-{i}",
                        {"nbytes": 2048, "startstops": [{
                            "action": "compute", "start": 0.0,
                            "stop": 0.01,
                        }]},
                    )
                    for ws in state.workers.values()
                    for i, ts in enumerate(list(ws.processing))
                ]
                if not batch:
                    break
                h0 = ne.hydrations if ne is not None else 0
                t0 = time.perf_counter()
                r = state.stimulus_tasks_finished_batch(batch)
                eng += time.perf_counter() - t0
                if ne is not None:
                    hyd += ne.hydrations - h0
                if collect:
                    out.append(r)
                rounds += 1
                assert rounds < 5000
        return time.perf_counter() - t_all, eng, hyd, out

    def freeze(obj):
        if isinstance(obj, dict):
            return tuple(sorted((k, freeze(v)) for k, v in obj.items()))
        if isinstance(obj, (list, tuple)):
            return tuple(freeze(v) for v in obj)
        if isinstance(obj, (str, bytes, int, float, bool)) or obj is None:
            return obj
        return repr(type(obj))

    def canon(rounds):
        return [
            {
                dest: sorted(
                    (freeze({k: v for k, v in m.items()
                             if k != "run_spec"}) for m in msgs),
                    key=repr,
                )
                for dest, msgs in d.items()
            }
            for cm, wm in rounds for d in (cm, wm)
        ]

    def snap(state):
        return {
            k: (
                ts.state,
                ts.processing_on.address if ts.processing_on else None,
                tuple(ws.address for ws in ts.who_has),
            )
            for k, ts in state.tasks.items()
        }

    # --- bit-parity on a randomized flood ----------------------------
    a, b = build(False, seed=3), build(True, seed=3)
    _, _, _, ra = flood(a, collect=True)
    _, _, _, rb = flood(b, collect=True)
    assert snap(a) == snap(b), "native/oracle state mismatch"
    assert [r[:5] for r in a.transition_log] ==         [r[:5] for r in b.transition_log], "story mismatch"
    assert canon(ra) == canon(rb), "message mismatch"
    counters = b.native.counters()
    total = counters["transitions"] + counters["oracle_transitions"]
    escape_rate = counters["escapes"] / max(total, 1)
    assert counters["transitions"] > 0, "native engine never ran"
    assert escape_rate < 0.10, (
        f"escape rate {escape_rate:.1%} — the compiled arms are not "
        f"absorbing their share ({counters})"
    )

    # --- same-session speedup (best-of-pairs, drift-robust) ----------
    # Two planes per pair: the ENGINE plane (stimulus calls only — the
    # deferred-materialization contract keeps python bookkeeping out of
    # it, gate >= 10x) and the whole flood loop including the python
    # batch builds that hydrate the previous round (legacy gate 1.3x).
    flood(build(False))
    flood(build(True))
    ratios, eng_ratios, hyd_in_timer = [], [], 0
    for _ in range(REPS):
        wo, eo, _, _ = flood(build(False))
        wn, en, h, _ = flood(build(True))
        ratios.append(wo / wn)
        eng_ratios.append(eo / en)
        hyd_in_timer += h
    speedup = max(ratios)
    speedup_engine = max(eng_ratios)
    assert hyd_in_timer == 0, (
        f"{hyd_in_timer} rows hydrated INSIDE the engine timer — a "
        "no-introspection flood must defer every segment (escape or "
        "stray read on the stimulus path is dragging replay back into "
        "the engine plane)"
    )
    assert speedup_engine >= 10.0, (
        f"engine-plane speedup {speedup_engine:.2f}x under the 10x "
        f"floor (pairs {[round(r, 1) for r in eng_ratios]}; PERF.md "
        f"Round 12)"
    )
    assert speedup >= 1.3, (
        f"native flood speedup {speedup:.2f}x under the 1.3x floor "
        f"(pairs {[round(r, 2) for r in ratios]})"
    )

    # --- per-flood alloc budget (stale floods: no state growth) ------
    st = build(True, seed=4)
    stale = [(f"ghost-{i}", "sim://w0", f"g{i}", {"nbytes": 8})
             for i in range(64)]
    def drain(r):
        # consume the lazy flood messages: the read barrier replays the
        # deferred segment and returns its tape to the pool, so the
        # steady state the block budget measures includes recycling
        return sum(len(v) for v in r[1].values())

    with dtpu_config.set(OVR):
        for _ in range(4):
            drain(st.stimulus_tasks_finished_batch(list(stale)))
        b0 = _sys.getallocatedblocks()
        for _ in range(32):
            drain(st.stimulus_tasks_finished_batch(list(stale)))
        alloc_delta = _sys.getallocatedblocks() - b0
    assert alloc_delta < 300, (
        f"native flood path leaked {alloc_delta} blocks over 32 "
        "identical stale floods"
    )

    return {
        "n_tasks": WIDTH * LAYERS,
        "transitions": b.transition_counter,
        "native_transitions": counters["transitions"],
        "escapes": counters["escapes"],
        "escape_rate": round(escape_rate, 4),
        "parity": True,
        "speedup_best": round(speedup, 2),
        "speedup_pairs": [round(r, 2) for r in ratios],
        "speedup_engine_best": round(speedup_engine, 2),
        "speedup_engine_pairs": [round(r, 1) for r in eng_ratios],
        "hydrations_in_timer": hyd_in_timer,
        "alloc_delta_blocks": alloc_delta,
        "host_canary_ms": _host_canary_ms(),
    }


async def _smoke_census_live() -> dict:
    """Live half of the census gate: a real in-process cluster computes
    keys, the client releases everything, and the run must QUIESCE
    CENSUS-CLEAN on every role — zero non-allowlisted residue, every
    walk-vs-counter audit green (diagnostics/census.py)."""
    import asyncio

    from distributed_tpu import config as dtpu_config
    from distributed_tpu.client.client import Client
    from distributed_tpu.deploy.local import LocalCluster

    with dtpu_config.set({"scheduler.jax.enabled": False}):
        async with LocalCluster(n_workers=2, threads_per_worker=1) as cluster:
            async with Client(cluster.scheduler_address) as c:
                futs = c.map(_inc, range(64))
                res = await c.gather(futs)
                assert res == list(range(1, 65)), res[:5]
                for f in futs:
                    f.release()
                del futs
                s = cluster.scheduler.state
                deadline = time.monotonic() + 20.0
                while time.monotonic() < deadline:
                    if not s.tasks and s.census.quiesced() and all(
                        not w.state.tasks for w in cluster.workers
                    ):
                        break
                    await asyncio.sleep(0.05)
                assert s.census.quiesced(), {
                    m: s.census.families[m].probe() for m in s.census.motion
                }
                censuses = [("scheduler", s.census)] + [
                    (w.address, w.state.census) for w in cluster.workers
                ]
                n_fam = 0
                for who, census in censuses:
                    census.audit()
                    residue = census.residue()
                    assert not residue, (who, census.enrich_findings(residue))
                    n_fam += len(census.families)
                # the RPC twin serves the same truth
                recs = await c.scheduler.get_census(deep=True)
                head = recs[0]
                assert head["quiesced"] is True, head
    return {"censuses": len(censuses), "families": n_fam}


def _smoke_census() -> dict:
    """State-census gate (diagnostics/census.py; docs/observability.md
    "State census & retention"):

    - census-on (sentinel ticking every flood round — a strict
      over-approximation of the 2s production cadence) vs census-off
      engine floods stay under the 5% budget by the min-per-pair-ratio
      estimator;
    - sentinel ticks are allocation-free (``sys.getallocatedblocks``
      over a 20k-tick burst);
    - a live run-then-quiesce LocalCluster ends census-clean on every
      role, and the walk-vs-counter audits pass throughout.
    """
    import asyncio
    import sys as _sys

    from distributed_tpu.diagnostics.census import RetentionSentinel
    from distributed_tpu.graph.spec import TaskSpec
    from distributed_tpu.scheduler.state import SchedulerState

    N_WORKERS, N_TASKS, REPS = 16, 2000, 7

    def build():
        state = SchedulerState(validate=False)
        for i in range(N_WORKERS):
            state.add_worker_state(
                f"tcp://census:{i}", nthreads=2, memory_limit=2**30,
                name=f"c{i}",
            )
        tasks = {f"cns-{i}": TaskSpec(_inc, (i,)) for i in range(N_TASKS)}
        state.update_graph_core(
            tasks, {k: set() for k in tasks}, list(tasks),
            client="smoke", stimulus_id="smoke-census-graph",
        )
        return state

    def flood(state, sentinel) -> float:
        t0 = time.perf_counter()
        rounds = 0
        while True:
            batch = [
                (ts.key, ws.address, f"smk-cns-{ts.key}", {"nbytes": 8})
                for ws in state.workers.values()
                for ts in list(ws.processing)
            ]
            if not batch:
                break
            state.stimulus_tasks_finished_batch(batch)
            if sentinel is not None:
                sentinel.tick()
            rounds += 1
            assert rounds < 10 * N_TASKS, "flood did not converge"
        return time.perf_counter() - t0

    def arm(on: bool) -> float:
        state = build()
        sentinel = RetentionSentinel(state.census) if on else None
        return flood(state, sentinel)

    arm(True)   # untimed warmup (allocator/code warmup)
    arm(False)
    on_walls, off_walls = [], []
    for _ in range(REPS):
        on_walls.append(arm(True))
        off_walls.append(arm(False))
    min_ratio = min(on / off for on, off in zip(on_walls, off_walls))
    overhead_pct = max(0.0, (min_ratio - 1.0) * 100)
    assert overhead_pct < 5.0, (
        f"census-on overhead {overhead_pct:.1f}% exceeds the 5% budget "
        f"(on={on_walls}, off={off_walls})"
    )

    # allocation contract: the sentinel tick (every cheap probe + the
    # slope folds) allocates nothing in steady state
    state = build()
    sentinel = RetentionSentinel(state.census)
    for _ in range(64):
        sentinel.tick()  # warm per-family floats + probe code paths
    b0 = _sys.getallocatedblocks()
    for _ in range(20_000):
        sentinel.tick()
    alloc_delta = _sys.getallocatedblocks() - b0
    assert alloc_delta < 50, (
        f"sentinel tick allocated ({alloc_delta} blocks over 20k ticks)"
    )

    live = asyncio.run(_smoke_census_live())
    return {
        "n_workers": N_WORKERS,
        "n_tasks": N_TASKS,
        "census_on_s": [round(w, 3) for w in on_walls],
        "census_off_s": [round(w, 3) for w in off_walls],
        "overhead_pct": round(overhead_pct, 2),
        "alloc_delta_blocks": alloc_delta,
        "live_clean": True,
        "live_censuses": live["censuses"],
        "live_families": live["families"],
        "host_canary_ms": _host_canary_ms(),
    }


def _smoke_lint() -> dict:
    """The determinism lint gate rides the smoke: bench headlines are
    only comparable across runs and processes if every scheduling
    decision is hash-seed- and allocation-independent
    (docs/determinism.md), so --smoke refuses to bless a tree with
    determinism findings."""
    t0 = time.perf_counter()
    r = subprocess.run(
        [sys.executable, "-m", "distributed_tpu.analysis",
         "--rule", "determinism", "--format", "json"],
        capture_output=True, text=True, timeout=180,
        cwd=os.path.dirname(os.path.abspath(__file__)),
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    report = json.loads(r.stdout)
    assert report["findings"] == [], report["findings"]
    assert report["errors"] == [], report["errors"]
    return {
        "rule": "determinism",
        "findings": 0,
        "suppressed": report["suppressed"],
        "wall_s": round(time.perf_counter() - t0, 2),
    }


def run_smoke(only: str | None = None):
    """``python bench.py --smoke [name]``: tiny CPU-pinned configs; one
    JSON line on stdout; raises (non-zero exit) on any failure.  With a
    name (e.g. ``--smoke restart``) runs just that config."""
    import asyncio

    # the mesh smoke needs the 8-device CPU mesh; the flag must be in
    # place before ANY config initializes the backend
    _ensure_cpu_mesh_env()
    t0 = time.perf_counter()

    def retry_once(fn):
        # the 5% overhead gates sit at this box's noise margin: in a
        # noisy phase a single A/B reads 7-15% with or WITHOUT the
        # feature under test (measured at 1 device too).  A genuine
        # overhead regression is systematic and fails both attempts;
        # one-shot box-phase noise does not.
        try:
            return fn()
        except AssertionError:
            return fn()

    builders = {
        "cluster": lambda: asyncio.run(_smoke_cluster()),
        "placement": _smoke_placement,
        "mirror": _smoke_mirror,
        "wire": lambda: asyncio.run(_smoke_wire()),
        "trace": lambda: retry_once(_smoke_trace),
        "telemetry": lambda: retry_once(_smoke_telemetry),
        "selfprofile": lambda: retry_once(_smoke_selfprofile),
        "ledger": lambda: retry_once(_smoke_ledger),
        "engine": lambda: retry_once(_smoke_engine),
        "sim": _smoke_sim,
        "restart": lambda: retry_once(_smoke_restart),
        "census": lambda: retry_once(_smoke_census),
        "lint": _smoke_lint,
        # "mesh" LAST on purpose: the sharded programs spin up the
        # 8-device XLA runtime (one thread pool per virtual device on a
        # 2-core box) and that background churn measurably widens the
        # pure-python flood A/Bs above — trace/telemetry's 5% overhead
        # gates flaked 2-in-3 with the mesh config ahead of them
        "mesh": _smoke_mesh,
    }
    if only is not None:
        if only not in builders:
            raise SystemExit(
                f"unknown smoke config {only!r}; one of {sorted(builders)}"
            )
        names = [only]
    else:
        names = list(builders)
    configs = {name: builders[name]() for name in names}
    print(
        json.dumps(
            {
                "smoke": True,
                "total_s": round(time.perf_counter() - t0, 1),
                "configs": configs,
            }
        )
    )


# =====================================================================
# harness
# =====================================================================

def run_config(name, force_cpu=False):
    """Child entry: run one config, print its JSON dict as the last line."""
    if name == "dag_10m":
        # the sharded headline runs on the multi-device CPU mesh
        _ensure_cpu_mesh_env()
    elif force_cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
    from distributed_tpu.ops.compile_cache import enable_compile_cache

    enable_compile_cache()
    if name == "dag_1m":
        result = cfg_dag_1m()
    elif name == "dag_10m":
        result = cfg_dag_10m()
    elif name == "sim_10k":
        result = cfg_sim_10k()
    else:
        import asyncio

        fn = {
            "array_sum": cfg_array_sum,
            "rechunk_tensordot": cfg_rechunk_tensordot,
            "steal": cfg_steal,
            "shuffle": cfg_shuffle,
        }[name]
        result = asyncio.run(fn())
    sys.stdout.flush()
    print(json.dumps(result))


def _parse_json_tail(stdout: str):
    """Last JSON-looking line of a child's stdout, or None."""
    for line in reversed(stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    return None


def main():
    t_start = time.perf_counter()
    cpu_env = dict(os.environ, JAX_PLATFORMS="cpu")

    configs = {}
    errors = {}
    for name, timeout, force_cpu in CONFIGS:
        env = cpu_env if force_cpu else dict(os.environ)
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--config", name]
                + (["--force-cpu"] if force_cpu else []),
                env=env,
                capture_output=True,
                text=True,
                timeout=timeout,
            )
            if proc.stderr:
                sys.stderr.write(proc.stderr[-2000:])
            parsed = _parse_json_tail(proc.stdout)
            if parsed is None:
                raise RuntimeError(
                    f"rc={proc.returncode}: "
                    + (proc.stderr or proc.stdout).strip()[-400:]
                )
            configs[name] = parsed
        except subprocess.TimeoutExpired:
            errors[name] = f"timed out after {timeout}s"
        except Exception as exc:
            errors[name] = str(exc)[:400]

    dag = configs.get("dag_1m")
    headline = {
        "metric": "task-placement decisions/sec, 1M-task DAG on 512 workers",
        "value": dag["decisions_per_s"] if dag else 0,
        "unit": "decisions/s",
        "vs_baseline": dag["vs_baseline"] if dag else 0.0,
        "backend": dag["backend"] if dag else None,
        "total_bench_s": round(time.perf_counter() - t_start, 1),
        "configs": configs,
    }
    if errors:
        headline["errors"] = errors
    print(json.dumps(headline))
    sys.exit(0)


if __name__ == "__main__":
    if "--smoke" in sys.argv[1:]:
        _i = sys.argv.index("--smoke")
        _only = (
            sys.argv[_i + 1]
            if len(sys.argv) > _i + 1 and not sys.argv[_i + 1].startswith("-")
            else None
        )
        run_smoke(_only)
    elif len(sys.argv) >= 3 and sys.argv[1] == "--config":
        run_config(sys.argv[2], force_cpu="--force-cpu" in sys.argv)
    else:
        try:
            main()
        except SystemExit:
            raise  # main's own clean exit — the JSON is already printed
        except BaseException as exc:  # absolute backstop: always emit JSON
            print(
                json.dumps(
                    {
                        "metric": "task-placement decisions/sec, "
                        "1M-task DAG on 512 workers",
                        "value": 0,
                        "unit": "decisions/s",
                        "vs_baseline": 0.0,
                        "error": f"{type(exc).__name__}: {exc}"[:400],
                    }
                )
            )
            sys.exit(0)

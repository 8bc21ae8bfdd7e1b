"""Observability tests: HTTP routes, Prometheus, SystemMonitor, task
stream, profiler, events (reference http/*/tests, test_events patterns)."""

from __future__ import annotations

import asyncio
import json
import os
import time as _time

from distributed_tpu.client.client import Client
from distributed_tpu.deploy.local import LocalCluster
from distributed_tpu.scheduler.server import Scheduler
from distributed_tpu.worker.server import Worker

from conftest import gen_test


async def new_cluster(**kwargs):
    cluster = LocalCluster(
        n_workers=kwargs.pop("n_workers", 2),
        scheduler_kwargs={"validate": True, **kwargs.pop("scheduler_kwargs", {})},
        worker_kwargs={"validate": True, **kwargs.pop("worker_kwargs", {})},
        **kwargs,
    )
    await cluster._start()
    return cluster


async def http_get(port: int, path: str) -> tuple[int, bytes]:
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {path} HTTP/1.0\r\n\r\n".encode())
    await writer.drain()
    data = await reader.read()
    writer.close()
    head, _, body = data.partition(b"\r\n\r\n")
    status = int(head.split()[1])
    return status, body


@gen_test()
async def test_http_health_info_metrics():
    async with await new_cluster() as cluster:
        async with Client(cluster.scheduler_address) as c:
            futs = c.map(lambda x: x + 1, range(5))
            await c.gather(futs)
            port = cluster.scheduler.http_server.port
            status, body = await http_get(port, "/health")
            assert status == 200 and body == b"ok"
            status, body = await http_get(port, "/info")
            info = json.loads(body)
            assert info["type"] == "Scheduler"
            assert len(info["workers"]) == 2
            status, body = await http_get(port, "/metrics")
            text = body.decode()
            assert "dtpu_scheduler_workers 2" in text
            assert "dtpu_scheduler_tasks" in text
            status, body = await http_get(port, "/json/counts.json")
            counts = json.loads(body)
            assert counts["workers"] == 2
            status, _ = await http_get(port, "/nope")
            assert status == 404
            # worker metrics too
            wport = cluster.workers[0].http_server.port
            status, body = await http_get(wport, "/metrics")
            assert b"dtpu_worker_tasks_stored" in body


@gen_test()
async def test_system_monitor_samples():
    async with await new_cluster(n_workers=1) as cluster:
        mon = cluster.scheduler.monitor
        mon.update()
        mon.update()
        recent = mon.recent()
        assert recent["memory"] > 0
        rq = mon.range_query()
        assert len(rq["time"]) >= 2


@gen_test()
async def test_task_stream_records():
    async with await new_cluster() as cluster:
        async with Client(cluster.scheduler_address) as c:
            futs = c.map(lambda x: x * 2, range(6), pure=False)
            await c.gather(futs)
            stream = await c.get_task_stream()
            assert len(stream) == 6
            rec = stream[0]
            assert rec["worker"] is not None
            assert rec["startstops"] and rec["startstops"][0]["action"] == "compute"
            # every rectangle carries the stimulus id of the transition
            # that produced it — the join key against /trace (PR 6)
            assert all(r["stimulus_id"] for r in stream)
            trace_stims = {
                ev["stim"] for ev in cluster.scheduler.trace.tail()
            }
            assert {r["stimulus_id"] for r in stream} <= trace_stims


@gen_test(timeout=60)
async def test_profile_collects_samples():
    async with await new_cluster(n_workers=1) as cluster:
        async with Client(cluster.scheduler_address) as c:
            def busy(x):
                t0 = _time.time()
                while _time.time() - t0 < 0.5:
                    sum(range(1000))
                return x

            fut = c.submit(busy, 1)
            await fut.result()
            prof = await c.profile()
            assert prof["count"] > 0
            # the busy function appears somewhere in the tree
            def find(node):
                if "busy" in node.get("description", ""):
                    return True
                return any(find(ch) for ch in node.get("children", {}).values())

            assert find(prof)


@gen_test()
async def test_events_and_subscription():
    async with await new_cluster(n_workers=1) as cluster:
        async with Client(cluster.scheduler_address) as c:
            seen: list = []
            c.subscribe_topic("my-topic", seen.append)
            await asyncio.sleep(0.05)
            c.log_event("my-topic", {"x": 1})
            for _ in range(100):
                if seen:
                    break
                await asyncio.sleep(0.01)
            assert seen == [{"x": 1}]
            events = await c.get_events("my-topic")
            assert len(events) == 1
            assert events[0][1] == {"x": 1}


@gen_test(timeout=60)
async def test_json_api_and_dashboard():
    """Dashboard-lite JSON routes + the self-contained HTML page
    (reference http/scheduler/api.py, dashboard/)."""
    import json as _json
    import urllib.request

    async with await new_cluster(
        n_workers=2, scheduler_kwargs={"http_port": 0}
    ) as cluster:
        async with Client(cluster.scheduler_address) as c:
            futs = c.map(lambda x: x * 2, range(20), pure=False)
            await c.gather(futs)
            for w in cluster.workers:
                await w.heartbeat()
            port = cluster.scheduler.http_server.port

            def get(path):
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=5
                ) as r:
                    return r.headers.get_content_type(), r.read()

            loop = asyncio.get_running_loop()
            ct, body = await loop.run_in_executor(None, get, "/api/v1/workers")
            ws = _json.loads(body)
            assert ct == "application/json" and len(ws) == 2
            assert all("managed_bytes" in w and "occupancy" in w for w in ws)

            _, body = await loop.run_in_executor(None, get, "/api/v1/tasks")
            tasks = _json.loads(body)
            assert tasks["by_state"].get("memory", 0) >= 20

            _, body = await loop.run_in_executor(
                None, get, "/api/v1/task_stream"
            )
            stream = _json.loads(body)
            assert len(stream) >= 20
            assert all("startstops" in r for r in stream)

            _, body = await loop.run_in_executor(None, get, "/api/v1/memory")
            mem = _json.loads(body)
            assert len(mem["workers"]) == 2

            ct, body = await loop.run_in_executor(None, get, "/dashboard")
            assert ct == "text/html"
            assert b"task_stream" in body and b"<svg" in body


@gen_test(timeout=60)
async def test_memory_sampler():
    """MemorySampler context manager records a cluster memory timeseries
    (reference diagnostics/memory_sampler.py:180)."""
    import numpy as np

    from distributed_tpu.diagnostics.memory_sampler import MemorySampler

    def chunk(i):
        return np.ones(1_000_000)  # 8 MB

    async with await new_cluster(n_workers=2) as cluster:
        async with Client(cluster.scheduler_address) as c:
            ms = MemorySampler()
            async with ms.sample("run", client=c, interval=0.05):
                futs = c.map(chunk, range(4), pure=False)
                await c.gather(futs)
                await asyncio.sleep(0.3)
            series = ms.to_list("run")
            assert len(series) >= 3
            assert ms.max("run") >= 4 * 8_000_000
            # offsets monotonically increase
            assert all(b[0] > a[0] for a, b in zip(series, series[1:]))


@gen_test()
async def test_progress_bar_tracks_futures():
    """progress() renders until every future settles and reports erred
    counts (reference diagnostics/tests/test_progressbar.py)."""
    import io

    from distributed_tpu.diagnostics.progressbar import progress

    async with Scheduler(listen_addr="inproc://", validate=True) as s:
        async with Worker(s.address, nthreads=2):
            async with Client(s.address) as c:
                futs = c.map(lambda x: x * 2, range(10))
                buf = io.StringIO()
                await asyncio.wait_for(progress(futs, file=buf), 30)
                text = buf.getvalue()
                assert "10/10" in text
                assert text.endswith("\n")
                assert await c.gather(futs) == [x * 2 for x in range(10)]

                bad = c.map(
                    lambda x: 1 // (x % 3), range(6), pure=False
                )
                buf = io.StringIO()
                await asyncio.wait_for(progress(bad, file=buf), 30)
                assert "2 erred" in buf.getvalue()


@gen_test(timeout=120)
async def test_dashboard_profile_and_graph_routes():
    """Dashboard-lite round 4: /api/v1/profile serves the merged worker
    flame-graph call tree and /api/v1/graph a layered dependency graph;
    the HTML page embeds renderers for both (reference
    dashboard/components/scheduler.py profile + graph components,
    diagnostics/graph_layout.py:9)."""
    import json
    import time as _time
    import urllib.request

    from distributed_tpu import config
    from distributed_tpu.client.client import Client
    from distributed_tpu.deploy.local import LocalCluster

    def work(i):
        _time.sleep(0.03)
        return sum(range(50_000)) + i

    with config.set({"worker.profile.enabled": True}):
        async with LocalCluster(
            n_workers=2, scheduler_kwargs={"http_port": 0}
        ) as cluster:
            async with Client(cluster.scheduler_address) as c:
                a = [c.submit(work, i, key=f"ga-{i}") for i in range(8)]
                b = [
                    c.submit(lambda x, y: x + y, a[i], a[i + 1],
                             key=f"gb-{i}")
                    for i in range(0, 6, 2)
                ]
                await c.gather(b)
                port = cluster.scheduler.http_server.port
                loop = asyncio.get_running_loop()

                def get(p):
                    with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}{p}"
                    ) as r:
                        return json.loads(r.read())

                g = await loop.run_in_executor(None, get, "/api/v1/graph")
                assert g["nodes"] and g["edges"]
                for src, dst in g["edges"]:
                    assert g["nodes"][src]["layer"] < g["nodes"][dst]["layer"]
                prof = await loop.run_in_executor(
                    None, get, "/api/v1/profile"
                )
                assert "count" in prof and "children" in prof

                def fetch_html():
                    with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/dashboard"
                    ) as r:
                        return r.read().decode()

                html = await loop.run_in_executor(None, fetch_html)
                for needle in ("drawGraph", "drawFlame",
                               "/api/v1/graph", "/api/v1/profile"):
                    assert needle in html, needle


@gen_test(timeout=120)
async def test_worker_proxy_pages_with_deaths():
    """Per-worker pages THROUGH the scheduler (reference http/proxy.py
    role): health / metrics / profile / info render for live workers
    and stay serviceable while workers die mid-run."""
    import functools
    import json as _json
    import urllib.request

    async def fetch(url, expect_status=200):
        loop = asyncio.get_running_loop()

        def get(u):
            import urllib.error

            try:
                r = urllib.request.urlopen(u, timeout=10)
                return r.status, r.read()
            except urllib.error.HTTPError as e:
                return e.code, e.read()

        status, body = await loop.run_in_executor(
            None, functools.partial(get, url)
        )
        assert status == expect_status, (url, status, body[:200])
        return body

    def slow(x):
        import time as _t

        _t.sleep(0.05)
        return x + 1

    async with LocalCluster(n_workers=4, threads_per_worker=1) as cluster:
        async with Client(cluster.scheduler_address) as c:
            port = cluster.scheduler.http_server.port
            base = f"http://127.0.0.1:{port}"
            futs = c.map(slow, range(40), pure=False)

            idx = _json.loads(await fetch(f"{base}/workers/"))
            assert len(idx) == 4
            name = idx[0]["name"]
            health = _json.loads(await fetch(f"{base}/workers/{name}/health"))
            assert health["ok"] is True
            metrics = _json.loads(
                await fetch(f"{base}/workers/{name}/metrics")
            )
            assert metrics["worker"] == idx[0]["address"]
            prof = _json.loads(await fetch(f"{base}/workers/{name}/profile"))
            assert isinstance(prof, dict)
            info = _json.loads(await fetch(f"{base}/workers/{name}/info"))
            assert info["nthreads"] == 1

            # two workers die mid-run: the proxy keeps answering — the
            # index shrinks, a dead name 404s gracefully, survivors serve
            victims = [w for w in cluster.workers[:2]]
            dead_names = [str(w.name) for w in victims]
            for w in victims:
                await w.close(report=False)
            cluster.workers = cluster.workers[2:]
            deadline = asyncio.get_running_loop().time() + 30
            while len(cluster.scheduler.state.workers) > 2:
                assert asyncio.get_running_loop().time() < deadline
                await asyncio.sleep(0.05)
            idx2 = _json.loads(await fetch(f"{base}/workers"))
            assert len(idx2) == 2
            gone = _json.loads(
                await fetch(f"{base}/workers/{dead_names[0]}/health",
                            expect_status=404)
            )
            assert "error" in gone
            survivor = idx2[0]["name"]
            health2 = _json.loads(
                await fetch(f"{base}/workers/{survivor}/health")
            )
            assert health2["ok"] is True
            # the run itself survives the deaths
            assert await asyncio.wait_for(c.gather(futs), 60) == list(
                range(1, 41)
            )


@gen_test(timeout=120)
async def test_performance_report_activity_seconds_spill_workload():
    """The done-criterion for fine metrics (reference metrics.py:159,336):
    a spill-heavy workload's performance report carries per-activity
    seconds — spill serialize/disk-write/disk-read plus the gather-dep
    network/deserialize/other split from the DelayedMetricsLedger."""
    from distributed_tpu import config as dtpu_config

    # pause OFF: a 4 MB memory_limit makes the process-RSS fraction
    # permanently exceed the pause threshold, so on a slow box the
    # 100 ms monitor tick can fire mid-workload and pause both workers
    # FOREVER (nothing ever brings rss under 4 MB) — observed as a 60 s
    # gather timeout.  This test is about spill metering, which keys on
    # managed (fast_bytes) memory and still engages.
    with dtpu_config.set({"worker.memory.pause": 0}):
        await _spill_workload_body()


async def _spill_workload_body():
    import numpy as np

    def chunk(i):
        return np.full((512, 256), float(i))  # ~1 MB

    def combine(a, b):
        return float(a.sum() + b.sum())

    async with LocalCluster(
        n_workers=2,
        threads_per_worker=1,
        worker_kwargs={"memory_limit": 4_000_000,  # ~4 chunks -> spills
                       "heartbeat_interval": 0.1},
    ) as cluster:
        async with Client(cluster.scheduler_address) as c:
            # pin chunks alternately so every combine is cross-worker by
            # construction (scheduler load-balance drift under a loaded
            # box once co-located everything and no gather-dep traffic
            # ever happened)
            addrs = [w.address for w in cluster.workers]
            chunks = [
                c.submit(chunk, i, pure=False, workers=[addrs[i % 2]])
                for i in range(10)
            ]
            outs = [
                c.submit(combine, a, b, pure=False)
                for a, b in zip(chunks[:-1], chunks[1:])
            ]
            await asyncio.wait_for(c.gather(outs), 60)
            # let a couple of heartbeats ship the fine-metric deltas
            deadline = asyncio.get_running_loop().time() + 30
            spans = cluster.scheduler.spans
            def have(context, label):
                return any(
                    k[0] == context and k[3] == label and v > 0
                    for k, v in spans.cumulative_worker_metrics.items()
                )
            while not (have("spill", "disk-write")
                       and have("gather-dep", "network")):
                assert asyncio.get_running_loop().time() < deadline, (
                    dict(spans.cumulative_worker_metrics)
                )
                await asyncio.sleep(0.1)
            html = await cluster.scheduler.performance_report_html()
            assert "Activities (fine metrics)" in html
            for needle in ("disk-write", "network", "deserialize"):
                assert needle in html, needle


@gen_test(timeout=120)
async def test_cluster_dump_artefact_roundtrip():
    """dump_cluster_state -> DumpArtefact: offline post-mortem queries
    (reference cluster_dump.py:111 DumpArtefact)."""
    import os as _os
    import tempfile

    from distributed_tpu.diagnostics.cluster_dump import DumpArtefact

    tdir = tempfile.TemporaryDirectory()
    path = _os.path.join(tdir.name, "dump.json")
    async with LocalCluster(n_workers=2, threads_per_worker=1) as cluster:
        async with Client(cluster.scheduler_address) as c:
            futs = c.map(lambda x: x + 1, range(6), pure=False)
            assert await asyncio.wait_for(c.gather(futs), 60) == list(
                range(1, 7)
            )
            await c.dump_cluster_state(path)

    d = DumpArtefact.from_file(path)
    assert len(d.workers) == 2
    assert d.state_counts().get("memory", 0) >= 6
    key = futs[0].key
    info = d.worker_of(key)
    assert info["state"] == "memory" and info["who_has"]
    story = d.story(key)
    assert story, "transition log rows for the key must travel in the dump"
    assert any(row[0] == key for row in story)
    summary = d.workers_summary()
    assert all(v["nthreads"] == 1 for v in summary.values())
    # the flight-recorder causal tails ship in the dump by default
    # (PR 6): scheduler last-N plus each node's, and the trace joins
    # the dumped story rows on stimulus id
    assert d.flight_recorder, "scheduler flight-recorder tail missing"
    assert d.trace_tail(cat="engine"), d.flight_recorder[:5]
    assert len(d.worker_traces) == 2, list(d.worker_traces)
    assert all(evs for evs in d.worker_traces.values())
    sid = story[0][4]
    assert d.trace_tail(stim=sid), f"no trace events for stimulus {sid}"
    tdir.cleanup()


@gen_test(timeout=120)
async def test_memory_trace_roundtrip():
    """tracemalloc-backed memory introspection (reference memray role):
    start -> allocate-heavy workload -> report shows allocation sites
    and the data-store view -> stop."""
    import numpy as np

    def allocate(i):
        return np.ones((256, 256)) * i  # ~0.5 MB per task

    async with LocalCluster(n_workers=2, threads_per_worker=1) as cluster:
        async with Client(cluster.scheduler_address) as c:
            await c.memory_trace_start()
            futs = c.map(allocate, range(6), pure=False)
            await asyncio.wait_for(c.gather(futs), 60)
            reports = await c.memory_trace_report(top_n=5)
            assert len(reports) == 2
            for addr, rep in reports.items():
                assert rep["status"] == "OK", (addr, rep)
                assert rep["traced_bytes"] > 0
                assert rep["top"] and all(
                    "site" in t and t["bytes"] >= 0 for t in rep["top"]
                )
                assert rep["data_store"]["keys"] >= 0
            stopped = await c.memory_trace_stop()
            # stop is refcounted per server (diagnostics/memtrace.py):
            # each response reports whether the process-global trace is
            # STILL live — only the last owner's stop reads False, and
            # after the broadcast nothing must be tracing
            import tracemalloc

            assert any(r["tracing"] is False for r in stopped.values())
            assert not tracemalloc.is_tracing()


@gen_test(timeout=120)
async def test_device_profile_roundtrip():
    """XLA device-timeline tracing (the reference's low-level profiler
    role, profile.py:550): start -> run jax work (tasks annotated with
    their keys on the device timeline) -> stop reports the trace
    artifact files.  One worker: the XLA profiler is process-global, so
    in-process clusters trace from a single worker (documented in
    diagnostics/device_profile.py)."""
    from distributed_tpu.diagnostics import device_profile

    if not device_profile.available():  # pragma: no cover
        import pytest

        pytest.skip("jax profiler unavailable")

    def devwork(i):
        import jax.numpy as jnp

        return float(jnp.sum(jnp.arange(64.0) * i))

    async with LocalCluster(n_workers=1, threads_per_worker=1) as cluster:
        async with Client(cluster.scheduler_address) as c:
            started = await c.device_profile_start()
            assert all(r["status"] == "OK" for r in started.values()), started
            # a second start must fail cleanly, not wedge the profiler
            again = await c.device_profile_start()
            assert all(r["status"] == "error" for r in again.values())
            futs = c.map(devwork, range(4), pure=False)
            assert await asyncio.wait_for(c.gather(futs), 60) == [
                float(sum(range(64)) * i) for i in range(4)
            ]
            stopped = await c.device_profile_stop()
            for rep in stopped.values():
                assert rep["status"] == "OK", rep
                # the XLA profiler wrote its TensorBoard/XProf artifact
                assert rep["files"], rep
                assert any("plugins/profile" in f for f in rep["files"])
            # stop without a trace running errors cleanly
            idle = await c.device_profile_stop()
            assert all(r["status"] == "error" for r in idle.values())


@gen_test()
async def test_group_timing_buckets():
    """GroupTiming (reference progress.py:344 role): compute seconds
    aggregate into wall-clock buckets per prefix."""
    async with await new_cluster() as cluster:
        async with Client(cluster.scheduler_address) as c:
            import time as _t

            def work(x):
                _t.sleep(0.05)
                return x

            futs = [c.submit(work, i, key=f"gt-{i}") for i in range(6)]
            await c.gather(futs)
            data = await c.scheduler.get_group_timing()
            assert data["bucket_s"] > 0
            assert "gt" in data["series"], data["series"].keys()
            total = sum(data["series"]["gt"])
            assert 0.2 < total < 3.0, total  # ~6 x 50ms of compute


@gen_test()
async def test_eventstream_topic():
    """Opt-in eventstream publishes per-task events on a topic
    (reference diagnostics/eventstream.py role)."""
    async with await new_cluster(n_workers=1) as cluster:
        async with Client(cluster.scheduler_address) as c:
            topic = await c.scheduler.eventstream_start()
            assert topic == "task-events"
            await c.submit(lambda: 41, key="ev-1").result()
            events = await c.get_events(topic)
            acts = [m.get("action") for _, m in events]
            assert "task-finished" in acts, events
            keys = [m.get("key") for _, m in events]
            assert "ev-1" in keys
            await c.scheduler.eventstream_stop()
            n = len(await c.get_events(topic))
            await c.submit(lambda: 42, key="ev-2").result()
            assert len(await c.get_events(topic)) == n  # stopped


# --------------------------------------------------------- flight recorder


def _build_trace_state(n_workers=4, n_tasks=60):
    """Deterministic SchedulerState + pending graph for record/replay
    tests (same construction = same starting state, the replay
    contract's precondition; docs/observability.md)."""
    from distributed_tpu.graph.spec import TaskSpec
    from distributed_tpu.scheduler.state import SchedulerState

    state = SchedulerState(validate=True)
    for i in range(n_workers):
        state.add_worker_state(
            f"tcp://fr:{i}", nthreads=2, memory_limit=2**30, name=f"fr{i}"
        )
    tasks = {f"fr-{i}": TaskSpec(lambda: i) for i in range(n_tasks)}
    deps = {f"fr-{i}": set() for i in range(n_tasks)}
    # a dependent layer so the flood cascades through waiting->processing
    for i in range(0, n_tasks, 3):
        tasks[f"frd-{i}"] = TaskSpec(lambda x: x)
        deps[f"frd-{i}"] = {f"fr-{i}", f"fr-{(i + 1) % n_tasks}"}
    state.update_graph_core(
        tasks, deps, list(tasks), client="frc",
        stimulus_id="fr-graph",
    )
    return state


def _flood_to_memory(state):
    """Report every processing task finished, in payload-sized batches,
    until the whole graph is in memory — the multi-flood run."""
    rounds = 0
    while True:
        batch = [
            (ts.key, ws.address, f"fr-fin-{ts.key}", {"nbytes": 16})
            for ws in state.workers.values()
            for ts in list(ws.processing)
        ]
        if not batch:
            break
        state.stimulus_tasks_finished_batch(batch)
        rounds += 1
        assert rounds < 10_000
    return rounds


def test_record_replay_round_trip():
    """ACCEPTANCE (PR 6): a recorded stimulus trace of a multi-flood run
    re-fed through the batched engine offline reproduces the identical
    transition stream (key, start, finish, stimulus, order)."""
    from distributed_tpu.diagnostics.flight_recorder import (
        replay_stimulus_trace,
        transition_stream,
        verify_journal,
    )

    rec = _build_trace_state()
    mark = len(rec.transition_log)
    rec.trace.journal_start()
    rounds = _flood_to_memory(rec)
    assert rounds >= 2, "not a multi-flood run"
    records = list(rec.trace.journal)
    assert records and all(r["v"] == 1 for r in records)
    # floods journal as ONE record per engine batch (the durable-
    # capture hot-path format; scalar "task-finished" remains for the
    # single-RPC path)
    assert all(
        r["op"] in ("tasks-finished-batch", "transitions") for r in records
    )
    verify_journal(records)

    rep = _build_trace_state()
    mark_b = len(rep.transition_log)
    cm, wm = replay_stimulus_trace(rep, records)
    recorded = transition_stream(rec, mark)
    replayed = transition_stream(rep, mark_b)
    assert recorded, "flood produced no transitions"
    assert recorded == replayed
    # terminal states agree too, not just the log
    assert {k: ts.state for k, ts in rec.tasks.items()} == {
        k: ts.state for k, ts in rep.tasks.items()
    }
    # an edited journal must refuse to replay...
    import pytest

    tampered = [dict(r) for r in records]
    tampered[3] = dict(tampered[3], payload={"key": "tampered"})
    with pytest.raises(ValueError, match="digest"):
        replay_stimulus_trace(_build_trace_state(), tampered)
    # ...and so must a head-truncated one (deque overflow evicts the
    # OLDEST records; replaying from the wrong start would silently
    # present a divergent stream as faithful)
    with pytest.raises(ValueError, match="complete capture"):
        replay_stimulus_trace(_build_trace_state(), records[2:])


def test_record_replay_erred_and_transitions_ops():
    """The journal covers the erred arm and bare recommendation rounds,
    and replay folds mixed consecutive runs correctly."""
    from distributed_tpu.diagnostics.flight_recorder import (
        replay_stimulus_trace,
        transition_stream,
    )

    def drive(state):
        state.trace.journal_start()
        procs = [
            (ts.key, ws.address)
            for ws in state.workers.values()
            for ts in list(ws.processing)
        ]
        fin = [(k, a, f"mx-fin-{k}", {"nbytes": 8}) for k, a in procs[:3]]
        err = [
            (k, a, f"mx-err-{k}", {"exception_text": "boom"})
            for k, a in procs[3:5]
        ]
        state.stimulus_tasks_finished_batch(fin)
        state.stimulus_tasks_erred_batch(err)
        # the replica-release plane (AMM drops): the removal mutates
        # state OUTSIDE the engine and is journaled as its own op,
        # followed by the engine round it recommended
        rel_key, rel_addr = fin[0][0], fin[0][1]
        recs = state.stimulus_release_worker_data(
            rel_key, rel_addr, "mx-rwd"
        )
        if recs:
            state.transitions(recs, "mx-rwd")
        # a bare recommendation round (the release plane)
        state.transitions({procs[5][0]: "released"}, "mx-rel")
        return state

    rec = _build_trace_state()
    mark = len(rec.transition_log)
    drive(rec)
    ops = [r["op"] for r in rec.trace.journal]
    assert "tasks-finished-batch" in ops and "task-erred" in ops
    assert "release-worker-data" in ops and "transitions" in ops

    rep = _build_trace_state()
    mark_b = len(rep.transition_log)
    replay_stimulus_trace(rep, list(rec.trace.journal))
    assert transition_stream(rec, mark) == transition_stream(rep, mark_b)
    # the replayed removal really happened: replica sets agree
    assert {
        k: sorted(ws.address for ws in ts.who_has)
        for k, ts in rec.tasks.items()
    } == {
        k: sorted(ws.address for ws in ts.who_has)
        for k, ts in rep.tasks.items()
    }
    # a record whose digest field was DROPPED (not just stale) is an
    # edit too — verification must refuse, not silently skip
    import pytest

    clipped = [dict(r) for r in rec.trace.journal]
    clipped[1].pop("digest")
    with pytest.raises(ValueError, match="missing"):
        replay_stimulus_trace(_build_trace_state(), clipped)


def test_flight_recorder_ring_and_sampling():
    from distributed_tpu.tracing import FlightRecorder

    tr = FlightRecorder(ring_size=8, enabled=True, sample=1,
                        journal=False, journal_size=4)
    for i in range(20):
        tr.emit("engine", "e", f"s-{i}", n=i)
    assert tr.total == 20
    assert len(tr) == 8
    tail = tr.tail()
    assert [ev["n"] for ev in tail] == list(range(12, 20))
    assert [ev["seq"] for ev in tail] == list(range(12, 20))
    assert tr.tail(3)[0]["n"] == 17
    # disabled recorder emits nothing; sampling keeps 1-in-N
    off = FlightRecorder(ring_size=8, enabled=False)
    off.emit("engine", "e", "s")
    assert off.total == 0
    sam = FlightRecorder(ring_size=64, enabled=True, sample=4)
    for _ in range(40):
        sam.emit_task("transition", "memory", "s")
    assert sam.total == 10


def test_perfetto_export_schema_and_cli(tmp_path):
    """ACCEPTANCE (PR 6): the Perfetto export of a traced run is valid
    Chrome trace_event JSON (schema-validated, no browser needed), via
    both the API and the CLI."""
    import subprocess
    import sys as _sys

    from distributed_tpu.diagnostics.flight_recorder import to_perfetto
    from distributed_tpu.tracing import to_jsonl

    state = _build_trace_state()
    _flood_to_memory(state)
    events = state.trace.tail()
    assert events
    doc = to_perfetto(events)
    # trace_event JSON-object format contract
    assert isinstance(doc["traceEvents"], list)
    assert doc["displayTimeUnit"] in ("ms", "ns")
    cats = set()
    for ev in doc["traceEvents"]:
        assert set(ev) >= {"name", "ph", "ts", "pid", "tid"}, ev
        # "C" = counter samples (shadow divergence / telemetry tracks)
        assert ev["ph"] in ("i", "M", "X", "C")
        if ev["ph"] == "i":
            assert isinstance(ev["ts"], float) and ev["ts"] >= 0
            assert ev["s"] in ("t", "p", "g")
            cats.add(ev["cat"])
    # a bare SchedulerState run has no server, so only the engine-side
    # categories appear here; ingress/egress tracks are asserted on the
    # live cluster in test_trace_endpoint_and_histograms_live
    assert {"engine", "transition"} <= cats
    json.dumps(doc)  # round-trippable

    # CLI: JSONL file in, perfetto JSON out
    src = tmp_path / "trace.jsonl"
    src.write_text(to_jsonl(events))
    out = tmp_path / "out.json"
    proc = subprocess.run(
        [_sys.executable, "-m",
         "distributed_tpu.diagnostics.flight_recorder",
         "--input", str(src), "--perfetto", str(out)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    doc2 = json.loads(out.read_text())
    assert len(doc2["traceEvents"]) == len(doc["traceEvents"])
    # a newer schema major is refused, not mis-rendered
    import pytest

    with pytest.raises(ValueError, match="schema"):
        to_perfetto([{"v": 99, "cat": "engine", "ts": 0.0}])


@gen_test()
async def test_trace_endpoint_and_histograms_live():
    """/trace on both roles serves the schema-versioned JSONL tail, one
    stimulus id joins ingress -> engine -> egress across it, and the
    engine/egress histograms appear on /metrics with observations."""
    from distributed_tpu.tracing import from_jsonl

    async with await new_cluster() as cluster:
        async with Client(cluster.scheduler_address) as c:
            futs = c.map(lambda x: x + 3, range(12), pure=False)
            await c.gather(futs)
            sport = cluster.scheduler.http_server.port
            status, body = await http_get(sport, "/trace")
            assert status == 200
            events = from_jsonl(body)
            assert events and all(ev["v"] == 1 for ev in events)
            by_cat = {}
            for ev in events:
                by_cat.setdefault(ev["cat"], []).append(ev)
            assert by_cat.get("ingress") and by_cat.get("engine")
            assert by_cat.get("egress") and by_cat.get("transition")
            # causal join: some task-finished stimulus appears at
            # ingress AND in the engine pass it folded into
            fin_stims = {
                ev["stim"] for ev in by_cat["ingress"]
                if ev["name"] == "task-finished"
            }
            assert fin_stims & {
                ev["stim"]
                for ev in by_cat["engine"] + by_cat["transition"]
            }
            # the update-graph ingress joins the compute-task egress
            ug = [ev for ev in by_cat["ingress"]
                  if ev["name"] == "update-graph"]
            assert ug and any(
                ev["stim"] == ug[-1]["stim"] for ev in by_cat["egress"]
            )
            # worker role serves its own stimulus timeline
            wport = cluster.workers[0].http_server.port
            status, body = await http_get(wport, "/trace")
            assert status == 200
            wevents = from_jsonl(body)
            assert wevents and all(
                ev["cat"] == "wstim" for ev in wevents
            )
            assert any(ev["name"] == "ComputeTaskEvent" for ev in wevents)
            # histograms made it to /metrics with real observations
            status, body = await http_get(sport, "/metrics")
            text = body.decode()
            for needle in (
                'dtpu_engine_pass_seconds_bucket{le="+Inf"}',
                "dtpu_engine_transition_batch_size_count",
                "dtpu_egress_envelope_msgs_sum",
                "dtpu_trace_events_total",
            ):
                assert needle in text, needle
            count = [
                ln for ln in text.splitlines()
                if ln.startswith("dtpu_engine_pass_seconds_count")
            ][0]
            assert float(count.split()[-1]) > 0


@gen_test()
async def test_route_index_ledger_and_build_info_live():
    """The "/" route index lists every observability route on BOTH
    roles, /ledger serves the decision–outcome snapshot on the
    scheduler, and /metrics carries the dtpu_build_info identity gauge
    (docs/observability.md "Decision ledger & critical-path")."""
    import json as _json

    from distributed_tpu.tracing import from_jsonl

    async with await new_cluster() as cluster:
        async with Client(cluster.scheduler_address) as c:
            await c.gather(c.map(lambda x: x + 1, range(8), pure=False))
            sport = cluster.scheduler.http_server.port
            status, body = await http_get(sport, "/")
            assert status == 200
            idx = _json.loads(body)
            assert idx["role"] == "scheduler"
            assert {
                "/metrics", "/trace", "/telemetry", "/profile", "/ledger",
            } <= set(idx["routes"])
            wport = cluster.workers[0].http_server.port
            status, body = await http_get(wport, "/")
            assert status == 200
            widx = _json.loads(body)
            assert widx["role"] == "worker"
            assert {
                "/metrics", "/trace", "/telemetry", "/profile",
            } <= set(widx["routes"])
            # /ledger: summary head + row tail, every flood placement
            # joined to its memory outcome
            status, body = await http_get(sport, "/ledger")
            assert status == 200
            recs = from_jsonl(body)
            assert recs[0]["type"] == "ledger-summary"
            assert recs[0]["outcomes"].get("memory", 0) >= 8
            rows = [r for r in recs if r["type"] == "ledger-row"]
            assert rows and all(r["v"] == 1 for r in rows)
            # the RPC twin serves the same snapshot shape
            rpc = await c.scheduler.get_ledger(n=4)
            assert rpc[0]["type"] == "ledger-summary"
            assert len(rpc) == 5
            # build info on both roles
            for port, role in ((sport, "scheduler"), (wport, "worker")):
                status, body = await http_get(port, "/metrics")
                line = [
                    ln for ln in body.decode().splitlines()
                    if ln.startswith("dtpu_build_info{")
                ][0]
                assert f'role="{role}"' in line
                assert line.endswith(" 1")
            # ledger regret families made it to the exposition
            status, body = await http_get(sport, "/metrics")
            text = body.decode()
            assert "dtpu_ledger_rows_total" in text
            assert "dtpu_ledger_joined_total" in text


def test_build_info_never_initializes_a_jax_backend():
    """A process serving /metrics (a worker, say) must not take the chip
    by scraping its identity: the backend label stays empty until the
    process has initialized a backend on its own."""
    import subprocess
    import sys

    code = (
        "import jax\n"
        "from distributed_tpu.http.server import build_info_lines\n"
        "public = jax.devices, jax.default_backend\n"
        "def initialize(*a, **kw):\n"
        "    raise AssertionError('the scrape initialized a backend')\n"
        "jax.devices = jax.default_backend = initialize\n"
        "first = build_info_lines('worker')[0]\n"
        "assert 'backend=\"\"' in first, first\n"
        "jax.devices, jax.default_backend = public\n"
        "jax.devices()\n"
        "later = build_info_lines('worker')[0]\n"
        "assert 'backend=\"cpu\"' in later, later\n"
        "print('OK')\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, cwd=repo, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_backend_label_when_the_private_query_moves(monkeypatch):
    """The one private jax query behind the backend label reads as "not
    initialized" should a jax upgrade move it."""
    import jax
    from jax._src import xla_bridge

    from distributed_tpu.http.server import _initialized_backend

    jax.devices()
    assert _initialized_backend() == "cpu"
    monkeypatch.delattr(xla_bridge, "backends_are_initialized")
    assert _initialized_backend() == ""


def test_rate_limiter_filter():
    import logging

    from distributed_tpu.utils.misc import RateLimiterFilter

    f = RateLimiterFilter("spammy", rate=60.0)
    rec = logging.LogRecord("test-rlf", logging.INFO, "f", 1,
                            "spammy message", (), None)
    other = logging.LogRecord("test-rlf", logging.INFO, "f", 1,
                              "normal message", (), None)
    assert f.filter(rec) is True      # first passes
    assert f.filter(rec) is False     # repeat suppressed
    assert f.filter(other) is True    # non-matching always passes


@gen_test()
async def test_computations_track_submissions():
    """Computation objects group each update_graph batch
    (reference scheduler.py:864)."""
    async with await new_cluster(n_workers=1) as cluster:
        async with Client(cluster.scheduler_address) as c:
            await c.gather([c.submit(lambda x: x, i, key=f"ca-{i}")
                            for i in range(3)])
            await c.gather([c.submit(lambda x: -x, i, key=f"cb-{i}")
                            for i in range(2)])
            comps = await c.scheduler.get_computations()
            assert len(comps) >= 2
            names = [set(co["groups"]) for co in comps]
            assert any("ca" in ns for ns in names)
            assert any("cb" in ns for ns in names)
            last = comps[-1]
            assert last["states"].get("memory", 0) + last["states"].get(
                "forgotten", 0
            ) > 0
            assert last["stop"] >= last["start"] or last["stop"] == 0.0


@gen_test()
async def test_computations_resubmission_does_not_duplicate():
    """Resubmitting known keys neither re-attributes old groups to a
    fresh Computation nor floods the bounded history deque."""
    from distributed_tpu.graph.spec import Graph, TaskSpec

    async with await new_cluster(n_workers=1) as cluster:
        async with Client(cluster.scheduler_address) as c:
            def build():
                g = Graph()
                for i in range(3):
                    g.tasks[f"rs-{i}"] = TaskSpec(lambda: 7)
                return g

            outs = [f"rs-{i}" for i in range(3)]
            futs = c.compute_graph(build(), outs)
            await c.gather([futs[k] for k in outs])
            comps = cluster.scheduler.state.computations
            assert sum(1 for co in comps if co.groups) == 1
            n0 = len(comps)
            # resubmit the SAME graph repeatedly (keys known, futures
            # held): no group may be re-attributed, and the bounded
            # history must not grow beyond one trailing empty entry
            for _ in range(5):
                futs2 = c.compute_graph(build(), outs)
                await c.gather([futs2[k] for k in outs])
            attributed = sum(1 for co in comps if co.groups)
            assert attributed == 1, [
                (co.id, sorted(tg.name for tg in co.groups)) for co in comps
            ]
            assert len(comps) <= n0 + 1  # at most one trailing empty


def test_metrics_names_unique_and_documented():
    """Every `dtpu_*` line each exposition emits must be unique (no
    duplicate samples, Prometheus rejects them) and documented in the
    consolidated docs/observability.md metric table — so the metric
    surface cannot drift away from its documentation."""
    from pathlib import Path

    from distributed_tpu.http.server import scheduler_metrics, worker_metrics
    from distributed_tpu.scheduler.state import SchedulerState
    from distributed_tpu.worker.state_machine import WorkerState

    from distributed_tpu.telemetry import LinkTelemetry

    from distributed_tpu.diagnostics.selfprofile import (
        ControlPlaneProfiler,
        LoopWatchdog,
    )

    class _Stealing:
        count = 3

    class _Sched:
        state = SchedulerState()
        extensions = {"stealing": _Stealing()}
        # self-profiling plane (diagnostics/selfprofile.py): the parity
        # gate must cover dtpu_wall_/dtpu_profile_/dtpu_loop_ families
        cp_profiler = ControlPlaneProfiler(idents=lambda: [])
        watchdog = LoopWatchdog()

    _Sched.watchdog.tick()
    with _Sched.state.wall.phase("engine.drain", "pm-stim"):
        pass

    # one task so the labeled per-state samples are exercised
    _Sched.state.new_task("metrics-k", None)
    # seed the telemetry plane so every dtpu_link_/dtpu_prior_/
    # dtpu_costmodel_ family is exercised (the parity gate must cover
    # the full measured-truth surface)
    tel = _Sched.state.telemetry
    tel.fold_rows(
        [["tcp://pm:1", "tcp://pm:2", 1_000_000, 0.01, 2]],
        reporter="tcp://pm:2",
    )
    tel.fold_rows(
        [["tcp://pm:1", "tcp://pm:2", 1_100_000, 0.01, 2]],
        reporter="tcp://pm:1",
    )
    tel.record_rtt("tcp://pm:2", 0.002)
    tel.fold_fine_rows([
        ["execute", "", "inc", "compute", "seconds", 0.5],
        ["execute", "", "inc", "output", "bytes", 1000.0],
        ["execute", "", "inc", "count", "tasks", 2],
    ])
    tel.observe_divergence(1.0, 0.1, True)
    # seed the decision ledger so every dtpu_ledger_* family is
    # exercised (ledger.py; docs/observability.md "Decision ledger"):
    # one joined dep-bearing row populates the regret histograms and
    # the per-prefix/per-link aggregates, one open row the gauge
    led = _Sched.state.ledger
    h = led.file(
        "placement", "pm-led-k", "inc", "tcp://pm:2", "pm-stim",
        0.01, 0.02, True, 4096, 1, 0.5, "tcp://pm:1", "",
    )
    led.join_row(h, "memory", "tcp://pm:2", None, 0.4, tel)
    led.file("steal", "pm-led-open", "inc", "tcp://pm:2", "pm-stim2")
    # seed the sharded-engine + sharded-mirror families (the mesh plan
    # path, PR 8): a real sharded_device_view over the conftest CPU
    # mesh populates the per-shard mirror counters, and one folded
    # engine-shard stat row populates dtpu_engine_shard_*
    _Sched.state.add_worker_state(
        "tcp://pm:9", nthreads=1, memory_limit=2**30, name="pm9"
    )
    from distributed_tpu.ops.partition import make_engine_mesh

    _Sched.state.mirror.sharded_device_view(make_engine_mesh(layout="4x2"))
    _Sched.state.observe_engine_shards(
        [{"shard": 0, "kernel_ms": 0.5, "h2d_bytes": 1024},
         {"shard": 1, "kernel_ms": 0.6, "h2d_bytes": 1024}]
    )
    # seed the native transition engine (scheduler/native_engine.py) so
    # the dtpu_engine_native_* families are exercised where the
    # toolchain exists; a no-g++ box skips them (graceful fallback is
    # the contract, and the names stay documented either way)
    _Sched.state.attach_native(build=True)
    # seed scheduler durability (scheduler/durability.py) so the
    # dtpu_durability_* family is exercised: an attached manager with
    # one epoch's stats
    from distributed_tpu.scheduler.durability import (
        DurabilityManager,
        MemorySink,
    )

    _Sched.durability = DurabilityManager(_Sched.state, MemorySink())
    _Sched.durability.snapshot(full=True)
    # seed the state census + retention sentinel on both roles so every
    # dtpu_census_* family is exercised (diagnostics/census.py;
    # docs/observability.md "State census & retention")
    from distributed_tpu.diagnostics.census import RetentionSentinel

    _Sched.state.census.sentinel = RetentionSentinel(
        _Sched.state.census, trace=_Sched.state.trace
    )
    _Sched.state.census.sentinel.tick()

    class _SpillDict(dict):  # enables the spill metric lines
        spilled_count = 0
        slow_bytes = 0

    class _Worker:
        state = WorkerState(nthreads=1)
        data = _SpillDict()
        get_data_wire_bytes = 0
        telemetry = LinkTelemetry()
        cp_profiler = ControlPlaneProfiler(idents=lambda: [])
        watchdog = LoopWatchdog()

    _Worker.telemetry.record("tcp://pm:2", "tcp://pm:3", 1000, 0.001)
    with _Worker.state.wall.phase("wengine.stimulus", "pm-stim"):
        pass
    _Worker.state.census.sentinel = RetentionSentinel(
        _Worker.state.census, trace=_Worker.state.trace
    )
    _Worker.state.census.sentinel.tick()

    repo = Path(__file__).resolve().parent.parent
    docs = (repo / "docs/observability.md").read_text()

    all_names: set[str] = set()
    for blob in (scheduler_metrics(_Sched()), worker_metrics(_Worker())):
        seen_samples: set[str] = set()
        declared: set[str] = set()
        for line in blob.decode().splitlines():
            if not line:
                continue
            if line.startswith("# TYPE "):
                name = line.split()[2]
                assert name not in declared, f"duplicate TYPE for {name}"
                declared.add(name)
                continue
            if line.startswith("#"):
                continue
            sample = line.rsplit(" ", 1)[0]  # "name{labels}" or "name"
            name = sample.split("{", 1)[0]
            assert name.startswith("dtpu_"), line
            assert sample not in seen_samples, f"duplicate sample {sample}"
            seen_samples.add(sample)
            all_names.add(name)

    # the full surface must be present in this test's expositions —
    # including the engine/egress histogram families, the flight-
    # recorder gauges (PR 6), and the telemetry plane (PR 7)
    assert {"dtpu_scheduler_tasks", "dtpu_worker_tasks_executing",
            "dtpu_wire_pool_bytes", "dtpu_stealing_moves_total",
            "dtpu_worker_spill_count_total",
            "dtpu_engine_transition_batch_size_bucket",
            "dtpu_engine_transition_batch_size_sum",
            "dtpu_engine_transition_batch_size_count",
            "dtpu_engine_pass_seconds_bucket",
            "dtpu_egress_envelope_msgs_bucket",
            "dtpu_trace_events_total",
            "dtpu_trace_ring_events",
            "dtpu_link_bandwidth_bytes_per_second",
            "dtpu_link_latency_seconds",
            "dtpu_link_transfer_bytes_total",
            "dtpu_link_samples_total",
            "dtpu_link_served_wire_bytes_total",
            "dtpu_link_heartbeat_rtt_seconds",
            "dtpu_prior_duration_seconds",
            "dtpu_prior_nbytes",
            "dtpu_prior_tasks_total",
            "dtpu_costmodel_divergence_ratio_bucket",
            "dtpu_costmodel_divergence_ratio_sum",
            "dtpu_costmodel_divergence_ratio_count",
            "dtpu_costmodel_shadow_evals_total",
            "dtpu_costmodel_shadow_measured_total",
            "dtpu_build_info",
            "dtpu_ledger_rows_total",
            "dtpu_ledger_joined_total",
            "dtpu_ledger_unjoined_total",
            "dtpu_ledger_superseded_total",
            "dtpu_ledger_open_rows",
            "dtpu_ledger_regret_seconds_bucket",
            "dtpu_ledger_regret_seconds_sum",
            "dtpu_ledger_regret_seconds_count",
            "dtpu_ledger_prefix_regret_seconds_total",
            "dtpu_ledger_prefix_decisions_total",
            "dtpu_ledger_link_regret_seconds_total",
            "dtpu_ledger_link_transfer_seconds_total",
            "dtpu_ledger_link_decisions_total",
            "dtpu_durability_snapshot_seconds_total",
            "dtpu_durability_snapshot_bytes_total",
            "dtpu_durability_snapshot_rows_total",
            "dtpu_durability_epochs_total",
            "dtpu_durability_base_epochs_total",
            "dtpu_durability_journal_records_total",
            "dtpu_durability_journal_bytes_total",
            "dtpu_durability_replay_records",
            "dtpu_durability_restore_seconds",
            "dtpu_durability_torn_records_total",
            "dtpu_durability_reconcile_corrections_total",
            "dtpu_durability_recovery_awaiting_workers",
            "dtpu_mirror_shard_rows_uploaded_total",
            "dtpu_mirror_shard_bytes_uploaded_total",
            "dtpu_mirror_shard_full_packs_total",
            "dtpu_engine_shard_kernel_ms",
            "dtpu_engine_shard_h2d_bytes_total",
            "dtpu_wall_seconds_total",
            "dtpu_wall_phase_entries_total",
            "dtpu_profile_samples_total",
            "dtpu_profile_idle_samples_total",
            "dtpu_loop_lag_seconds_bucket",
            "dtpu_loop_lag_seconds_sum",
            "dtpu_loop_lag_seconds_count",
            "dtpu_loop_ticks_total",
            "dtpu_loop_stalls_total",
            "dtpu_census_families",
            "dtpu_census_quiesced",
            "dtpu_census_count",
            "dtpu_census_growth_per_s",
            "dtpu_census_audits_total",
            "dtpu_census_audit_failures_total",
            "dtpu_census_findings_total",
            "dtpu_census_leaks_flagged_total"} <= all_names
    if _Sched.state.native is not None:
        assert {"dtpu_engine_native_transitions_total",
                "dtpu_engine_native_escapes_total",
                "dtpu_engine_native_oracle_transitions_total",
                "dtpu_engine_hydrations_total",
                "dtpu_engine_hydration_cache_hits_total",
                "dtpu_engine_hydration_cache_rows"} <= all_names
    undocumented = sorted(n for n in all_names if n not in docs)
    assert not undocumented, (
        f"metrics missing from the docs/observability.md table: "
        f"{undocumented}"
    )

"""Measured-truth telemetry plane tests (telemetry.py;
docs/observability.md): per-link transfer stats, heartbeat deltas and
RTT, task-prefix priors, the shadow cost-model divergence monitor
(read-only proven by property test), /telemetry routes, dumps, and
Perfetto counter tracks."""

from __future__ import annotations

import asyncio
import json

import pytest

from distributed_tpu import config
from distributed_tpu.client.client import Client
from distributed_tpu.deploy.local import LocalCluster
from distributed_tpu.scheduler.server import Scheduler
from distributed_tpu.worker.server import Worker

from conftest import gen_test


async def http_get(port: int, path: str) -> tuple[int, bytes]:
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {path} HTTP/1.0\r\n\r\n".encode())
    await writer.drain()
    data = await reader.read()
    writer.close()
    head, _, body = data.partition(b"\r\n\r\n")
    return int(head.split()[1]), body


# ------------------------------------------------------------------ units


def test_ewma_weighted_update():
    from distributed_tpu.telemetry import EWMA

    e = EWMA(alpha=0.5)
    e.update(10.0)
    assert e.value == 10.0 and e.count == 1
    e.update(20.0)
    assert e.value == 15.0
    # a weight-N row applies the N-fold decay in one step:
    # alpha_eff = 1 - (1-alpha)**N
    a, b = EWMA(0.5), EWMA(0.5)
    a.update(10.0)
    b.update(10.0)
    for _ in range(3):
        a.update(30.0)
    b.update(30.0, weight=3)
    assert a.value == pytest.approx(b.value)
    assert a.count == b.count == 4


def test_link_delta_take_restore_and_fold():
    from distributed_tpu.telemetry import LinkTelemetry

    lt = LinkTelemetry(alpha=0.5, enabled=True)
    lt.record("a", "b", 1_000_000, 0.01)   # 100 MB/s
    lt.record("a", "b", 1_000_000, 0.01)
    lt.record("b", "a", 500, 0.001)
    link = lt.links[("a", "b")]
    assert link.bandwidth.value == pytest.approx(1e8)
    assert link.bandwidth.count == 2
    assert link.bytes_total == 2_000_000
    # t-digest saw both samples
    assert link.digest.count() == 2
    delta = lt.take()
    assert not lt.since_heartbeat
    rows = lt.rows(delta)
    assert sorted(rows) == sorted(
        [["a", "b", 2_000_000, 0.02, 2], ["b", "a", 500, 0.001, 1]]
    )
    # failed heartbeat: restore merges back (and stacks with new samples)
    lt.restore(delta)
    lt.record("a", "b", 1_000_000, 0.01)
    rows2 = dict(
        ((r[0], r[1]), r[2:]) for r in lt.rows(lt.take())
    )
    assert rows2[("a", "b")] == [3_000_000, 0.03, 3]

    # scheduler-side fold: the DESTINATION's report is the bandwidth
    # sample; the SOURCE's report is the cross-check only
    from distributed_tpu.telemetry import ClusterTelemetry

    agg = ClusterTelemetry(alpha=0.5, enabled=True)
    agg.fold_rows([["a", "b", 4_000_000, 0.02, 2]], reporter="b")
    agg.fold_rows([["a", "b", 4_400_000, 0.02, 2]], reporter="a")
    link = agg.links[("a", "b")]
    assert link.bandwidth.value == pytest.approx(2e8)
    assert link.bandwidth.count == 2
    assert link.bytes_total == 4_000_000
    assert link.peer_bytes == 4_400_000 and link.peer_count == 2
    # removing a worker prunes its RTT and every link touching it
    # (restarted workers bind fresh ports; dead LinkStats would leak)
    agg.record_rtt("a", 0.001)
    agg.forget_worker("a")
    assert "a" not in agg.rtt and not agg.links
    # the LOCAL serving-end record (record_peer) also only touches the
    # cross-check totals — its clock stops at the OS write, so it must
    # never fold into the dst-observed bandwidth EWMA — but its delta
    # row still ships (the scheduler classifies by reporter)
    srv = LinkTelemetry(alpha=0.5, enabled=True)
    srv.record_peer("me", "peer", 2048, 0.001)
    link = srv.links[("me", "peer")]
    assert link.peer_bytes == 2048 and link.peer_count == 1
    assert link.bandwidth.count == 0 and link.bytes_total == 0
    assert srv.rows(srv.take()) == [["me", "peer", 2048, 0.001, 1]]
    # disabled collector records nothing
    off = LinkTelemetry(alpha=0.5, enabled=False)
    off.record("a", "b", 1, 1.0)
    off.record_peer("a", "b", 1, 1.0)
    assert not off.links and not off.since_heartbeat


def test_priors_fold_from_fine_rows():
    from distributed_tpu.telemetry import ClusterTelemetry

    tel = ClusterTelemetry(alpha=0.5, enabled=True)
    # one heartbeat's execute rows: 4 tasks of prefix "inc", mean
    # duration 0.25 s, mean output 1000 bytes; non-execute rows ignored
    tel.fold_fine_rows([
        ["execute", "span-1", "inc", "compute", "seconds", 1.0],
        ["execute", "span-1", "inc", "output", "bytes", 4000.0],
        ["execute", "span-1", "inc", "count", "tasks", 4],
        ["gather-dep", "", "", "network", "seconds", 9.0],
        ["execute", "", "", "compute", "seconds", 9.0],  # no prefix
    ])
    prior = tel.priors["inc"]
    assert prior.duration.value == pytest.approx(0.25)
    assert prior.nbytes.value == pytest.approx(1000.0)
    assert prior.n_tasks == 4
    assert len(tel.priors) == 1
    # second heartbeat folds as a count-weighted EWMA step
    tel.fold_fine_rows([
        ["execute", "span-1", "inc", "compute", "seconds", 0.75],
        ["execute", "span-1", "inc", "output", "bytes", 3000.0],
        ["execute", "span-1", "inc", "count", "tasks", 1],
    ])
    assert prior.duration.value == pytest.approx(0.5 * 0.25 + 0.5 * 0.75)
    assert prior.n_tasks == 5
    rec = prior.record()
    assert rec["type"] == "prior" and rec["prefix"] == "inc"


def test_get_comm_cost_measured_fallbacks():
    from distributed_tpu.graph.spec import TaskSpec
    from distributed_tpu.scheduler.state import SchedulerState

    state = SchedulerState(validate=True)
    w0 = state.add_worker_state("tcp://m:0", nthreads=1)
    w1 = state.add_worker_state("tcp://m:1", nthreads=1)
    w2 = state.add_worker_state("tcp://m:2", nthreads=1)
    dep = state.new_task("dep-k", TaskSpec(lambda: 1))
    dep.nbytes = 10_000_000
    state.add_replica(dep, w0)
    state.add_replica(dep, w1)
    ts = state.new_task("use-k", TaskSpec(lambda x: x))
    ts.dependencies.add(dep)

    # no telemetry at all: measured == the constant model, flag False
    constant = state.get_comm_cost(ts, w2)
    measured, used = state.get_comm_cost_measured(ts, w2)
    assert not used and measured == pytest.approx(constant)

    # rtt known but link unseen: constant bandwidth + measured fixed cost
    state.telemetry.record_rtt("tcp://m:2", 0.005)
    measured, used = state.get_comm_cost_measured(ts, w2)
    assert used
    assert measured == pytest.approx(
        dep.nbytes / state.bandwidth + 0.005
    )

    # measured links: the BEST holder link prices the dep
    state.telemetry.fold_rows(
        [["tcp://m:0", "tcp://m:2", 10_000_000, 0.1, 1],   # 100 MB/s
         ["tcp://m:1", "tcp://m:2", 10_000_000, 0.01, 1]],  # 1 GB/s
        reporter="tcp://m:2",
    )
    measured, used = state.get_comm_cost_measured(ts, w2)
    assert used
    best = state.telemetry.links[("tcp://m:1", "tcp://m:2")]
    assert measured == pytest.approx(
        dep.nbytes / best.bandwidth.value + best.latency.value
    )
    # a resident dep costs nothing in either model
    state.add_replica(dep, w2)
    assert state.get_comm_cost_measured(ts, w2) == (0.0, False)


def test_divergence_ratio_clamps_and_extremes():
    from distributed_tpu.telemetry import RATIO_CLAMP, ClusterTelemetry

    tel = ClusterTelemetry(alpha=0.5, enabled=True)
    # extremes are None until a MEASURED eval happens (a 1.0 default
    # would report a never-observed perfect agreement)
    assert tel.ratio_min is None and tel.ratio_max is None
    assert tel.observe_divergence(1.0, 0.1, True) == pytest.approx(0.1)
    assert tel.ratio_min == tel.ratio_max == pytest.approx(0.1)
    assert tel.observe_divergence(0.0, 0.0, False) == 1.0
    assert tel.observe_divergence(0.0, 5.0, True) == RATIO_CLAMP
    assert tel.hist_divergence.count == 3
    assert tel.shadow_evals == 3 and tel.shadow_measured == 2
    assert tel.ratio_min == pytest.approx(0.1)
    assert tel.ratio_max == RATIO_CLAMP
    rec = [r for r in tel.snapshot() if r["type"] == "divergence"][0]
    assert rec["evals"] == 3 and rec["measured"] == 2


# --------------------------------------------- shadow mode is READ-ONLY


def _build_decision_state(enabled: bool):
    """Identical graph + fleet, telemetry enabled/disabled; the enabled
    arm gets measured links wildly different from the constant."""
    from distributed_tpu.graph.spec import TaskSpec
    from distributed_tpu.scheduler.state import SchedulerState

    with config.set({"scheduler.telemetry.enabled": enabled}):
        state = SchedulerState(validate=True)
    addrs = [f"tcp://pd:{i}" for i in range(6)]
    for a in addrs:
        state.add_worker_state(a, nthreads=2, memory_limit=2**30)
    # measured links at 10x the constant bandwidth on every pair (the
    # disabled arm gets them too — proving they are never consulted)
    state.telemetry.fold_rows(
        [[a, b, 1_000_000_000, 1.0, 4] for a in addrs for b in addrs
         if a != b],
        reporter="",
    )
    for a in addrs:
        state.telemetry.record_rtt(a, 0.003)
    tasks = {f"src-{i}": TaskSpec(lambda: 1) for i in range(40)}
    deps: dict = {f"src-{i}": set() for i in range(40)}
    for i in range(20):
        tasks[f"mid-{i}"] = TaskSpec(lambda x: x)
        deps[f"mid-{i}"] = {f"src-{i}", f"src-{i + 1}"}
    for i in range(5):
        tasks[f"top-{i}"] = TaskSpec(lambda x: x)
        deps[f"top-{i}"] = {f"mid-{4 * i}", f"mid-{4 * i + 1}"}
    state.update_graph_core(
        tasks, deps, list(tasks), client="pd", stimulus_id="pd-graph"
    )
    return state


def _flood(state, nbytes=5_000_000):
    while True:
        batch = [
            (ts.key, ws.address, f"fin-{ts.key}", {"nbytes": nbytes})
            for ws in state.workers.values()
            for ts in list(ws.processing)
        ]
        if not batch:
            return
        state.stimulus_tasks_finished_batch(batch)


def test_shadow_mode_identical_decisions_on_off():
    """ACCEPTANCE: telemetry enabled vs disabled produces bit-identical
    placement AND steal decisions — the shadow monitor is read-only —
    while the enabled arm's divergence histogram records real nonzero
    divergence (measured 10x bandwidth vs the constant)."""
    from distributed_tpu.diagnostics.flight_recorder import (
        transition_stream,
    )
    from distributed_tpu.scheduler.stealing import WorkStealing
    from distributed_tpu.utils.test import StubScheduler

    streams, placements, steals, sents = [], [], [], []
    for enabled in (True, False):
        state = _build_decision_state(enabled)
        mark = len(state.transition_log)
        _flood(state)
        streams.append(transition_stream(state, mark))
        placements.append({
            k: ts.processing_on.address if ts.processing_on else None
            for k, ts in sorted(state.tasks.items())
        })
        # steal cycle: pile a restricted burst on one worker, balance
        from distributed_tpu.graph.spec import TaskSpec

        w0 = next(iter(state.workers.values()))
        state.new_task_prefix("stl").add_duration(0.05)
        stasks = {f"stl-{i}": TaskSpec(lambda: 1) for i in range(60)}
        sched = StubScheduler(state)
        stealing = WorkStealing(sched)
        state.update_graph_core(
            stasks, {k: set() for k in stasks}, list(stasks),
            client="pd",
            annotations_by_key={
                k: {"workers": [w0.address], "allow_other_workers": True}
                for k in stasks
            },
            stimulus_id="pd-steal",
        )
        stealing.balance()
        steals.append({
            k: (info.victim.address, info.thief.address)
            for k, info in sorted(stealing.in_flight.items())
        })
        sents.append(
            [sorted(wm) for _cm, wm in sched.sent]
        )
        if enabled:
            tel = state.telemetry
            assert tel.shadow_evals > 0
            assert tel.hist_divergence.count == tel.shadow_evals
            assert tel.shadow_measured > 0
            # measured 10x bandwidth: the ratio extremes moved off 1.0
            assert tel.ratio_min < 0.9, (tel.ratio_min, tel.ratio_max)
            # the sampled flight-recorder shadow hops carry stimuli
            shadow = [
                ev for ev in state.trace.tail() if ev["cat"] == "shadow"
            ]
            assert shadow and all(ev["stim"] for ev in shadow)
            assert {ev["name"] for ev in shadow} >= {"placement"}
        else:
            assert state.telemetry.shadow_evals == 0
            assert state.telemetry.hist_divergence.count == 0

    on, off = 0, 1
    assert streams[on] == streams[off]
    assert placements[on] == placements[off]
    assert steals[on] and steals[on] == steals[off]
    assert sents[on] == sents[off]


def test_steal_shadow_event_carries_stimulus():
    """Steal pricing records its own shadow hop under the move's
    stimulus id (stealing.move_task_request)."""
    from distributed_tpu.scheduler.stealing import WorkStealing
    from distributed_tpu.utils.test import StubScheduler

    state = _build_decision_state(True)
    _flood(state)
    from distributed_tpu.graph.spec import TaskSpec

    w0 = next(iter(state.workers.values()))
    state.new_task_prefix("stl").add_duration(0.05)
    stasks = {f"stl-{i}": TaskSpec(lambda: 1) for i in range(60)}
    sched = StubScheduler(state)
    stealing = WorkStealing(sched)
    state.update_graph_core(
        stasks, {k: set() for k in stasks}, list(stasks), client="pd",
        annotations_by_key={
            k: {"workers": [w0.address], "allow_other_workers": True}
            for k in stasks
        },
        stimulus_id="pd-steal",
    )
    stealing.balance()
    assert stealing.in_flight
    steal_shadow = [
        ev for ev in state.trace.tail()
        if ev["cat"] == "shadow" and ev["name"] == "steal"
    ]
    assert steal_shadow
    stims = {info.stimulus_id for info in stealing.in_flight.values()}
    assert {ev["stim"] for ev in steal_shadow} <= stims | {""}
    assert any(ev["stim"] in stims for ev in steal_shadow)


# ------------------------------------------------------------- live wire


@gen_test(timeout=60)
async def test_tcp_echo_link_samples_track_measured_bandwidth():
    """A TCP echo through the real comm stack, each round trip filed as
    one link sample the way ``_gather_dep`` files a fetch: the
    collector's EWMA bandwidth lands within 2x of the echo's own MB/s,
    and on loopback it is more than 1.5x away from the
    ``scheduler.bandwidth`` constant (the constant's error, kept as a
    checked artifact)."""
    import time

    import numpy as np

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
    src, dst = listener.contact_address, "tcp://requester"
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
            collector.record(src, dst, size, mono() - m0)
        echo_bw = size * reps / (time.perf_counter() - t0)
    finally:
        await comm.close()
        listener.stop()
    link = collector.links[(src, dst)]
    assert link.bandwidth.count == reps
    assert link.bytes_total == size * reps
    measured = link.bandwidth.value
    assert echo_bw / 2 <= measured <= echo_bw * 2, (measured, echo_bw)
    ratio = measured / float(config.get("scheduler.bandwidth"))
    assert ratio > 1.5 or ratio < 1 / 1.5, ratio
    # the heartbeat delta's encode keeps the sample count
    rows = collector.rows(collector.take())
    assert rows and rows[0][4] == reps


@gen_test(timeout=120)
async def test_link_samples_both_ends_agree_over_tcp():
    """SATELLITE: get_data true-wire-bytes attribute to per-link samples
    on BOTH ends, and the two ends agree within framing overhead —
    asserted on the scheduler's fleet aggregate (the serving end's
    wire bytes land as the peer cross-check next to the requesting
    end's payload bytes)."""
    import numpy as np

    async with Scheduler(validate=True) as s:  # tcp by default
        async with Worker(s.address, nthreads=1,
                          heartbeat_interval=0.1) as a:
            async with Worker(s.address, nthreads=1,
                              heartbeat_interval=0.1) as b:
                async with Client(s.address) as c:
                    def chunk(i):
                        return np.full((512, 256), float(i))  # ~1 MB

                    chunks = [
                        c.submit(chunk, i, pure=False,
                                 workers=[[a.address, b.address][i % 2]])
                        for i in range(6)
                    ]
                    outs = [
                        c.submit(lambda x, y: float(x.sum() + y.sum()),
                                 u, v, pure=False)
                        for u, v in zip(chunks[:-1], chunks[1:])
                    ]
                    await asyncio.wait_for(c.gather(outs), 60)

                    # each worker recorded BOTH ends locally
                    for w, peer in ((a, b), (b, a)):
                        links = w.telemetry.links
                        assert (peer.address, w.address) in links, (
                            w.address, list(links)
                        )
                        assert (w.address, peer.address) in links

                    # heartbeats ship both views to the scheduler;
                    # wait until the aggregate caught up with BOTH
                    # workers' local totals (the two ends' deltas land
                    # on different heartbeats)
                    tel = s.state.telemetry
                    pairs = [(a, b), (b, a)]
                    deadline = asyncio.get_running_loop().time() + 30

                    def caught_up():
                        for src, dst in pairs:
                            key = (src.address, dst.address)
                            agg = tel.links.get(key)
                            req = dst.telemetry.links.get(key)
                            srv = src.telemetry.links.get(key)
                            if agg is None or req is None or srv is None:
                                return False
                            if agg.bytes_total != req.bytes_total:
                                return False
                            if agg.peer_bytes != srv.peer_bytes:
                                return False
                        return True

                    while not caught_up():
                        assert (
                            asyncio.get_running_loop().time() < deadline
                        ), {k: (v.bytes_total, v.peer_bytes)
                            for k, v in tel.links.items()}
                        await asyncio.sleep(0.05)
                    for src, dst in pairs:
                        link = tel.links[(src.address, dst.address)]
                        # the two ends recorded the same serves
                        assert link.bandwidth.count == link.peer_count, (
                            link.src, link.dst, link.bandwidth.count,
                            link.peer_count,
                        )
                        # true wire bytes vs sizeof payload: equal up
                        # to framing/serialization overhead (numpy
                        # payloads serialize ~1:1; headers are KBs)
                        assert link.peer_bytes == pytest.approx(
                            link.bytes_total, rel=0.1, abs=64 * 1024
                        ), (link.src, link.dst, link.bytes_total,
                            link.peer_bytes)
                        # framing ADDS bytes (sizeof vs serialized can
                        # differ by object-header noise, nothing more)
                        assert link.peer_bytes >= link.bytes_total - 4096
                        assert link.bandwidth.value > 0


@gen_test(timeout=120)
async def test_telemetry_routes_rtt_metrics_and_dump():
    """ACCEPTANCE: the snapshot (link EWMAs + t-digest quantiles +
    priors) is fetchable via /telemetry on BOTH roles, the heartbeat
    RTT EWMA shows up as dtpu_link_heartbeat_rtt_seconds, the
    divergence histogram is nonzero on a loopback cluster whose
    measured bandwidth differs from the constant, and the snapshot
    ships in cluster dumps."""
    import numpy as np

    from distributed_tpu.diagnostics.cluster_dump import DumpArtefact
    from distributed_tpu.tracing import from_jsonl

    async with LocalCluster(
        n_workers=2, threads_per_worker=1,
        worker_kwargs={"heartbeat_interval": 0.1},
    ) as cluster:
        async with Client(cluster.scheduler_address) as c:
            addrs = [w.address for w in cluster.workers]

            def chunk(i):
                return np.full((512, 256), float(i))  # ~1 MB

            async def cross_wave(offset):
                chunks = [
                    c.submit(chunk, offset + i, pure=False,
                             workers=[addrs[i % 2]])
                    for i in range(6)
                ]
                outs = [
                    c.submit(lambda x, y: float(x.sum() + y.sum()),
                             u, v, pure=False)
                    for u, v in zip(chunks[:-1], chunks[1:])
                ]
                await asyncio.wait_for(c.gather(outs), 60)

            await cross_wave(0)
            tel = cluster.scheduler.state.telemetry
            deadline = asyncio.get_running_loop().time() + 30
            while not (tel.links and tel.rtt and tel.priors):
                assert asyncio.get_running_loop().time() < deadline
                await asyncio.sleep(0.05)
            # second wave AFTER links are measured: placement shadow
            # evals now price deps over measured links
            await cross_wave(100)
            while not tel.shadow_measured:
                assert asyncio.get_running_loop().time() < deadline
                await asyncio.sleep(0.05)

            # divergence histogram is NONZERO and the measured ratio
            # moved off 1.0 (loopback bandwidth != the 100 MB/s
            # constant)
            assert tel.hist_divergence.count > 0
            assert (tel.ratio_min, tel.ratio_max) != (1.0, 1.0)

            # /telemetry on the scheduler role
            sport = cluster.scheduler.http_server.port
            status, body = await http_get(sport, "/telemetry")
            assert status == 200
            recs = from_jsonl(body)
            by_type: dict = {}
            for r in recs:
                by_type.setdefault(r["type"], []).append(r)
            assert by_type.get("link") and by_type.get("rtt")
            assert by_type.get("prior") and by_type.get("divergence")
            link = by_type["link"][0]
            assert link["bandwidth"] > 0 and "bw_q50" in link
            assert {"bw_q90", "bw_q99"} <= set(link)
            prior = [
                p for p in by_type["prior"] if p["prefix"] == "chunk"
            ][0]
            assert prior["duration"] > 0 and prior["nbytes"] > 500_000
            assert by_type["divergence"][0]["count"] > 0

            # /telemetry on the worker role
            wport = cluster.workers[0].http_server.port
            status, body = await http_get(wport, "/telemetry")
            assert status == 200
            wrecs = from_jsonl(body)
            assert wrecs and all(r["type"] == "link" for r in wrecs)

            # RTT + divergence + priors on /metrics
            status, body = await http_get(sport, "/metrics")
            text = body.decode()
            for needle in (
                "dtpu_link_heartbeat_rtt_seconds",
                "dtpu_link_bandwidth_bytes_per_second",
                'dtpu_costmodel_divergence_ratio_bucket{le="+Inf"}',
                "dtpu_costmodel_shadow_measured_total",
                "dtpu_prior_duration_seconds",
                "dtpu_prior_tasks_total",
            ):
                assert needle in text, needle
            rtt_line = [
                ln for ln in text.splitlines()
                if ln.startswith("dtpu_link_heartbeat_rtt_seconds{")
            ][0]
            assert float(rtt_line.rsplit(" ", 1)[1]) > 0
            status, body = await http_get(wport, "/metrics")
            assert b"dtpu_link_bandwidth_bytes_per_second" in body

            # the snapshot ships in cluster dumps
            state = await c.scheduler.get_cluster_state()
            d = DumpArtefact(state)
            assert d.telemetry_records("link")
            assert d.telemetry_records("divergence")[0]["evals"] > 0
            # excluding it works like the other artefacts
            lean = await c.scheduler.get_cluster_state(
                exclude=["telemetry"]
            )
            assert "telemetry" not in lean["scheduler"]


@gen_test(timeout=120)
async def test_span_metrics_survive_worker_restart():
    """SATELLITE: cumulative_worker_metrics heartbeat-delta aggregation
    across a worker restart — re-registration must neither double-count
    nor lose the pre-restart cumulative samples."""
    async with Scheduler(validate=True) as s:
        spans = s.spans

        def exec_count():
            return sum(
                v for k, v in spans.cumulative_worker_metrics.items()
                if k[0] == "execute" and k[3] == "count"
            )

        async with Worker(s.address, nthreads=1,
                          heartbeat_interval=0.05) as a:
            async with Client(s.address) as c:
                await c.gather(c.map(lambda x: x + 1, range(7),
                                     pure=False))
                deadline = asyncio.get_running_loop().time() + 30
                while exec_count() < 7:
                    assert asyncio.get_running_loop().time() < deadline
                    await asyncio.sleep(0.05)
                # deltas were taken: a few idle heartbeats must not
                # re-add them
                await a.heartbeat()
                await a.heartbeat()
                assert exec_count() == 7
        # worker gone; pre-restart samples survive removal
        assert exec_count() == 7
        async with Worker(s.address, nthreads=1,
                          heartbeat_interval=0.05):
            async with Client(s.address) as c:
                await c.gather(c.map(lambda x: x + 1, range(5),
                                     pure=False))
                deadline = asyncio.get_running_loop().time() + 30
                while exec_count() < 12:
                    assert asyncio.get_running_loop().time() < deadline
                    await asyncio.sleep(0.05)
                await asyncio.sleep(0.2)  # extra heartbeats: no double
                assert exec_count() == 12


# ------------------------------------------------------------- exporters


def test_perfetto_counter_tracks_and_cli(tmp_path):
    """SATELLITE: the Perfetto exporter renders telemetry snapshots and
    shadow events as counter tracks on the stimulus timeline."""
    import subprocess
    import sys as _sys

    from distributed_tpu.diagnostics.flight_recorder import to_perfetto
    from distributed_tpu.tracing import to_jsonl

    state = _build_decision_state(True)
    _flood(state)
    events = state.trace.tail()
    telemetry = state.telemetry.snapshot()
    assert any(ev["cat"] == "shadow" for ev in events)
    doc = to_perfetto(events, telemetry=telemetry)
    counters = [
        ev for ev in doc["traceEvents"] if ev["ph"] == "C"
    ]
    names = {ev["name"] for ev in counters}
    assert "costmodel divergence ratio" in names
    assert any(n.startswith("link ") and n.endswith(" MB/s")
               for n in names)
    assert any(n.startswith("rtt ") for n in names)
    for ev in counters:
        assert ev["ts"] >= 0 and isinstance(ev["args"], dict)
    json.dumps(doc)
    # the shadow swimlane metadata track exists
    assert any(
        ev.get("ph") == "M"
        and "shadow" in (ev.get("args") or {}).get("name", "")
        for ev in doc["traceEvents"]
    )

    # CLI: --telemetry renders the counter tracks
    src = tmp_path / "trace.jsonl"
    src.write_text(to_jsonl(events))
    tsrc = tmp_path / "telemetry.jsonl"
    tsrc.write_text(to_jsonl(telemetry))
    out = tmp_path / "out.json"
    proc = subprocess.run(
        [_sys.executable, "-m",
         "distributed_tpu.diagnostics.flight_recorder",
         "--input", str(src), "--telemetry", str(tsrc),
         "--perfetto", str(out)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    doc2 = json.loads(out.read_text())
    assert any(
        ev["ph"] == "C" and ev["name"].startswith("link ")
        for ev in doc2["traceEvents"]
    )

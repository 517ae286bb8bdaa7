"""Continuous pipeline profiler + fleet capacity telemetry
(obs/profiler.py): span folding with self-time semantics, the no-op
fast path when disabled, tick/utilization/bottleneck derivation,
/profile vs --profile-json parity, process-level gauges, snapshot
percentiles, FleetCapacity headroom math, and the Hello capacity
advertisement -> ShardedFilterClient re-export over a real gRPC hop."""

import asyncio
import json
import time

import pytest

from klogs_tpu.obs import Registry, register_all, snapshot, trace
from klogs_tpu.obs.profiler import (
    PROFILER,
    STAGES,
    FleetCapacity,
    PipelineProfiler,
    refresh_process_metrics,
)

run = asyncio.run


@pytest.fixture(autouse=True)
def _clean_profiler():
    PROFILER.reset()
    trace.reset(None)
    yield
    PROFILER.reset()
    trace.reset(None)


def _span_doc(name, dur, span_id="a" * 16, parent=None):
    return {"name": name, "duration_s": dur, "span_id": span_id,
            "parent_id": parent, "trace_id": "t" * 32}


# -- enablement / the no-op fast path ---------------------------------

def test_disabled_profiler_installs_nothing(monkeypatch):
    """The profiler-off contract: with no enablement, the tracer sink
    is never installed and spans allocate nothing in the profiler —
    the per-span cost of a disabled profiler is exactly zero."""
    monkeypatch.delenv("KLOGS_PROFILE_SAMPLE", raising=False)
    assert PROFILER.maybe_enable() is False
    assert PROFILER.on_span not in trace.TRACER._sinks
    trace.TRACER.configure(1.0)
    with trace.TRACER.span("device.fetch"):
        pass
    assert PROFILER._stages == {}
    assert PROFILER.profile_doc()["enabled"] is False


def test_sample_zero_kills_even_explicit_enable(monkeypatch):
    """KLOGS_PROFILE_SAMPLE=0 is the kill switch: an explicit
    --profile-json-style enable() must stay off."""
    monkeypatch.setenv("KLOGS_PROFILE_SAMPLE", "0")
    assert PROFILER.enable() is False
    assert PROFILER.enabled is False
    assert PROFILER.on_span not in trace.TRACER._sinks


def test_profile_sample_env_validation(monkeypatch):
    for bad in ("nope", "-0.5", "1.5"):
        monkeypatch.setenv("KLOGS_PROFILE_SAMPLE", bad)
        with pytest.raises(ValueError, match="KLOGS_PROFILE_SAMPLE"):
            PipelineProfiler().maybe_enable()


def test_enable_raises_trace_sampling_unless_pinned(monkeypatch):
    monkeypatch.delenv("KLOGS_TRACE_SAMPLE", raising=False)
    trace.reset(None)
    assert not trace.TRACER.enabled
    PROFILER.enable(0.5)
    assert trace.TRACER.sample_rate() == 0.5
    # An explicit env rate (even 0) always wins.
    monkeypatch.setenv("KLOGS_TRACE_SAMPLE", "0")
    trace.reset(None)
    PROFILER.enable(1.0)
    assert trace.TRACER.sample_rate() == 0.0


# -- span folding -----------------------------------------------------

def test_fold_self_time_subtracts_children():
    """Stages nest (shard.dispatch wraps rpc.client); each folds its
    SELF time or the outermost wrapper always wins the bottleneck."""
    PROFILER.enable(1.0)
    PROFILER.on_span(_span_doc("device.fetch", 0.4, span_id="c" * 16,
                               parent="p" * 16))
    PROFILER.on_span(_span_doc("coalescer.dispatch", 0.5,
                               span_id="p" * 16))
    with PROFILER._lock:
        stages = {k: tuple(v) for k, v in PROFILER._stages.items()}
    assert stages["device.fetch"][0] == pytest.approx(0.4)
    assert stages["coalescer.dispatch"][0] == pytest.approx(0.1)


def test_fold_ignores_unknown_names_and_missing_duration():
    PROFILER.enable(1.0)
    PROFILER.on_span(_span_doc("not.a.stage", 1.0))
    PROFILER.on_span({"name": "device.fetch", "duration_s": None,
                      "span_id": "x" * 16, "parent_id": None})
    assert PROFILER._stages == {}


def test_child_busy_bounded():
    PROFILER.enable(1.0)
    for i in range(4100):
        PROFILER.on_span(_span_doc("rpc.client", 0.001,
                                   span_id=f"{i:016x}",
                                   parent=f"{i + 1000000:016x}"))
    assert len(PROFILER._child_busy) <= 4096


# -- ticking ----------------------------------------------------------

def test_tick_utilization_bottleneck_and_metric_sync():
    r = Registry()
    register_all(r)
    PROFILER.enable(1.0)
    PROFILER.bind_registry(r)
    PROFILER.tick()  # open the window
    PROFILER.on_span(_span_doc("device.fetch", 0.08, span_id="1" * 16))
    PROFILER.on_span(_span_doc("rpc.server", 0.02, span_id="2" * 16))
    time.sleep(0.05)
    doc = PROFILER.tick()
    assert doc["bottleneck"] == "device.fetch"
    assert doc["stages"]["device.fetch"]["utilization"] > \
        doc["stages"]["rpc.server"]["utilization"] > 0
    busy = r.family("klogs_profile_stage_busy_seconds_total")
    assert busy.labels(stage="device.fetch").value == pytest.approx(0.08)
    # A second tick without new spans must not double-count counters.
    PROFILER.tick()
    assert busy.labels(stage="device.fetch").value == pytest.approx(0.08)
    assert r.family("klogs_profile_stage_spans_total").labels(
        stage="device.fetch").value == 1
    assert PROFILER.max_utilization() is not None


def test_probes_sampled_and_broken_probe_ignored():
    PROFILER.enable(1.0)
    PROFILER.add_probe("coalescer.queue_depth", lambda: 7)

    def boom() -> float:
        raise RuntimeError("probe died")

    PROFILER.add_probe("bad.probe", boom)
    doc = PROFILER.tick()
    assert doc["samples"] == {"coalescer.queue_depth": 7.0}
    # remove_probe with fn only drops the registered owner.
    other = lambda: 1.0  # noqa: E731
    PROFILER.remove_probe("coalescer.queue_depth", other)
    assert "coalescer.queue_depth" in PROFILER._probes
    PROFILER.remove_probe("coalescer.queue_depth")
    assert "coalescer.queue_depth" not in PROFILER._probes


def test_async_service_registers_and_drops_probes():
    from klogs_tpu.filters.base import FilterStats, LogFilter

    class Echo(LogFilter):
        def match_lines(self, lines):
            return [True] * len(lines)

    PROFILER.enable(1.0)
    from klogs_tpu.filters.async_service import AsyncFilterService

    svc = AsyncFilterService(Echo(), stats=FilterStats())
    doc = PROFILER.tick()
    for name in ("coalescer.queue_depth", "coalescer.pending_lines",
                 "device.in_flight_used", "device.fetch_queue"):
        assert name in doc["samples"], name
    svc.close()
    assert PROFILER.tick()["samples"] == {}


def test_run_ticker_final_tick_and_stop():
    async def scenario():
        PROFILER.enable(1.0)
        stop = asyncio.Event()
        task = asyncio.get_running_loop().create_task(
            PROFILER.run_ticker(stop, interval_s=0.02))
        await asyncio.sleep(0.06)
        stop.set()
        await task

    run(scenario())
    assert PROFILER._last_doc is not None


# -- /profile endpoint vs --profile-json stream -----------------------

def test_profile_endpoint_equals_profile_json_stream(tmp_path):
    """The snapshot-parity discipline /traces set for tracing: the
    endpoint serves the exact last ticked doc, which is also the last
    JSONL line — the two surfaces can never disagree."""
    from klogs_tpu.obs import MetricsHTTPServer
    from tests.conftest import http_get

    path = tmp_path / "profile.jsonl"
    PROFILER.enable(1.0)
    PROFILER.set_json_path(str(path))
    with trace.TRACER.span("device.fetch"):
        pass
    PROFILER.tick()
    time.sleep(0.01)
    PROFILER.tick()

    async def scenario():
        srv = MetricsHTTPServer(Registry())
        port = await srv.start()
        try:
            return await http_get(port, "/profile")
        finally:
            await srv.stop()

    status, body = run(scenario())
    assert status == 200
    served = json.loads(body)
    lines = [json.loads(ln) for ln in
             path.read_text().strip().splitlines()]
    assert len(lines) == 2
    assert served == lines[-1]
    assert served["stages"]["device.fetch"]["spans"] == 1
    assert set(served["stages"]) <= set(STAGES)


# -- process-level gauges ---------------------------------------------

def test_process_metrics_refresh_and_scrape():
    r = Registry()
    register_all(r)
    refresh_process_metrics(r)
    assert r.family("klogs_process_uptime_seconds").value > 0
    assert r.family("klogs_process_rss_bytes").value > 1 << 20

    from klogs_tpu.obs import MetricsHTTPServer
    from tests.conftest import http_get

    async def scenario():
        srv = MetricsHTTPServer(r)
        port = await srv.start()
        try:
            return await http_get(port, "/metrics")
        finally:
            await srv.stop()

    _, body = run(scenario())
    text = body.decode()
    assert "klogs_process_uptime_seconds " in text
    assert "klogs_process_rss_bytes " in text


# -- snapshot percentiles (--stats-json satellite) --------------------

def test_snapshot_reservoir_percentiles():
    r = Registry()
    h = r.histogram("t_lat_seconds", "help", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.02, 0.05, 0.2, 0.5):
        h.observe(v)
    doc = snapshot(r)
    sample = doc["t_lat_seconds"]["samples"][0]
    # Additive keys next to the existing p50/p99 layout.
    assert sample["p50"] == pytest.approx(0.05)
    assert sample["p90"] == pytest.approx(0.5)
    assert sample["p99"] == pytest.approx(0.5)
    assert set(sample) >= {"buckets", "sum", "count", "p50", "p90", "p99"}


# -- FleetCapacity ----------------------------------------------------

def test_capacity_offered_admitted_and_rate(monkeypatch):
    monkeypatch.setenv("KLOGS_FLEET_CAPACITY_LPS", "1000")
    r = Registry()
    register_all(r)
    cap = FleetCapacity(registry=r)
    cap.note_offered(500)
    cap.note_admitted(400)
    assert cap.rates() == (None, None)  # baseline sample too fresh
    time.sleep(0.3)
    offered_lps, admitted_lps = cap.rates()
    assert offered_lps > admitted_lps > 0
    doc = cap.doc()
    assert doc["offered_lines"] == 500 and doc["admitted_lines"] == 400
    # Saturated vs the 1000 l/s envelope: admitted ~1300 l/s -> 0.
    assert doc["headroom"] == 0.0
    assert r.family("klogs_fleet_offered_lines_total").value == 500
    assert r.family("klogs_fleet_headroom").value == 0.0


def test_capacity_headroom_from_envelope_idle(monkeypatch):
    monkeypatch.setenv("KLOGS_FLEET_CAPACITY_LPS", "1000000")
    cap = FleetCapacity()
    # A fresh idle server advertises full rate-headroom.
    assert cap.headroom() == 1.0
    cap.note_admitted(100)
    time.sleep(0.3)
    h = cap.headroom()
    assert 0.9 < h <= 1.0


def test_capacity_headroom_utilization_fallback(monkeypatch):
    """Without an envelope the profiler's peak stage utilization
    stands in, clamped at 1 (concurrency-inclusive)."""
    monkeypatch.delenv("KLOGS_FLEET_CAPACITY_LPS", raising=False)
    prof = PipelineProfiler()
    cap = FleetCapacity(envelope_lps=0.0, profiler=prof)
    assert cap.headroom() is None  # no signal at all
    prof.enable(1.0)
    prof.tick()
    prof.on_span(_span_doc("device.fetch", 0.05))
    time.sleep(0.07)
    prof.tick()
    h = cap.headroom()
    assert h is not None and 0.0 <= h < 1.0
    prof.reset()


def test_capacity_envelope_validation(monkeypatch):
    monkeypatch.setenv("KLOGS_FLEET_CAPACITY_LPS", "-3")
    with pytest.raises(ValueError, match="KLOGS_FLEET_CAPACITY_LPS"):
        FleetCapacity().envelope_lps()


def test_headroom_without_envelope_reads_live_utilization(monkeypatch):
    """With no calibrated envelope, headroom comes from the live
    profiler alone: None before any signal (no rate is ever assumed
    from a record of another run), 0.0 once a stage is saturated, and
    an explicit operator calibration still outranks utilization."""
    monkeypatch.delenv("KLOGS_FLEET_CAPACITY_LPS", raising=False)
    prof = PipelineProfiler()
    cap = FleetCapacity(profiler=prof)
    assert cap.envelope_lps() is None
    assert cap.headroom() is None
    prof.enable(1.0)
    prof.tick()
    prof.on_span(_span_doc("device.fetch", 10.0))  # saturated
    time.sleep(0.05)
    prof.tick()
    assert cap.headroom() == 0.0
    monkeypatch.setenv("KLOGS_FLEET_CAPACITY_LPS", "1000000")
    assert cap.headroom() == 1.0
    prof.reset()


def test_profile_interval_validated_at_enable(monkeypatch):
    """Review regression: a malformed KLOGS_PROFILE_INTERVAL_S must
    raise on the enablement path, not kill the background ticker
    silently."""
    monkeypatch.setenv("KLOGS_PROFILE_INTERVAL_S", "abc")
    with pytest.raises(ValueError, match="KLOGS_PROFILE_INTERVAL_S"):
        PipelineProfiler().enable(1.0)


def test_profile_doc_on_demand_skips_file_io(tmp_path):
    """Review regression: /profile before the first tick runs on the
    event loop — the on-demand snapshot must not append to the JSONL
    file (that is the off-loop ticker's job)."""
    path = tmp_path / "p.jsonl"
    PROFILER.enable(1.0)
    PROFILER.set_json_path(str(path))
    doc = PROFILER.profile_doc()
    assert doc["enabled"] is True
    assert not path.exists()


# -- the real-hop acceptance tests ------------------------------------

import importlib.util  # noqa: E402

needs_grpc = pytest.mark.skipif(
    importlib.util.find_spec("grpc") is None, reason="grpc not installed")


@needs_grpc
def test_hello_capacity_to_shard_reexport_parity(monkeypatch):
    """The autoscaling signal end to end: the filterd advertises
    headroom/offered/admitted through Hello; the sharded client's
    capacity refresh re-exports them per endpoint — gauge equal to the
    advertised headroom, counters advanced by deltas (never
    double-counted), a restarted server restarting its series."""
    monkeypatch.setenv("KLOGS_FLEET_CAPACITY_LPS", "1000000")
    from klogs_tpu.filters.base import frame_lines
    from klogs_tpu.service.server import FilterServer
    from klogs_tpu.service.shard import ShardedFilterClient

    async def scenario():
        srv = FilterServer(["ERROR"], backend="cpu", port=0)
        port = await srv.start()
        target = f"127.0.0.1:{port}"
        reg = Registry()
        sc = ShardedFilterClient([target], registry=reg)
        try:
            await sc.verify_patterns(["ERROR"])
            payload, offsets, _ = frame_lines(
                [b"an ERROR", b"ok", b"more ERROR"])
            await sc.match_framed(payload, offsets)
            ep = sc._endpoints[0]
            await sc._refresh_capacity(ep)
            g_head = reg.family("klogs_fleet_endpoint_headroom")
            c_off = reg.family("klogs_fleet_endpoint_offered_lines_total")
            c_adm = reg.family(
                "klogs_fleet_endpoint_admitted_lines_total")
            assert c_off.labels(endpoint=target).value == 3
            assert c_adm.labels(endpoint=target).value == 3
            server_head = srv.capacity.doc()["headroom"]
            assert g_head.labels(endpoint=target).value == pytest.approx(
                server_head, abs=0.05)
            # Delta discipline: a refresh without new traffic must not
            # advance the counters.
            await sc._refresh_capacity(ep)
            assert c_off.labels(endpoint=target).value == 3
            # Restart semantics: the advertised total COLLAPSING below
            # the remembered one restarts the series from the new
            # total instead of emitting a negative delta.
            ep.cap_offered = 1000
            sc._note_capacity(ep, {"fleet_offered_lines": 2,
                                   "fleet_admitted_lines": 2})
            assert c_off.labels(endpoint=target).value == 5
            # Review regression — out-of-order Hellos: a total only
            # SLIGHTLY below the remembered one is the older in-flight
            # answer (prober racing the exit-dump sweep), not a
            # restart; re-counting it as a fresh delta would spike the
            # counter by the endpoint's lifetime total.
            ep.cap_offered = 1000
            sc._note_capacity(ep, {"fleet_offered_lines": 990,
                                   "fleet_admitted_lines": 990})
            assert c_off.labels(endpoint=target).value == 5
            assert ep.cap_offered == 1000  # newer state kept
        finally:
            await sc.aclose()
            await srv.stop()

    run(asyncio.wait_for(scenario(), timeout=30))


@needs_grpc
def test_offered_vs_admitted_gap_on_quota_shed(monkeypatch):
    """A multi-tenant quota shed leaves the offered/admitted gap the
    autoscaling signal measures: offered advances for the shed batch,
    admitted does not."""
    monkeypatch.setenv("KLOGS_FLEET_CAPACITY_LPS", "1000000")
    from klogs_tpu.filters.base import frame_lines
    from klogs_tpu.service.client import RemoteFilterClient, ShedByServer
    from klogs_tpu.service.server import FilterServer

    async def scenario():
        srv = FilterServer(["ERROR"], backend="cpu", port=0,
                           multi_set=True, tenant_quota_lines=4)
        port = await srv.start()
        client = RemoteFilterClient(f"127.0.0.1:{port}")
        try:
            await client.verify_patterns(["ERROR"])
            payload, offsets, _ = frame_lines([b"a", b"b"])
            await client.match_framed(payload, offsets)
            assert srv.capacity.offered == 2
            assert srv.capacity.admitted == 2
            big = [b"line %d" % i for i in range(8)]
            payload, offsets, _ = frame_lines(big)
            with pytest.raises(ShedByServer):
                await client.match_framed(payload, offsets)
            assert srv.capacity.offered == 10
            assert srv.capacity.admitted == 2
            info = await client.hello()
            assert info["fleet_offered_lines"] == 10
            assert info["fleet_admitted_lines"] == 2
        finally:
            await client.aclose()
            await srv.stop()

    run(asyncio.wait_for(scenario(), timeout=30))


@needs_grpc
def test_profiler_folds_stages_across_real_hop():
    """Profiler on, one framed match through server + client: the tick
    attributes busy-seconds to the rpc/coalescer/device stages of the
    span catalog."""
    from klogs_tpu.filters.base import frame_lines
    from klogs_tpu.service.client import RemoteFilterClient
    from klogs_tpu.service.server import FilterServer

    PROFILER.enable(1.0)

    async def scenario():
        srv = FilterServer(["ERROR"], backend="cpu", port=0)
        port = await srv.start()
        client = RemoteFilterClient(f"127.0.0.1:{port}")
        try:
            payload, offsets, _ = frame_lines([b"an ERROR", b"ok"])
            await client.match_framed(payload, offsets)
        finally:
            await client.aclose()
            await srv.stop()

    run(asyncio.wait_for(scenario(), timeout=30))
    doc = PROFILER.tick()
    for stage in ("rpc.client", "rpc.server", "coalescer.dispatch",
                  "device.fetch"):
        assert stage in doc["stages"], (stage, sorted(doc["stages"]))
        assert doc["stages"][stage]["spans"] >= 1

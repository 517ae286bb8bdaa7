"""Observability subsystem: metrics core thread-safety, Prometheus
exposition golden text, the /metrics + /healthz HTTP sidecar with
cold-start vs warm readiness, the FilterStats registry view, and the
metric-inventory docs lint."""

import asyncio
import json
import threading

import pytest

from klogs_tpu.obs import (
    Health,
    MetricsHTTPServer,
    Registry,
    register_all,
    render,
    snapshot,
)


# -- metrics core -----------------------------------------------------

def test_counter_gauge_basics():
    r = Registry()
    c = r.counter("t_total", "help")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)  # a decreasing counter corrupts every rate() over it
    g = r.gauge("t_depth", "help")
    g.set(7)
    g.inc(2)
    g.dec()
    assert g.value == 8


def test_histogram_buckets_sum_count_percentile():
    r = Registry()
    h = r.histogram("t_lat", "help", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    counts, total, n = h._default().snapshot()
    assert counts == [1, 2, 1]  # 5.0 lands past the last bound (+Inf)
    assert n == 5 and abs(total - 5.605) < 1e-9
    assert abs(h.percentile(50) - 0.05) < 1e-9


def test_registry_get_or_create_and_conflicts():
    r = Registry()
    a = r.counter("t_total", "help")
    assert r.counter("t_total") is a  # get-or-create, not duplicate
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("t_total")
    with pytest.raises(KeyError, match="inventory"):
        r.family("klogs_not_a_real_metric_total")


def test_labeled_children():
    r = Registry()
    fam = r.counter("t_by_pod_total", "help", labelnames=("pod",))
    fam.labels(pod="a").inc(3)
    fam.labels(pod="b").inc()
    fam.labels(pod="a").inc()  # same child
    assert fam.labels(pod="a").value == 4
    with pytest.raises(ValueError, match="takes labels"):
        fam.labels(container="x")
    with pytest.raises(ValueError, match="use .labels"):
        fam.inc()  # bare labeled family refuses samples


def test_registry_threaded_increments_are_exact():
    """The thread-safety contract: N threads x M increments lose
    nothing (counter, gauge, histogram alike)."""
    r = Registry()
    c = r.counter("t_total")
    h = r.histogram("t_lat", buckets=(0.5,))
    fam = r.counter("t_labeled_total", labelnames=("k",))
    N, M = 8, 2500

    def work(i):
        child = fam.labels(k=str(i % 2))
        for _ in range(M):
            c.inc()
            h.observe(0.1)
            child.inc()

    threads = [threading.Thread(target=work, args=(i,)) for i in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == N * M
    assert h.count == N * M
    counts, total, n = h._default().snapshot()
    assert counts == [N * M] and n == N * M
    assert sum(ch.value for _, ch in fam.children()) == N * M


def test_histogram_bucket_is_the_first_bound_at_or_above():
    from klogs_tpu.obs.metrics import LATENCY_BUCKETS, Histogram

    bounds = LATENCY_BUCKETS
    values = [0.0, *bounds, *(b * 0.999 for b in bounds),
              *(b * 1.001 for b in bounds), 1e9, float("nan")]
    h = Histogram(bounds)
    for v in values:
        h.observe(v)
    want = [sum(1 for v in values
                if v <= b and all(v > c for c in bounds[:i]))
            for i, b in enumerate(bounds)]
    counts, _, n = h.snapshot()
    assert counts == want and n == len(values)
    assert sum(counts) == n - sum(1 for v in values if not v <= bounds[-1])


# -- exposition -------------------------------------------------------

def test_prometheus_exposition_golden():
    r = Registry()
    r.counter("t_lines_total", "Lines seen.").inc(42)
    g = r.gauge("t_depth", "Queue depth.", labelnames=("shard",))
    g.labels(shard="0").set(3)
    h = r.histogram("t_lat_seconds", "Latency.", buckets=(0.01, 0.1))
    h.observe(0.005)
    h.observe(0.05)
    h.observe(7.0)
    assert render(r) == (
        "# HELP t_depth Queue depth.\n"
        "# TYPE t_depth gauge\n"
        't_depth{shard="0"} 3\n'
        "# HELP t_lat_seconds Latency.\n"
        "# TYPE t_lat_seconds histogram\n"
        't_lat_seconds_bucket{le="0.01"} 1\n'
        't_lat_seconds_bucket{le="0.1"} 2\n'
        't_lat_seconds_bucket{le="+Inf"} 3\n'
        "t_lat_seconds_sum 7.055\n"
        "t_lat_seconds_count 3\n"
        "# HELP t_lines_total Lines seen.\n"
        "# TYPE t_lines_total counter\n"
        "t_lines_total 42\n"
    )


def test_exposition_escapes_label_values():
    r = Registry()
    fam = r.counter("t_total", 'he"lp', labelnames=("k",))
    fam.labels(k='a"b\\c\nd').inc()
    txt = render(r)
    assert 't_total{k="a\\"b\\\\c\\nd"} 1' in txt


def test_snapshot_json_round_trips():
    r = Registry()
    register_all(r)
    r.family("klogs_sink_lines_total").inc(9)
    doc = json.loads(json.dumps(snapshot(r)))
    assert doc["klogs_sink_lines_total"]["samples"][0]["value"] == 9
    assert "buckets" in doc["klogs_sink_batch_latency_seconds"]["samples"][0]


def test_register_all_exposes_every_layer_zero_valued():
    """A scrape during cold start must already show the whole panel:
    'no traffic yet' and 'not instrumented' have to be distinguishable."""
    r = Registry()
    register_all(r)
    txt = render(r)
    for layer in ("klogs_engine_", "klogs_coalescer_", "klogs_sink_",
                  "klogs_fanout_", "klogs_rpc_"):
        assert layer in txt, f"layer {layer} missing from exposition"
    assert "klogs_sink_lines_total 0" in txt


# -- FilterStats as a registry view -----------------------------------

def test_filterstats_is_a_view_over_the_registry():
    from klogs_tpu.filters.base import FilterStats

    r = Registry()
    s = FilterStats(registry=r)
    s.record_batch(n_lines=100, n_matched=7, n_bytes_in=5000,
                   n_bytes_out=350, latency_s=0.02)
    s.record_deadline_flush()
    # The summary attributes and the scrape read the SAME objects.
    assert s.lines_in == 100 and s.lines_matched == 7
    txt = render(r)
    assert "klogs_sink_lines_total 100" in txt
    assert "klogs_sink_lines_matched_total 7" in txt
    assert "klogs_sink_deadline_flush_total 1" in txt
    assert "klogs_sink_batch_latency_seconds_count 1" in txt


# -- HTTP sidecar -----------------------------------------------------

from tests.conftest import http_get as _http_get  # noqa: E402


def test_http_sidecar_metrics_and_health_transitions():
    r = Registry()
    register_all(r)
    r.family("klogs_sink_lines_total").inc(5)
    health = Health()
    alive = {"ok": True}
    health.add_live_check("loop", lambda: alive["ok"])
    health.add_ready_check("device", lambda: True)

    async def run():
        srv = MetricsHTTPServer(r, health=health, port=0)
        port = await srv.start()
        try:
            status, body = await _http_get(port, "/metrics")
            assert status == 200
            assert b"klogs_sink_lines_total 5" in body

            # Cold start: live (don't restart me) but NOT ready (don't
            # route to me) — the distinction that matters mid-compile.
            status, body = await _http_get(port, "/healthz")
            assert status == 200 and json.loads(body)["ready"] is False
            status, body = await _http_get(port, "/readyz")
            assert status == 503 and json.loads(body)["warm"] is False

            health.set_ready()  # the warmup batch landed
            status, body = await _http_get(port, "/readyz")
            assert status == 200 and json.loads(body)["ready"] is True

            # A dead coalescer loop flips LIVENESS (restart me).
            alive["ok"] = False
            status, body = await _http_get(port, "/healthz")
            assert status == 503
            assert json.loads(body)["checks"]["loop"] is False

            status, _ = await _http_get(port, "/nope")
            assert status == 404
        finally:
            await srv.stop()

    asyncio.run(run())


def test_health_warmup_does_not_override_drain():
    """mark_warm (the warmup-batch gate) must not un-drain a server: a
    rolling restart can issue set_ready(False) the moment the process
    is up, BEFORE the warmup batch lands — the late warmup completing
    must leave readiness off (this raced in the sharded-tier drain
    test). An explicit set_ready(True) still lifts the drain."""
    h = Health()
    assert h.readiness()[0] is False
    h.mark_warm()  # normal cold start: warmup flips readiness on
    assert h.readiness()[0] is True

    h2 = Health()
    h2.set_ready(False)  # drain arrives while still warming
    h2.mark_warm()  # warmup lands late
    assert h2.readiness()[0] is False, "warmup un-drained the server"
    h2.set_ready(True)  # operator decision beats the latch
    assert h2.readiness()[0] is True
    h2.set_ready(False)
    h2.mark_warm()
    assert h2.readiness()[0] is False


def test_http_sidecar_survives_garbage_requests():
    """A header line past the StreamReader limit (or any parse
    garbage) must drop the connection quietly — no unhandled-task
    traceback, and the server keeps serving."""
    r = Registry()
    r.counter("t_total").inc(3)

    async def run():
        srv = MetricsHTTPServer(r, port=0)
        port = await srv.start()
        try:
            reader, writer = await asyncio.open_connection("127.0.0.1",
                                                           port)
            writer.write(b"GET /metrics HTTP/1.1\r\nX: "
                         + b"a" * 200_000 + b"\r\n\r\n")
            await writer.drain()
            await reader.read()  # connection dropped, maybe empty
            writer.close()
            await writer.wait_closed()
            status, body = await _http_get(port, "/metrics")
            assert status == 200 and b"t_total 3" in body
        finally:
            await srv.stop()

    asyncio.run(run())


def test_http_sidecar_rejects_non_get():
    async def run():
        srv = MetricsHTTPServer(Registry(), port=0)
        port = await srv.start()
        try:
            reader, writer = await asyncio.open_connection("127.0.0.1",
                                                           port)
            writer.write(b"POST /metrics HTTP/1.1\r\nHost: x\r\n\r\n")
            await writer.drain()
            raw = await reader.read()
            writer.close()
            await writer.wait_closed()
            assert b"405" in raw.split(b"\r\n", 1)[0]
        finally:
            await srv.stop()

    asyncio.run(run())


# -- int32 guards (ADVICE r5 satellites) ------------------------------

def test_pure_python_frame_lines_overflow_raises(monkeypatch):
    """Past-int32 batches must raise like the C packer, not wrap the
    cumsum into negative offsets. (The limit is monkeypatched down:
    nobody allocates 2 GiB in CI to prove an inequality.)"""
    import klogs_tpu.native as native
    from klogs_tpu.filters import base

    monkeypatch.setattr(native, "hostops", None)  # force the pure path
    monkeypatch.setattr(base, "_INT32_MAX", 100)
    with pytest.raises(OverflowError, match="int32"):
        base.frame_lines([b"x" * 60, b"y" * 60])
    payload, offsets, raw = base.frame_lines([b"x" * 30, b"y" * 30])
    assert raw == 60 and offsets[-1] == 60


def test_coalesced_group_splits_below_int32_limit(monkeypatch):
    """A coalesced group whose combined payload would exceed the int32
    offsets limit is split into subgroups; every caller still gets
    correct verdicts (limit monkeypatched down to test-scale)."""
    from klogs_tpu.filters import async_service as asvc
    from klogs_tpu.filters.base import FilterStats
    from klogs_tpu.filters.cpu import RegexFilter

    monkeypatch.setattr(asvc, "GROUP_PAYLOAD_LIMIT", 64)
    r = Registry()
    stats = FilterStats(registry=r)
    svc = asvc.AsyncFilterService(
        RegexFilter(["ERROR"]), stats=stats,
        coalesce_delay_s=0.01, coalesce_lines=10_000)

    async def run():
        batches = [[b"an ERROR line %d" % i, b"fine %d" % i]
                   for i in range(6)]  # ~32 payload bytes per caller
        results = await asyncio.gather(*[svc.match(b) for b in batches])
        await svc.aclose()
        return results

    results = asyncio.run(run())
    assert all(got == [True, False] for got in results)
    splits = r.family("klogs_coalescer_group_splits_total").value
    assert splits >= 1, "expected at least one int32-limit group split"
    # More dispatches than one mega-group, fewer than one per caller
    # would only be true if no coalescing happened at all.
    assert svc.batches_dispatched >= 2


# -- collector CLI wiring ---------------------------------------------

def test_cli_flags_parse():
    from klogs_tpu.cli import parse_args

    o = parse_args(["-a", "--metrics-port", "0",
                    "--stats-json", "/tmp/out.json"])
    assert o.metrics_port == 0 and o.stats_json == "/tmp/out.json"
    d = parse_args(["-a"])
    assert d.metrics_port is None and d.stats_json is None


def test_stats_json_dump_e2e(tmp_path):
    """--stats-json: a collector run over the fake cluster dumps every
    layer's metrics (fanout + sink populated) at exit. Exact counts
    hold because each run gets its own registry (a second run in one
    process must not inherit the first run's counters)."""
    from klogs_tpu import app
    from klogs_tpu.cli import parse_args
    from klogs_tpu.cluster.fake import FakeCluster

    out = tmp_path / "stats.json"
    opts = parse_args(["-n", "default", "-a", "-p",
                       str(tmp_path / "logs"), "--match", "INFO",
                       "--stats-json", str(out)])
    fc = FakeCluster.synthetic(n_pods=2, n_containers=1,
                               lines_per_container=40)
    rc = asyncio.run(app.run_async(opts, backend=fc))
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc["summary"]["lines_in"] == 80
    assert doc["summary"]["lines_matched"] == 20
    assert doc["metrics"]["klogs_sink_lines_total"]["samples"][0][
        "value"] >= 80
    # Fan-out layer captured per-stream bytes for both pods.
    fanout = doc["metrics"]["klogs_fanout_stream_bytes_total"]["samples"]
    assert len(fanout) >= 2 and all(s["value"] > 0 for s in fanout)
    assert "klogs_rpc_requests_total" in doc["metrics"]


def test_collector_metrics_port_serves_during_run(tmp_path):
    """--metrics-port on the collector: scrape the sidecar mid-run
    (follow mode) and see live fanout/sink values."""
    from klogs_tpu import app
    from klogs_tpu.cli import parse_args
    from klogs_tpu.cluster.fake import FakeCluster

    opts = parse_args(["-n", "default", "-a", "-f", "-p",
                       str(tmp_path / "logs"), "--match", "INFO",
                       "--metrics-port", "0"])
    fc = FakeCluster.synthetic(n_pods=1, n_containers=1,
                               lines_per_container=30)

    async def run():
        stop = asyncio.Event()

        async def scrape_then_stop():
            # Wait until the sidecar binds (run_async starts it after
            # pipeline construction), then scrape and stop the follow.
            for _ in range(200):
                await asyncio.sleep(0.01)
                port = _collector_metrics_port()
                if port is not None:
                    break
            else:
                raise AssertionError("metrics sidecar never started")
            status, body = await _http_get(port, "/metrics")
            assert status == 200
            text = body.decode()
            assert "klogs_fanout_active_streams" in text
            status, hz = await _http_get(port, "/healthz")
            assert status == 200 and json.loads(hz)["ready"] is True
            stop.set()
            return text

        def _collector_metrics_port():
            # The sidecar registers on the process-global registry; the
            # bound port is discoverable from the server object held by
            # run_async — probe via the known localhost listener range
            # is flaky, so grab it off the obs module's last server.
            return getattr(app, "_test_metrics_port", None)

        # Expose the bound port for the prober via a tiny hook: wrap
        # MetricsHTTPServer.start once for this test.
        from klogs_tpu import obs

        orig_start = obs.MetricsHTTPServer.start

        async def start_and_record(self):
            port = await orig_start(self)
            app._test_metrics_port = port
            return port

        obs.MetricsHTTPServer.start = start_and_record
        try:
            task = asyncio.create_task(scrape_then_stop())
            rc = await app.run_async(opts, backend=fc, stop=stop)
            text = await task
            assert rc == 0
            return text
        finally:
            obs.MetricsHTTPServer.start = orig_start
            if hasattr(app, "_test_metrics_port"):
                del app._test_metrics_port

    text = asyncio.run(run())
    assert "klogs_sink_lines_total" in text


# -- docs lint (tier-1) -----------------------------------------------

def test_metrics_docs_lint():
    from tools.check_metrics_docs import check

    assert check() == []

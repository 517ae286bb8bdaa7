"""Batch tracing + flight recorder (obs/trace.py): span core semantics,
context propagation across a real gRPC hop and through sharded
hedging (loser cancelled, winner parented), exemplar exposition, the
/traces endpoint vs --trace-json parity, and the acceptance chaos
scenario — kill one of three filterds under a KLOGS_FAULTS-style spec
and reconstruct the failed batch's full hop sequence (fanout →
coalesce → route → hedge → reroute → device dispatch → sink) from the
flight-recorder dump."""

import asyncio
import json
import os

import pytest

from klogs_tpu import obs
from klogs_tpu.obs import trace


def run(coro):
    return asyncio.run(coro)


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace.reset(None)
    yield
    trace.reset(None)


# -- span core --------------------------------------------------------


def test_sampling_off_is_the_noop_singleton():
    trace.reset(0.0)
    s = trace.TRACER.span("anything", k=1)
    assert s is trace.NOOP_SPAN
    with s:
        # No context is installed: children are noops too, and nothing
        # ever reaches the ring.
        assert trace.TRACER.span("child") is trace.NOOP_SPAN
    assert trace.TRACER.finished_spans() == []


def test_sample_env_is_validated(monkeypatch):
    monkeypatch.setenv("KLOGS_TRACE_SAMPLE", "lots")
    trace.reset(None)
    with pytest.raises(ValueError, match="KLOGS_TRACE_SAMPLE"):
        trace.TRACER.span("x")
    monkeypatch.setenv("KLOGS_TRACE_SAMPLE", "1.5")
    trace.reset(None)
    with pytest.raises(ValueError, match="KLOGS_TRACE_SAMPLE"):
        trace.TRACER.span("x")


def test_span_tree_attrs_events_and_grouping():
    trace.reset(1.0)
    t = trace.TRACER
    with t.span("root", pod="p1") as root:
        with t.span("mid") as mid:
            mid.add_event("hop", endpoint="e1")
            with t.span("leaf"):
                pass
        t.event("on-root")  # helper: lands on the CURRENT span
    spans = {d["name"]: d for d in t.finished_spans()}
    assert spans["root"]["parent_id"] is None
    assert spans["mid"]["parent_id"] == spans["root"]["span_id"]
    assert spans["leaf"]["parent_id"] == spans["mid"]["span_id"]
    assert len({d["trace_id"] for d in spans.values()}) == 1
    assert spans["mid"]["events"][0]["name"] == "hop"
    assert spans["root"]["events"][0]["name"] == "on-root"
    assert all(d["duration_s"] >= 0 for d in spans.values())
    doc = t.traces_doc()
    assert len(doc["traces"]) == 1
    assert [s["name"] for s in doc["traces"][0]["spans"]][0] == "root"


def test_attrs_are_bounded_and_clipped():
    trace.reset(1.0)
    with trace.TRACER.span("b") as sp:
        for i in range(trace.MAX_ATTRS + 10):
            sp.set_attr(f"k{i}", "v")
        sp.set_attr("long", "x" * 1000)
        for i in range(trace.MAX_EVENTS + 10):
            sp.add_event("e")
    d = trace.TRACER.finished_spans()[0]
    assert len(d["attrs"]) <= trace.MAX_ATTRS
    assert len(d["events"]) <= trace.MAX_EVENTS
    assert all(len(str(v)) <= trace.MAX_ATTR_LEN + 1
               for v in d["attrs"].values())


def test_error_and_cancellation_status():
    trace.reset(1.0)
    with pytest.raises(RuntimeError):
        with trace.TRACER.span("boom"):
            raise RuntimeError("nope")

    async def cancelled_span():
        async def inner():
            with trace.TRACER.span("loser"):
                await asyncio.sleep(30)

        task = asyncio.create_task(inner())
        await asyncio.sleep(0.01)
        task.cancel()
        try:
            await task
        except asyncio.CancelledError:
            pass

    run(cancelled_span())
    spans = {d["name"]: d for d in trace.TRACER.finished_spans()}
    assert spans["boom"]["status"] == "error"
    assert "RuntimeError" in spans["boom"]["attrs"]["error"]
    assert spans["loser"]["status"] == "cancelled"


def test_traceparent_roundtrip_and_malformed():
    ctx = trace.SpanContext(0xABCDEF, 0x1234, True)
    back = trace.SpanContext.from_traceparent(ctx.traceparent())
    assert (back.trace_id, back.span_id, back.sampled) == (
        0xABCDEF, 0x1234, True)
    for bad in ("", "00-zz-xx-01", "00-abc-def-01", "nonsense",
                "00-" + "0" * 32 + "-" + "0" * 16):
        assert trace.SpanContext.from_traceparent(bad) is None


def test_context_propagates_into_tasks_not_threads():
    trace.reset(1.0)

    async def scenario():
        with trace.TRACER.span("root"):
            async def child_task():
                with trace.TRACER.span("task-child"):
                    pass

            t = asyncio.create_task(child_task())
            await t
            # run_in_executor does NOT copy contextvars into the
            # worker thread (unlike to_thread): by convention the
            # await site owns the span (device.fetch pattern).
            def in_thread():
                return trace.TRACER.current_context()

            loop = asyncio.get_running_loop()
            assert await loop.run_in_executor(None, in_thread) is None

    run(scenario())
    spans = {d["name"]: d for d in trace.TRACER.finished_spans()}
    assert spans["task-child"]["parent_id"] == spans["root"]["span_id"]


def test_json_sink_writes_jsonl(tmp_path):
    trace.reset(1.0)
    path = str(tmp_path / "spans.jsonl")
    trace.TRACER.set_json_path(path)
    with trace.TRACER.span("a"):
        pass
    with trace.TRACER.span("b"):
        pass
    docs = [json.loads(ln) for ln in open(path)]
    assert [d["name"] for d in docs] == ["a", "b"]


def test_enable_default_respects_explicit_env(monkeypatch):
    monkeypatch.setenv("KLOGS_TRACE_SAMPLE", "0")
    trace.reset(None)
    trace.TRACER.enable_default()  # --trace-json with an explicit rate
    assert not trace.TRACER.enabled
    monkeypatch.delenv("KLOGS_TRACE_SAMPLE")
    trace.reset(None)
    trace.TRACER.enable_default()
    assert trace.TRACER.enabled


# -- exemplars --------------------------------------------------------


def test_exemplar_links_histogram_to_trace():
    from klogs_tpu.filters.base import FilterStats

    trace.reset(1.0)
    r = obs.Registry()
    s = FilterStats(registry=r)
    with trace.TRACER.span("batch") as sp:
        s.record_batch(n_lines=10, n_matched=1, n_bytes_in=100,
                       n_bytes_out=10, latency_s=0.003)
        tid = f"{sp.trace_id:032x}"
    txt = obs.render(r, exemplars=True)
    assert f'# {{trace_id="{tid}"' in txt
    # The DEFAULT exposition stays strict 0.0.4 — a plain Prometheus
    # scrape must never see exemplar suffixes (its parser rejects
    # anything after the sample value, failing the whole scrape).
    assert "# {" not in obs.render(r)
    # Without a recording span the exposition stays plain 0.0.4 text.
    trace.reset(0.0)
    r2 = obs.Registry()
    FilterStats(registry=r2).record_batch(
        n_lines=1, n_matched=0, n_bytes_in=1, n_bytes_out=0,
        latency_s=0.001)
    assert "# {" not in obs.render(r2)


# -- flight recorder --------------------------------------------------


def test_recorder_waits_for_the_triggering_trace_root(tmp_path):
    trace.reset(1.0)
    trace.RECORDER.configure(dir_path=str(tmp_path), min_interval_s=0.0)
    with trace.TRACER.span("other-batch"):
        pass  # a completed concurrent trace already in the ring
    with trace.TRACER.span("failed-batch") as root:
        with trace.TRACER.span("rpc"):
            trace.flight_trigger("breaker-open", breaker="rpc@x")
        # Armed but NOT yet written: the failed batch's root is open.
        assert trace.RECORDER.dumps == []
        failed_tid = f"{root.trace_id:032x}"
    trace.RECORDER.join_writes()
    assert len(trace.RECORDER.dumps) == 1
    blob = json.load(open(trace.RECORDER.dumps[0]))
    assert blob["reasons"][0]["reason"] == "breaker-open"
    assert blob["reasons"][0]["trace_id"] == failed_tid
    names = [s["name"] for s in blob["spans"]]
    assert "rpc" in names and "failed-batch" in names


def test_recorder_concurrent_root_does_not_cut_the_story(tmp_path):
    trace.reset(1.0)
    trace.RECORDER.configure(dir_path=str(tmp_path), min_interval_s=0.0)
    with trace.TRACER.span("failed") as failed:
        trace.flight_trigger("filter-degrade", action="drop")
        # A DIFFERENT trace completes first: must not flush the dump.
        with trace.TRACER.span("bystander", parent=None):
            pass
        assert trace.RECORDER.dumps == []
    trace.RECORDER.join_writes()
    assert len(trace.RECORDER.dumps) == 1
    blob = json.load(open(trace.RECORDER.dumps[0]))
    assert any(s["name"] == "failed" for s in blob["spans"])
    assert failed is not None


def test_recorder_rate_limit_and_flush(tmp_path):
    trace.reset(1.0)
    trace.RECORDER.configure(dir_path=str(tmp_path),
                             min_interval_s=3600.0)
    with trace.TRACER.span("b1"):
        trace.flight_trigger("sweep-fallback")
        trace.flight_trigger("sweep-fallback")  # rate-limited away
    trace.RECORDER.join_writes()
    assert len(trace.RECORDER.dumps) == 1
    # Within the window the same reason stays silent — even via flush.
    trace.flight_trigger("sweep-fallback")
    assert trace.RECORDER.flush() is None
    # A different reason is its own budget; flush writes it without
    # waiting for a root (teardown path).
    trace.flight_trigger("abort-escalation")
    path = trace.RECORDER.flush()
    assert path is not None and os.path.exists(path)


def test_recorder_noop_with_tracing_off(tmp_path):
    trace.reset(0.0)
    trace.RECORDER.configure(dir_path=str(tmp_path), min_interval_s=0.0)
    trace.flight_trigger("breaker-open", breaker="x")
    assert trace.RECORDER.dumps == [] and trace.RECORDER.flush() is None


def test_breaker_open_triggers_recorder(tmp_path):
    from klogs_tpu.resilience import CircuitBreaker

    trace.reset(1.0)
    trace.RECORDER.configure(dir_path=str(tmp_path), min_interval_s=0.0)
    br = CircuitBreaker(name="rpc@t", failure_threshold=2)
    with trace.TRACER.span("batch"):
        br.record_failure()
        br.record_failure()  # opens -> trigger armed
    trace.RECORDER.join_writes()
    assert len(trace.RECORDER.dumps) == 1
    blob = json.load(open(trace.RECORDER.dumps[0]))
    assert blob["reasons"][0]["reason"] == "breaker-open"
    assert blob["reasons"][0]["breaker"] == "rpc@t"


# -- /traces endpoint -------------------------------------------------


def test_traces_endpoint_serves_finished_spans():
    from tests.conftest import http_get

    trace.reset(1.0)
    with trace.TRACER.span("served"):
        pass

    async def scenario():
        srv = obs.MetricsHTTPServer(obs.Registry(), tracer=trace.TRACER)
        port = await srv.start()
        try:
            status, body = await http_get(port, "/traces")
        finally:
            await srv.stop()
        return status, json.loads(body)

    status, doc = run(scenario())
    assert status == 200
    assert [s["name"] for s in doc["traces"][0]["spans"]] == ["served"]


# -- real gRPC hop ----------------------------------------------------

import importlib.util

needs_grpc = pytest.mark.skipif(
    importlib.util.find_spec("grpc") is None, reason="grpc not installed")


def _by_name(spans):
    out = {}
    for d in spans:
        out.setdefault(d["name"], []).append(d)
    return out


@needs_grpc
def test_trace_propagates_across_a_real_grpc_hop():
    """One collector-side root span; the RPC carries the traceparent
    metadata; the server's rpc.server span (same process here, but the
    propagation is the real wire path) parents under the client's
    rpc.client span, and the server-side coalescer + device.fetch
    spans continue the SAME trace."""
    from klogs_tpu.filters.base import frame_lines
    from klogs_tpu.service.client import RemoteFilterClient
    from klogs_tpu.service.server import FilterServer

    trace.reset(1.0)

    async def scenario():
        srv = FilterServer(["ERROR"], backend="cpu", port=0)
        port = await srv.start()
        client = RemoteFilterClient(f"127.0.0.1:{port}")
        try:
            payload, offsets, _ = frame_lines([b"an ERROR", b"ok"])
            with trace.TRACER.span("sink.flush") as root:
                mask = await client.match_framed(payload, offsets)
            assert mask.tolist() == [True, False]
            return f"{root.trace_id:032x}", f"{root.span_id:016x}"
        finally:
            await client.aclose()
            await srv.stop()

    tid, root_sid = run(asyncio.wait_for(scenario(), timeout=30))
    spans = _by_name(trace.TRACER.finished_spans())
    server_side = [d for d in spans["rpc.server"]
                   if d["attrs"].get("method") == "MatchFramed"]
    assert len(server_side) == 1
    srv_span = server_side[0]
    assert srv_span["trace_id"] == tid, "trace did not cross the wire"
    # Parent = the client's rpc.client span for the match RPC, which
    # itself parents under the collector root.
    clients = {d["span_id"]: d for d in spans["rpc.client"]}
    parent = clients[srv_span["parent_id"]]
    assert parent["trace_id"] == tid
    assert parent["parent_id"] == root_sid
    assert parent["status"] == "ok"
    # Server-side coalescer + device fetch ride the same trace.
    co = [d for d in spans["coalescer.dispatch"] if d["trace_id"] == tid]
    assert co and co[0]["parent_id"] == srv_span["span_id"]
    fetch = [d for d in spans["device.fetch"] if d["trace_id"] == tid]
    assert fetch and fetch[0]["parent_id"] == co[0]["span_id"]


# -- sharded hedging --------------------------------------------------


def test_hedge_loser_span_cancelled_winner_parented():
    """The satellite contract: when a hedge wins, the losing attempt's
    span closes status=cancelled and the winner's span parents under
    the shard.dispatch span that raced them."""
    pytest.importorskip("grpc")
    from klogs_tpu.resilience import CircuitBreaker
    from klogs_tpu.service.shard import ShardedFilterClient

    trace.reset(1.0)

    class FakeClient:
        def __init__(self, target, delay_s):
            self.target = target
            self.delay_s = delay_s
            self.breaker = CircuitBreaker(name=f"rpc@{target}")

        async def match(self, lines):
            with trace.TRACER.span("rpc.client", target=self.target):
                await asyncio.sleep(self.delay_s)
                return [True] * len(lines)

        async def aclose(self):
            pass

    delays = {"slow:1": 30.0, "fast:1": 0.0}

    async def scenario():
        sc = ShardedFilterClient(
            ["slow:1", "fast:1"], hedge_s=0.05,
            client_factory=lambda t: FakeClient(t, delays[t]))
        try:
            assert await sc.match([b"x"]) == [True]
        finally:
            await sc.aclose()

    run(asyncio.wait_for(scenario(), timeout=30))
    spans = _by_name(trace.TRACER.finished_spans())
    dispatch = spans["shard.dispatch"][0]
    assert any(e["name"] == "shard.hedge" and e["endpoint"] == "fast:1"
               for e in dispatch["events"])
    assert dispatch["attrs"]["winner"] == "fast:1"
    attempts = {d["attrs"]["target"]: d for d in spans["rpc.client"]}
    assert attempts["slow:1"]["status"] == "cancelled"
    assert attempts["fast:1"]["status"] == "ok"
    for d in attempts.values():
        assert d["parent_id"] == dispatch["span_id"]
        assert d["trace_id"] == dispatch["trace_id"]


# -- chaos acceptance -------------------------------------------------


@pytest.fixture(autouse=True)
def _clean_faults():
    from klogs_tpu.resilience import FAULTS

    FAULTS.clear()
    FAULTS.bind_registry(None)
    yield
    FAULTS.clear()
    FAULTS.bind_registry(None)


@needs_grpc
def test_chaos_kill_one_of_three_reconstructs_batch(tmp_path, monkeypatch):
    """The acceptance scenario: the full collector (FakeCluster fanout
    -> filtered sinks -> sharded client) against a 3-filterd fleet, one
    endpoint first delayed (forcing a hedge) then killed via a targeted
    KLOGS_FAULTS spec. The breaker opening arms a flight-recorder dump
    from which this test reconstructs the failed batch's full hop
    sequence — fanout -> sink flush -> shard route/failover -> RPC
    client/server -> coalescer -> device fetch -> sink write — with
    per-stage durations; /traces and --trace-json emit the same
    spans."""
    from klogs_tpu import app
    import klogs_tpu.filters.sink as sink_mod
    import klogs_tpu.service.client as client_mod
    from klogs_tpu.cli import parse_args
    from klogs_tpu.cluster.fake import FakeCluster
    from klogs_tpu.resilience import RetryPolicy
    from klogs_tpu.service.server import FilterServer

    flight_dir = tmp_path / "flight"
    flight_dir.mkdir()
    trace.RECORDER.configure(dir_path=str(flight_dir), min_interval_s=0.0)
    monkeypatch.setattr(client_mod, "DEFAULT_RETRY", RetryPolicy(
        max_attempts=2, base_s=0.005, max_s=0.01, jitter=0.0))
    monkeypatch.setattr(client_mod, "DEFAULT_BREAKER_THRESHOLD", 2)
    monkeypatch.setenv("KLOGS_HEDGE_S", "0.05")
    # Small flush batches: flushes then fire INSIDE chunk writes, so
    # each batch's trace roots at fanout.read (the full hop story).
    orig_make = sink_mod.make_pipeline
    monkeypatch.setattr(
        sink_mod, "make_pipeline",
        lambda *a, **k: orig_make(*a, **{**k, "batch_lines": 16}))

    trace_file = str(tmp_path / "spans.jsonl")
    out_dir = str(tmp_path / "logs")
    fc = FakeCluster.synthetic(n_pods=2, n_containers=1,
                               lines_per_container=300)

    async def scenario():
        servers = [FilterServer(["ERROR"], backend="cpu", port=0)
                   for _ in range(3)]
        ports = [await s.start() for s in servers]
        targets = [f"127.0.0.1:{p}" for p in ports]
        victim = targets[1]
        # One delayed dispatch (hedge), then dead forever (failover,
        # breaker opens after threshold=2 attempts on one batch).
        monkeypatch.setenv(
            "KLOGS_FAULTS",
            f"rpc.match@{victim}:delay(0.4)*1;rpc.match@{victim}:error*")
        opts = parse_args([
            "-n", "default", "-a", "-p", out_dir, "--match", "ERROR",
            "--remote", ",".join(targets), "--trace-json", trace_file])
        try:
            rc = await app.run_async(opts, backend=fc)
        finally:
            for s in servers:
                await s.stop()
        return rc, victim

    rc, victim = run(asyncio.wait_for(scenario(), timeout=60))
    assert rc == 0  # survivors absorbed the stream; degrade never fired

    # --- the dump exists and names the breaker trigger ---------------
    assert trace.RECORDER.dumps, "breaker open produced no flight dump"
    blob = None
    for path in trace.RECORDER.dumps:
        cand = json.load(open(path))
        if any(r["reason"] == "breaker-open" for r in cand["reasons"]):
            blob = cand
            break
    assert blob is not None
    spans_by_id = {s["span_id"]: s for s in blob["spans"]}

    # --- reconstruct the failed batch's hop sequence -----------------
    failed = [s for s in blob["spans"] if s["name"] == "shard.dispatch"
              and any(e["name"] == "shard.failover"
                      and e["endpoint"] == victim for e in s["events"])]
    assert failed, "no shard.dispatch span recorded the failover"
    sd = failed[0]
    chain_up = []
    cur = sd
    while cur["parent_id"] is not None:
        cur = spans_by_id[cur["parent_id"]]
        chain_up.append(cur["name"])
    assert chain_up[-1] == "fanout.read", chain_up  # the trace root
    assert "sink.flush" in chain_up
    tid = sd["trace_id"]
    trace_spans = [s for s in blob["spans"] if s["trace_id"] == tid]
    names = {s["name"] for s in trace_spans}
    if "coalescer.dispatch" not in names:
        # This batch coalesced server-side with a concurrent caller
        # whose trace carries the group's dispatch span; ours is
        # connected via the documented coalescer.link event. Follow it.
        linked = [s for s in blob["spans"]
                  if s["name"] == "coalescer.dispatch"
                  and any(e["name"] == "coalescer.link"
                          and e.get("trace_id") == tid
                          for e in s["events"])]
        assert linked, "batch neither carries nor links a group span"
        trace_spans.extend(linked)
        trace_spans.extend(
            s for s in blob["spans"]
            if s["parent_id"] in {x["span_id"] for x in linked})
        names = {s["name"] for s in trace_spans}
    assert {"fanout.read", "sink.flush", "shard.dispatch", "rpc.client",
            "rpc.server", "coalescer.dispatch", "device.fetch",
            "sink.write"} <= names, names
    # Per-stage durations all present, and parents start before (or
    # with) their children down the whole chain.
    for s in trace_spans:
        assert s["duration_s"] is not None and s["duration_s"] >= 0
    for s in trace_spans:
        parent = spans_by_id.get(s["parent_id"] or "")
        if parent is not None:
            assert parent["start_unix"] <= s["start_unix"] + 1e-6
    # The winner answered on a survivor, not the victim.
    assert sd["attrs"]["winner"] != victim

    # --- the hedge and its cancelled loser were traced ---------------
    # Asserted over the FULL span stream (--trace-json), not the dump:
    # the dump is a point-in-time snapshot written the moment the
    # failover batch's root ends, and the hedged batch (whose victim
    # attempt sits in a 0.4s injected delay) can legitimately still be
    # in flight at that instant.
    all_spans = [json.loads(ln) for ln in open(trace_file)]
    assert any(s["name"] == "shard.dispatch"
               and any(e["name"] == "shard.hedge" for e in s["events"])
               for s in all_spans), "no hedge recorded"
    cancelled = [s for s in all_spans if s["name"] == "rpc.client"
                 and s["status"] == "cancelled"]
    assert cancelled and any(
        s["attrs"].get("target") == victim for s in cancelled)

    # --- /traces and --trace-json emit the same spans ----------------
    file_ids = {s["span_id"] for s in all_spans}
    assert file_ids  # the file sink actually wrote
    from tests.conftest import http_get

    async def traces_over_http():
        srv = obs.MetricsHTTPServer(obs.Registry(), tracer=trace.TRACER)
        port = await srv.start()
        try:
            _, body = await http_get(port, "/traces")
        finally:
            await srv.stop()
        return json.loads(body)

    doc = run(traces_over_http())
    endpoint_ids = {s["span_id"] for t in doc["traces"]
                    for s in t["spans"]}
    assert endpoint_ids == file_ids


def test_remote_parented_span_is_a_local_root_for_the_recorder(tmp_path):
    """Finding regression: on a filterd, every span of a propagated
    trace carries a parent id (the collector's), so a parent-is-None
    root test would never fire and server-side degrade dumps would be
    lost. A span parented under an EXTRACTED (remote) context counts
    as this process's root of the trace."""
    trace.reset(1.0)
    trace.RECORDER.configure(dir_path=str(tmp_path), min_interval_s=0.0)
    remote = trace.SpanContext(0xFEED, 0xBEEF, True)
    ctx = trace.TRACER.extract(
        [(trace.TRACEPARENT_KEY, remote.traceparent())])
    assert ctx is not None and ctx.remote
    with trace.TRACER.span("rpc.server", parent=ctx):
        trace.flight_trigger("sweep-fallback")
    trace.RECORDER.join_writes()
    assert len(trace.RECORDER.dumps) == 1
    blob = json.load(open(trace.RECORDER.dumps[0]))
    srv = [s for s in blob["spans"] if s["name"] == "rpc.server"][0]
    assert srv["parent_id"] is not None and srv["local_root"]


def test_coalescer_dispatch_span_records_failure():
    """Finding regression: a dispatch failure is routed to the member
    futures (swallowed), so without an explicit mark the span would
    close status=ok — a clean-looking dispatch for the failed batch."""
    from klogs_tpu.filters.async_service import AsyncFilterService
    from klogs_tpu.filters.base import LogFilter, frame_lines

    trace.reset(1.0)

    class Exploding(LogFilter):
        def match_lines(self, lines):
            raise RuntimeError("kernel gone")

        def dispatch_framed(self, payload, offsets):
            raise RuntimeError("kernel gone")

    async def scenario():
        svc = AsyncFilterService(Exploding(), coalesce_delay_s=0.001)
        payload, offsets, _ = frame_lines([b"x"])
        with pytest.raises(RuntimeError):
            await svc.match_framed(payload, offsets)
        await svc.aclose()

    run(scenario())
    spans = {d["name"]: d for d in trace.TRACER.finished_spans()}
    assert spans["coalescer.dispatch"]["status"] == "error"
    assert "kernel gone" in spans["coalescer.dispatch"]["attrs"]["error"]


def test_metrics_endpoint_exemplars_only_on_opt_in():
    """Finding regression: the plain /metrics body must stay strict
    0.0.4 (no exemplar suffix) or real scrapers fail wholesale;
    ?exemplars=1 opts in."""
    from klogs_tpu.filters.base import FilterStats
    from tests.conftest import http_get

    trace.reset(1.0)
    r = obs.Registry()
    s = FilterStats(registry=r)
    with trace.TRACER.span("batch"):
        s.record_batch(n_lines=1, n_matched=1, n_bytes_in=10,
                       n_bytes_out=10, latency_s=0.002)

    async def scenario():
        srv = obs.MetricsHTTPServer(r)
        port = await srv.start()
        try:
            _, plain = await http_get(port, "/metrics")
            _, rich = await http_get(port, "/metrics?exemplars=1")
        finally:
            await srv.stop()
        return plain.decode(), rich.decode()

    plain, rich = run(scenario())
    assert "# {" not in plain
    assert '# {trace_id="' in rich


# -- the device clock (--profile) ---------------------------------------


class _Annotation:
    """Stands in for jax.profiler.TraceAnnotation: logs enter/exit."""

    log: "list[tuple[str, str]]" = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _Annotation.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        _Annotation.log.append(("exit", self.name))


@pytest.fixture
def annotation(monkeypatch):
    import jax.profiler

    _Annotation.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    yield _Annotation.log
    trace.TRACER.device_clock(False)


def test_device_clock_off_is_the_noop_singleton_for_every_name(annotation):
    trace.reset(0.0)
    for name in trace.CLOCK_STAGES + ("sink.flush", "anything"):
        sp = trace.TRACER.span(name)
        assert sp is trace.NOOP_SPAN
        with sp:
            pass
    trace.TRACER.device_clock(True)
    trace.TRACER.device_clock(False)
    with trace.TRACER.span("coalescer.dispatch") as sp:
        assert sp is trace.NOOP_SPAN
    assert annotation == []


def test_device_clock_annotates_stages_sampled_or_not(annotation):
    trace.reset(0.0)
    trace.TRACER.device_clock(True)
    for name in trace.CLOCK_STAGES:
        with trace.TRACER.span(name, rows=8):
            pass
    # Not a clock stage, not sampled: nothing.
    assert trace.TRACER.span("sink.flush") is trace.NOOP_SPAN
    assert annotation == [(e, n) for n in trace.CLOCK_STAGES
                          for e in ("enter", "exit")]
    annotation.clear()
    # An unsampled head decision carried by a context: a real (unsampled)
    # span, annotated only under a clock-stage name.
    unsampled = trace.SpanContext(1, 2, sampled=False)
    with trace.TRACER.span("device.fetch", parent=unsampled):
        pass
    with trace.TRACER.span("sink.write", parent=unsampled):
        pass
    assert annotation == [("enter", "device.fetch"), ("exit", "device.fetch")]
    annotation.clear()
    # Sampled: every name annotates, nested as the spans are.
    trace.TRACER.configure(1.0)
    with trace.TRACER.span("sink.flush"):
        with trace.TRACER.span("device.frame"):
            pass
    assert annotation == [("enter", "sink.flush"), ("enter", "device.frame"),
                          ("exit", "device.frame"), ("exit", "sink.flush")]
    assert {d["name"] for d in trace.TRACER.finished_spans()} == {
        "sink.flush", "device.frame"}


def test_unsampled_clock_span_leaves_context_and_ring_alone(annotation):
    trace.reset(0.0)
    trace.TRACER.device_clock(True)
    sp = trace.TRACER.span("coalescer.dispatch", members=3)
    assert sp is not trace.NOOP_SPAN and not sp.sampled
    assert sp.context() is None
    with sp:
        assert trace._CURRENT.get() is None
        assert trace.TRACER.current_span() is None
        assert trace.TRACER.inject() == ()
        # A child under it is what it would be without it.
        assert trace.TRACER.span("sink.write") is trace.NOOP_SPAN
        sp.set_attr("k", "v")
        sp.add_event("e")
    assert trace.TRACER.finished_spans() == []
    assert annotation == [("enter", "coalescer.dispatch"),
                          ("exit", "coalescer.dispatch")]


def test_reset_turns_the_device_clock_off(annotation):
    trace.TRACER.device_clock(True)
    trace.reset(0.0)
    assert trace.TRACER.span("device.fetch") is trace.NOOP_SPAN


def test_clock_spans_land_on_the_host_plane_of_a_capture(tmp_path):
    """One real CPU capture: two overlapping, non-nested clock spans on
    one event-loop thread each keep their own start and duration on the
    /host:CPU plane, on the clock of the capture's profile_start_time."""
    import glob

    import jax.profiler
    from jax.profiler import ProfileData

    trace.reset(0.0)
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0

    async def group(delay_s, hold_s):
        await asyncio.sleep(delay_s)
        with trace.TRACER.span("coalescer.dispatch"):
            await asyncio.sleep(hold_s)

    async def both():
        await asyncio.gather(group(0.0, 0.05), group(0.02, 0.08))

    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        trace.TRACER.device_clock(True)
        run(both())
    finally:
        trace.TRACER.device_clock(False)
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    planes = {p.name: p for p in ProfileData.from_file(path).planes}
    found = sorted((e.start_ns, e.duration_ns)
                   for line in planes["/host:CPU"].lines
                   for e in line.events if e.name == "coalescer.dispatch")
    assert len(found) == 2
    (s1, d1), (s2, d2) = found
    assert 0.045e9 <= d1 < 1e9 and 0.075e9 <= d2 < 1e9
    assert s1 < s2 < s1 + d1 < s2 + d2  # overlapping, not nested
    assert "profile_start_time" in dict(planes["Task Environment"].stats)

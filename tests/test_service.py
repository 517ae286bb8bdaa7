"""Filter service (gRPC): round trip, pattern verification, CLI e2e
through --remote against FakeCluster."""

import asyncio
import json
import os

import pytest

pytest.importorskip("grpc")

from klogs_tpu import app
from klogs_tpu.cli import parse_args
from klogs_tpu.cluster.fake import FakeCluster
from klogs_tpu.filters.cpu import RegexFilter
from klogs_tpu.service.client import PatternMismatch, RemoteFilterClient
from klogs_tpu.service.server import FilterServer

PATTERNS = ["ERROR", r"WARN.*\d"]


async def with_server(patterns, backend, fn):
    server = FilterServer(patterns, backend=backend, port=0)
    port = await server.start()
    client = RemoteFilterClient(f"127.0.0.1:{port}")
    try:
        return await fn(client, port)
    finally:
        await client.aclose()
        await server.stop()


def test_match_round_trip():
    lines = [b"an ERROR here", b"all good", b"WARN code 42", b"WARN none"]

    async def fn(client, _):
        await client.verify_patterns(PATTERNS)
        return await client.match(lines)

    got = asyncio.run(with_server(PATTERNS, "cpu", fn))
    assert got == RegexFilter(PATTERNS).match_lines(lines)


def test_hello_reports_config():
    async def fn(client, _):
        return await client.hello()

    info = asyncio.run(with_server(PATTERNS, "cpu", fn))
    assert info["patterns"] == PATTERNS
    assert info["backend"] == "cpu"


def test_pattern_mismatch_fails_fast():
    async def fn(client, _):
        with pytest.raises(PatternMismatch):
            await client.verify_patterns(["different"])

    asyncio.run(with_server(PATTERNS, "cpu", fn))


def test_concurrent_clients_coalesce():
    async def fn(client, port):
        others = [RemoteFilterClient(f"127.0.0.1:{port}") for _ in range(3)]
        try:
            results = await asyncio.gather(
                client.match([b"ERROR x"]),
                *[c.match([b"nope", b"WARN 1"]) for c in others],
            )
        finally:
            for c in others:
                c.close()
        return results

    res = asyncio.run(with_server(PATTERNS, "cpu", fn))
    assert res[0] == [True]
    assert all(r == [False, True] for r in res[1:])


def test_cli_e2e_through_remote(tmp_path):
    out_dir = str(tmp_path / "logs")

    async def main():
        server = FilterServer(["INFO"], backend="tpu", port=0)
        port = await server.start()
        try:
            opts = parse_args([
                "-n", "default", "-a", "-p", out_dir,
                "--match", "INFO", "--remote", f"127.0.0.1:{port}",
            ])
            fc = FakeCluster.synthetic(n_pods=2, n_containers=1,
                                       lines_per_container=40)
            return await app.run_async(opts, backend=fc)
        finally:
            await server.stop()

    rc = asyncio.run(main())
    assert rc == 0
    files = sorted(os.listdir(out_dir))
    assert len(files) == 2
    total = 0
    for f in files:
        with open(os.path.join(out_dir, f), "rb") as fh:
            lines = fh.read().splitlines()
        assert lines and all(b"INFO" in ln for ln in lines)
        total += len(lines)
    assert total == 20  # 1/4 of 80 lines are INFO


def test_jumbo_batch_over_default_grpc_cap():
    """A coalesced batch well past gRPC's 4 MB default must round-trip."""
    lines = [b"x" * 4096 for _ in range(2000)]  # ~8 MB
    lines[500] = b"y" * 2000 + b"ERROR" + b"y" * 2000

    async def fn(client, _):
        return await client.match(lines)

    got = asyncio.run(with_server(PATTERNS, "cpu", fn))
    assert got.count(True) == 1 and got[500] is True


def test_cli_remote_pattern_mismatch_aborts(tmp_path):
    async def main():
        server = FilterServer(["OTHER"], backend="cpu", port=0)
        port = await server.start()
        try:
            opts = parse_args([
                "-n", "default", "-a", "-p", str(tmp_path / "x"),
                "--match", "INFO", "--remote", f"127.0.0.1:{port}",
            ])
            fc = FakeCluster.synthetic(n_pods=1)
            with pytest.raises(PatternMismatch):
                await app.run_async(opts, backend=fc)
        finally:
            await server.stop()

    asyncio.run(main())


def test_clean_shutdown_no_destroyed_tasks(recwarn):
    """VERDICT r1: awaited aclose() must leave no fire-and-forget close
    task to die with the loop (asyncio debug surfaces those as 'Task was
    destroyed but it is pending!' warnings)."""
    import warnings

    async def fn(client, _):
        await client.match([b"one ERROR", b"fine"])

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        loop = asyncio.new_event_loop()
        loop.set_debug(True)
        try:
            loop.run_until_complete(with_server(PATTERNS, "cpu", fn))
        finally:
            loop.close()
    msgs = [str(w.message) for w in caught]
    assert not any("Task was destroyed" in m for m in msgs), msgs


def test_verify_rejects_case_mode_mismatch():
    async def run():
        server = FilterServer(PATTERNS, backend="cpu", port=0,
                              ignore_case=True)
        port = await server.start()
        client = RemoteFilterClient(f"127.0.0.1:{port}")
        try:
            with pytest.raises(PatternMismatch):
                await client.verify_patterns(PATTERNS, ignore_case=False)
            await client.verify_patterns(PATTERNS, ignore_case=True)
        finally:
            await client.aclose()
            await server.stop()

    asyncio.run(run())


def _mint_cert(tmp_path, cn="localhost", name="srv"):
    """Self-signed cert+key with a SAN for 127.0.0.1/localhost, via the
    system openssl (no extra Python deps)."""
    import subprocess

    key, crt = tmp_path / f"{name}.key", tmp_path / f"{name}.crt"
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", str(key), "-out", str(crt), "-days", "2",
         "-subj", f"/CN={cn}",
         "-addext", "subjectAltName=DNS:localhost,IP:127.0.0.1"],
        check=True, capture_output=True)
    return str(key), str(crt)


def test_bearer_auth_enforced():
    """A token-protected server accepts the right bearer and rejects a
    missing/wrong one with UNAUTHENTICATED (cert-free auth for the
    cross-node collector->filterd hop)."""
    import grpc

    from klogs_tpu.cluster.backend import ClusterError

    async def run():
        server = FilterServer(PATTERNS, backend="cpu", port=0,
                              auth_token="s3cret")
        port = await server.start()
        good = RemoteFilterClient(f"127.0.0.1:{port}", auth_token="s3cret")
        bad = RemoteFilterClient(f"127.0.0.1:{port}")
        wrong = RemoteFilterClient(f"127.0.0.1:{port}", auth_token="nope")
        try:
            assert await good.match([b"an ERROR", b"fine"]) == [True, False]
            for c in (bad, wrong):
                with pytest.raises(ClusterError, match="UNAUTHENTICATED"):
                    await c.match([b"x"])
        finally:
            for c in (good, bad, wrong):
                await c.aclose()
            await server.stop()

    asyncio.run(run())


def test_tls_round_trip(tmp_path):
    """TLS server + client verifying it against the minted CA; a
    plaintext client against the TLS port fails, not silently passes."""
    import grpc

    key, crt = _mint_cert(tmp_path)

    async def run():
        server = FilterServer(PATTERNS, backend="cpu", port=0,
                              host="localhost", tls_cert=crt, tls_key=key)
        port = await server.start()
        tls = RemoteFilterClient(f"localhost:{port}", tls_ca=crt)
        plain = RemoteFilterClient(f"localhost:{port}")
        try:
            assert await tls.match([b"ERROR!", b"ok"]) == [True, False]
            info = await tls.hello()
            assert info["patterns"] == PATTERNS
            from klogs_tpu.cluster.backend import ClusterError
            with pytest.raises(ClusterError):
                await asyncio.wait_for(plain.match([b"x"]), timeout=5)
        finally:
            await tls.aclose()
            await plain.aclose()
            await server.stop()

    asyncio.run(run())


def test_mtls_requires_client_cert(tmp_path):
    import grpc

    skey, scrt = _mint_cert(tmp_path, name="srv")
    ckey, ccrt = _mint_cert(tmp_path, name="cli")

    async def run():
        server = FilterServer(PATTERNS, backend="cpu", port=0,
                              host="localhost", tls_cert=scrt, tls_key=skey,
                              tls_client_ca=ccrt)
        port = await server.start()
        with_cert = RemoteFilterClient(f"localhost:{port}", tls_ca=scrt,
                                       tls_cert=ccrt, tls_key=ckey)
        without = RemoteFilterClient(f"localhost:{port}", tls_ca=scrt)
        try:
            assert await with_cert.match([b"ERROR"]) == [True]
            from klogs_tpu.cluster.backend import ClusterError
            with pytest.raises(ClusterError):
                await asyncio.wait_for(without.match([b"x"]), timeout=5)
        finally:
            await with_cert.aclose()
            await without.aclose()
            await server.stop()

    asyncio.run(run())


def test_partial_tls_config_is_loud():
    from klogs_tpu.service.client import ServiceConfigError

    with pytest.raises(ValueError, match="together"):
        FilterServer(PATTERNS, backend="cpu", tls_cert="x.crt")
    with pytest.raises(ValueError, match="requires"):
        FilterServer(PATTERNS, backend="cpu", tls_client_ca="ca.crt")
    with pytest.raises(ServiceConfigError, match="require tls_ca"):
        RemoteFilterClient("h:1", tls_cert="c.crt", tls_key="c.key")
    with pytest.raises(ServiceConfigError, match="together"):
        RemoteFilterClient("h:1", tls_ca="ca.crt", tls_cert="c.crt")
    with pytest.raises(ServiceConfigError, match="cannot read"):
        RemoteFilterClient("h:1", tls_ca="/nonexistent/ca.crt")


def test_bearer_token_rotation_survives(tmp_path):
    """Both sides read the token from a file per RPC: rotating the
    mounted Secret mid-stream keeps the pipeline authenticated with no
    restart (the kubelet updates the file in place)."""
    tok = tmp_path / "token"
    tok.write_text("v1\n")

    async def run():
        server = FilterServer(PATTERNS, backend="cpu", port=0,
                              auth_token_file=str(tok))
        port = await server.start()
        client = RemoteFilterClient(f"127.0.0.1:{port}",
                                    auth_token_file=str(tok))
        try:
            assert await client.match([b"ERROR"]) == [True]
            tok.write_text("v2\n")  # rotation
            assert await client.match([b"ERROR again"]) == [True]
        finally:
            await client.aclose()
            await server.stop()

    asyncio.run(run())


def test_exclude_patterns_over_service():
    """filterd with --match + --exclude semantics; the handshake also
    verifies the exclude set (divergent filtering is impossible)."""
    async def run():
        server = FilterServer(["ERROR"], backend="cpu", port=0,
                              exclude=["healthz"])
        port = await server.start()
        client = RemoteFilterClient(f"127.0.0.1:{port}")
        try:
            await client.verify_patterns(["ERROR"], exclude=["healthz"])
            got = await client.match(
                [b"ERROR a", b"ERROR healthz", b"fine"])
            assert got == [True, False, False]
            with pytest.raises(PatternMismatch, match="exclude"):
                await client.verify_patterns(["ERROR"], exclude=[])
        finally:
            await client.aclose()
            await server.stop()

    asyncio.run(run())


from tests.conftest import http_get as _http_get  # noqa: E402


def test_metrics_scrape_on_live_coalesced_server():
    """The acceptance path: /metrics on a live filterd serving
    coalesced framed batches is valid Prometheus exposition covering
    all five instrumented layers, and /healthz (liveness) vs /readyz
    (readiness) split correctly across the cold-start warmup."""
    import threading

    import numpy as np

    from klogs_tpu.filters.base import frame_lines
    from klogs_tpu.obs import Registry

    reg = Registry()  # private: exact-count assertions stay hermetic

    async def run():
        server = FilterServer(PATTERNS, backend="cpu", port=0,
                              metrics_port=0, registry=reg)
        # Deterministic cold start: gate the engine's first fetch so
        # the warmup batch (and therefore readiness) waits on us.
        release = threading.Event()
        engine = server._service._filter
        orig_fetch = engine.fetch_framed
        gated = [True]

        def gated_fetch(handle):
            if gated[0]:
                gated[0] = False
                release.wait(5)
            return orig_fetch(handle)

        engine.fetch_framed = gated_fetch
        port = await server.start()
        mport = server.metrics_port
        clients = []
        try:
            # Mid-"compile": alive (don't restart) but NOT ready
            # (don't route) — the cold-start distinction.
            status, body = await _http_get(mport, "/healthz")
            assert status == 200
            status, body = await _http_get(mport, "/readyz")
            assert status == 503
            release.set()
            await asyncio.wait_for(server._warmup_task, 10)
            status, _ = await _http_get(mport, "/readyz")
            assert status == 200

            # Concurrent collectors shipping framed batches -> one
            # coalesced device group on the server.
            clients = [RemoteFilterClient(f"127.0.0.1:{port}")
                       for _ in range(3)]
            batches = [[b"an ERROR %d" % i, b"fine %d" % i]
                       for i in range(3)]
            results = await asyncio.gather(*[
                c.match_framed(*frame_lines(b)[:2])
                for c, b in zip(clients, batches)])
            for got in results:
                assert got.tolist() == [True, False]

            status, body = await _http_get(mport, "/metrics")
            assert status == 200
            text = body.decode()
            # All five instrumented layers in one exposition.
            for layer in ("klogs_engine_", "klogs_coalescer_",
                          "klogs_sink_", "klogs_fanout_", "klogs_rpc_"):
                assert layer in text, f"{layer} missing from scrape"
            # ...and live values, not just registered families.
            assert reg.family("klogs_rpc_requests_total").labels(
                method="MatchFramed").value == 3
            assert reg.family("klogs_coalescer_groups_total").value >= 1
            # warmup + client batches all crossed the engine
            assert reg.family(
                "klogs_engine_device_batch_seconds").count >= 2
            assert 'klogs_rpc_requests_total{method="MatchFramed"} 3' \
                in text
            assert "klogs_build_info" in text
        finally:
            for c in clients:
                await c.aclose()
            await server.stop()

    asyncio.run(run())


def test_exclude_only_service():
    async def run():
        server = FilterServer([], backend="cpu", port=0, exclude=["debug"])
        port = await server.start()
        client = RemoteFilterClient(f"127.0.0.1:{port}")
        try:
            got = await client.match([b"debug x", b"keep me"])
            assert got == [False, True]
        finally:
            await client.aclose()
            await server.stop()

    asyncio.run(run())


class TestClientCloseTask:
    def test_aclose_settles_a_parked_sync_close(self):
        """close() under a running loop parks its work on
        self._close_task; aclose() must settle it so the task cannot
        outlive the client (regression for the resource-lifecycle
        finding on the fire-and-forget close task)."""

        class _FakeChannel:
            def __init__(self):
                self.closes = 0

            async def close(self):
                self.closes += 1

        async def scenario():
            c = object.__new__(RemoteFilterClient)
            c._channel = _FakeChannel()
            c._close_task = None
            c.close()  # sync path: parks the channel close on a task
            assert c._close_task is not None
            await c.aclose()
            assert c._close_task is None
            assert c._channel.closes == 2  # parked close + aclose close
            leftovers = [t for t in asyncio.all_tasks()
                         if t is not asyncio.current_task()
                         and not t.done()]
            assert leftovers == []

        asyncio.run(scenario())


class TestServeTeardown:
    """serve() must stop the bound listener on every exit path — a
    raise after start() (banner printing) and a cancellation landing
    in wait() (regressions for the resource-lifecycle findings on the
    serve() teardown path)."""

    class _FakeServer:
        def __init__(self, *a, **kw):
            self.stops = 0
            self.tls_cert = None
            self.tls_client_ca = None
            self.auth_enabled = False
            self.host = "127.0.0.1"
            self.metrics_host = "127.0.0.1"
            self.metrics_port = None
            self.tenants = None
            self.backend = "cpu"
            self.patterns = ["x"]

        async def start(self):
            return 50051

        async def stop(self):
            self.stops += 1

        async def wait(self):
            await asyncio.Event().wait()

    def _patch(self, monkeypatch):
        from klogs_tpu.service import server as server_mod

        made = []

        def factory(*a, **kw):
            s = self._FakeServer()
            made.append(s)
            return s

        monkeypatch.setattr(server_mod, "FilterServer", factory)
        return server_mod, made

    def test_banner_raise_stops_server(self, monkeypatch):
        server_mod, made = self._patch(monkeypatch)

        def boom(*a):
            raise RuntimeError("banner boom")

        monkeypatch.setattr(server_mod, "banner_line", boom)
        with pytest.raises(RuntimeError, match="banner boom"):
            asyncio.run(server_mod.serve(["x"], "cpu", "127.0.0.1", 0))
        assert [s.stops for s in made] == [1]

    def test_cancel_during_wait_stops_server(self, monkeypatch):
        server_mod, made = self._patch(monkeypatch)

        async def scenario():
            task = asyncio.create_task(
                server_mod.serve(["x"], "cpu", "127.0.0.1", 0))
            await asyncio.sleep(0.05)
            task.cancel()
            with pytest.raises(asyncio.CancelledError):
                await task

        asyncio.run(asyncio.wait_for(scenario(), timeout=10))
        assert [s.stops for s in made] == [1]


def test_tpu_backend_hello_and_profile_carry_the_device_block():
    """filterd --backend tpu says what it runs on, in Hello and on
    /profile: the device as JAX reports it and the kernel in use (here
    the deliberate CPU run of the test suite: jnp)."""
    import jax

    from klogs_tpu.obs import Registry
    from klogs_tpu.obs.profiler import PROFILER

    async def run():
        server = FilterServer(["INFO"], backend="tpu", port=0,
                              metrics_port=0, registry=Registry())
        port = await server.start()
        c = RemoteFilterClient(f"127.0.0.1:{port}")
        try:
            info = await c.hello()
            status, body = await _http_get(server.metrics_port, "/profile")
            return info["device"], status, json.loads(body)
        finally:
            await c.aclose()
            await server.stop()

    try:
        hello_dev, status, prof = asyncio.run(run())
    finally:
        PROFILER.reset()
    assert status == 200
    for dev in (hello_dev, prof["device"]):
        assert dev["platform"] == jax.devices()[0].platform == "cpu"
        assert dev["count"] == jax.device_count()
        assert dev["kernel"] == "jnp"

"""Filter service server: owns the engine + device, serves Match RPCs.

Run standalone:  python -m klogs_tpu.service --match ERROR --match 'WARN.*' \
                     --backend tpu --port 50051

All client batches funnel into one AsyncFilterService, so concurrent
collectors coalesce into shared device batches (the device's efficient
regime) regardless of how small each client's flushes are.

Transport security (for the collector-in-cluster -> filterd-near-TPU
deployment, where the hop crosses node boundaries):

- TLS: ``tls_cert``/``tls_key`` serve over TLS; adding
  ``tls_client_ca`` requires and verifies client certificates (mTLS).
- Bearer auth: ``auth_token`` (or ``auth_token_file``, re-read per RPC
  so a rotated mounted Secret keeps working without a restart) rejects
  any RPC not carrying ``authorization: Bearer <token>`` metadata with
  UNAUTHENTICATED — the cert-free option a Kubernetes Secret deploys in
  one line. Token-only mode over plaintext sends the secret in the
  clear; combine with TLS on untrusted networks (the server prints a
  reminder).

Both default off: the localhost/co-located case stays zero-config.
Partial TLS configuration (cert without key, client-ca without cert) is
a constructor error, never a silent plaintext fallback.
"""

import asyncio
import hmac
import time

import grpc

from klogs_tpu.filters.async_service import AsyncFilterService
from klogs_tpu.obs import trace
from klogs_tpu.obs.profiler import PROFILER, FleetCapacity
from klogs_tpu.service import transport
from klogs_tpu.version import BUILD_VERSION


def _make_filter(patterns: list[str], backend: str,
                 ignore_case: bool = False,
                 exclude: "list[str] | None" = None,
                 stats=None, kernel: "str | None" = None):
    from klogs_tpu.filters.base import build_include_exclude

    made = []

    def one(pats):
        if backend == "cpu":
            from klogs_tpu.filters.cpu import best_host_filter

            # Index metrics ride the first-built side's registry, same
            # rule as the stats wiring below.
            f = best_host_filter(
                pats, ignore_case=ignore_case,
                registry=stats.registry
                if stats is not None and not made else None)[0]
        else:
            from klogs_tpu.filters.tpu import NFAEngineFilter

            # Stats ride the first-built side only (≙ make_pipeline's
            # rule: feeding both combiner inputs would double-count).
            f = NFAEngineFilter(pats, ignore_case=ignore_case,
                                kernel=kernel,
                                stats=stats if not made else None)
        made.append(f)
        return f

    return build_include_exclude(one, patterns, exclude)


def _uses_device_sweep(filt) -> bool:
    """True when any engine behind ``filt`` (possibly an
    include/exclude combiner) runs the fused device literal sweep —
    the TPU engine's sweep tables or an IndexedFilter narrowing on the
    device path."""
    stack = [filt]
    while stack:
        f = stack.pop()
        for attr in ("include", "exclude", "inner"):
            sub = getattr(f, attr, None)
            if sub is not None:
                stack.append(sub)
        if getattr(f, "_sweep_tables", None) is not None and \
                getattr(f, "_sweep_path", "device") == "device" and \
                not getattr(f, "bypassed", False):
            # bypassed: an IndexedFilter that switched itself to
            # scan-all no longer sweeps at all — stop advertising it.
            return True
        # A mesh-backed engine carries the sweep inside MeshEngine
        # (its _fn_sweep, surfaced as `swept`), not in the wrapper's
        # _sweep_tables.
        if getattr(getattr(f, "_engine", None), "swept", False):
            return True
    return False


def _read_tls(path: str, what: str) -> bytes:
    try:
        with open(path, "rb") as f:
            return f.read()
    except OSError as e:
        # ValueError: __main__'s friendly one-liner path.
        raise ValueError(f"cannot read {what} {path}: {e}") from e


def _client_host(peer: str) -> str:
    """gRPC peer -> bounded-cardinality client label: the HOST only.
    Ports churn per connection ('ipv4:127.0.0.1:54321'), so keeping
    them would mint a new series per reconnect."""
    if peer.startswith(("ipv4:", "ipv6:")):
        return peer.split(":", 1)[1].rsplit(":", 1)[0]
    return peer or "unknown"


class FilterServer:
    def __init__(self, patterns: list[str], backend: str = "tpu",
                 host: str = "127.0.0.1", port: int = 50051,
                 ignore_case: bool = False,
                 tls_cert: str | None = None, tls_key: str | None = None,
                 tls_client_ca: str | None = None,
                 auth_token: str | None = None,
                 auth_token_file: str | None = None,
                 exclude: "list[str] | None" = None,
                 metrics_port: int | None = None,
                 metrics_host: str = "127.0.0.1",
                 registry=None,
                 multi_set: bool = False,
                 tenant_max_sets: "int | None" = None,
                 tenant_quota_lines: "int | None" = None,
                 tenant_idle_s: "float | None" = None):
        if bool(tls_cert) != bool(tls_key):
            raise ValueError(
                "tls_cert and tls_key must be provided together "
                "(refusing to fall back to plaintext on partial TLS config)")
        if tls_client_ca and not tls_cert:
            raise ValueError("tls_client_ca (mTLS) requires tls_cert/tls_key")
        if auth_token and auth_token_file:
            raise ValueError("pass auth_token OR auth_token_file, not both")
        self.patterns = list(patterns)
        self.exclude = list(exclude or [])
        self.multi_set = multi_set
        if not self.patterns and not self.exclude and not multi_set:
            raise ValueError("need at least one --match or --exclude pattern")
        self.backend = backend
        # The kernel the TPU engines run, resolved once up front: a
        # filterd that finds no TPU exits at start (DeviceUnavailable)
        # instead of serving at jnp speed. None on the host backend.
        self.kernel = None
        self._device_doc = None
        if backend == "tpu":
            import functools

            from klogs_tpu.filters.tpu import device_doc, resolve_kernel

            self.kernel = resolve_kernel()
            self._device_doc = functools.partial(device_doc, self.kernel)
        self.host = host
        self.port = port
        self.ignore_case = ignore_case
        self.tls_cert = tls_cert
        self.tls_key = tls_key
        self.tls_client_ca = tls_client_ca
        self.auth_token = auth_token
        self.auth_token_file = auth_token_file
        # Observability sidecar (opt-in, --metrics-port): the registry
        # backs FilterStats AND the engine/coalescer/RPC families, so
        # /metrics is one consistent panel over the live pipeline.
        # Without it the server runs the zero-instrumentation path.
        self.metrics_port = metrics_port
        self.metrics_host = metrics_host
        self.registry = None
        self.health = None
        self._stats = None
        self._http = None
        self._warmup_task: asyncio.Task | None = None
        self._m_rpc = None
        if metrics_port is not None:
            from klogs_tpu import obs
            from klogs_tpu.filters.base import FilterStats

            # Per-SERVER registry by default: a restarted in-process
            # filterd must not inherit the previous instance's
            # counters into its /metrics.
            self.registry = registry if registry is not None else obs.Registry()
            # The whole inventory up front: a scrape during cold start
            # already shows every layer's (zero-valued) families.
            obs.register_all(self.registry)
            self.registry.family("klogs_build_info").labels(
                version=BUILD_VERSION).set(1)
            # Trace/flight-recorder counters scrape from this server's
            # sidecar (the tracer itself is process-global — one trace
            # story per process; a later server instance rebinds).
            from klogs_tpu.obs import trace as _trace

            _trace.TRACER.bind_registry(self.registry)
            _trace.RECORDER.bind_registry(self.registry)
            PROFILER.bind_registry(self.registry)
            self._stats = FilterStats(registry=self.registry)
            self._m_rpc = {
                "req": self.registry.family("klogs_rpc_requests_total"),
                "err": self.registry.family("klogs_rpc_errors_total"),
                "lat": self.registry.family("klogs_rpc_request_seconds"),
                "client": self.registry.family(
                    "klogs_rpc_client_requests_total"),
            }
            self.health = obs.Health()
            # Liveness: the coalescer loop must still accept work —
            # a closed service means restart; a merely-cold one does not.
            self.health.add_live_check(
                "coalescer", lambda: self._service is None
                or not self._service._closed)
        # Fleet capacity accounting (offered vs admitted lines +
        # headroom), advertised through Hello whether or not the
        # metrics sidecar runs — the sharded client re-exports it
        # per endpoint for the HPA scrape. The profiler carries it on
        # /profile too (a later server instance rebinds, like the
        # tracer's registry binding above).
        self.capacity = FleetCapacity(registry=self.registry)
        PROFILER.attach_capacity(self.capacity)
        # Not a bound method: the process-global profiler must not keep
        # this server alive past its run.
        PROFILER.attach_device(self._device_doc)
        # Multi-tenant registry (docs/TENANCY.md): content-addressed
        # pattern sets behind weighted-fair admission; the startup set
        # (when present) is adopted as a pinned default lane so legacy
        # un-tagged RPCs compete fairly with registered tenants.
        self.tenants = None
        self.default_set: "str | None" = None
        self._sweep_task: asyncio.Task | None = None
        self._sweep_stop: "asyncio.Event | None" = None
        if multi_set:
            from klogs_tpu.service.tenancy import PatternSetRegistry

            def factory(pats: list[str], excl: list[str],
                        ic: bool):
                # Tenant engines share the server's FilterStats (and
                # registry): engine metrics, sweep-fallback counters,
                # and flight-recorder triggers must fire for REGISTERED
                # sets too, not just the startup default — per-set
                # attribution rides the klogs_tenant_* families.
                return _make_filter(pats, self.backend, ignore_case=ic,
                                    exclude=excl, stats=self._stats,
                                    kernel=self.kernel)

            self.tenants = PatternSetRegistry(
                factory, stats=self._stats,
                max_sets=tenant_max_sets,
                quota_lines=tenant_quota_lines,
                idle_evict_s=tenant_idle_s)
        # The startup set compiles exactly as before (single-set path
        # byte-identical); a registry-only multi-set server (no --match)
        # has no default engine until the first Register RPC. In
        # registry mode the default service rides the registry's SHARED
        # fetch pool + in-flight budget — the process owns one device,
        # and legacy un-tagged traffic must not double that budget.
        self._filter = None
        self._service = None
        if self.patterns or self.exclude:
            self._filter = _make_filter(patterns, backend,
                                        ignore_case=ignore_case,
                                        exclude=self.exclude,
                                        stats=self._stats,
                                        kernel=self.kernel)
            shared = ({} if self.tenants is None
                      else dict(executor=self.tenants.executor,
                                in_flight=self.tenants.in_flight))
            self._service = AsyncFilterService(self._filter,
                                               stats=self._stats,
                                               **shared)
            if self.tenants is not None:
                self.default_set = self.tenants.adopt(
                    self.patterns, self.exclude, self.ignore_case,
                    self._service)
        self._server: grpc.aio.Server | None = None

    @property
    def device_sweep(self) -> bool:
        """Engine-detail discovery (Hello): whether the thousand-
        pattern device sweep is gating this server's kernel RIGHT NOW
        — an operator debugging a fleet throughput step needs to see
        which servers run the fused path without scraping each
        sidecar. Computed per Hello, not cached at startup: a sweep
        that degraded mid-run (kernel failure, host fallback) must
        stop being advertised. In registry mode ANY registered set's
        engine counts — a registry-only server whose tenants run the
        fused path must not advertise False."""
        if self._filter is not None and _uses_device_sweep(self._filter):
            return True
        if self.tenants is not None:
            return any(
                not e.pinned and _uses_device_sweep(e.service._filter)
                for e in self.tenants.entries())
        return False

    @property
    def device(self) -> "dict | None":
        """The device block (filters.tpu.device_doc) Hello and /profile
        carry; None on the host backend."""
        return self._device_doc() if self._device_doc is not None else None

    @property
    def auth_enabled(self) -> bool:
        return bool(self.auth_token or self.auth_token_file)

    def _current_token(self) -> str | None:
        if self.auth_token_file:
            # Re-read per check: a rotated mounted Secret (kubelet
            # updates the file) keeps authenticating without a restart
            # — same rationale as kubeconfig.in_cluster_creds.
            try:
                with open(self.auth_token_file) as f:
                    return f.read().strip() or None
            except OSError:
                return None
        return self.auth_token

    async def _check_auth(self, context) -> bool:
        if not self.auth_enabled:
            return True
        # The token-file re-read is disk I/O on a per-RPC path: off the
        # event loop, or one slow/NFS-mounted Secret volume stalls every
        # concurrent collector's RPCs behind it.
        if self.auth_token_file:
            token = await asyncio.to_thread(self._current_token)
        else:
            token = self.auth_token
        meta = dict(context.invocation_metadata() or ())
        got = meta.get("authorization", "")
        # Compare utf-8 bytes: compare_digest on str raises TypeError
        # for non-ASCII, which would turn every RPC into UNKNOWN.
        if token and hmac.compare_digest(
                got.encode(), f"Bearer {token}".encode()):
            return True
        await context.abort(grpc.StatusCode.UNAUTHENTICATED,
                            "missing or wrong bearer token")
        return False  # unreachable; abort raises

    def _instrumented(self, method: str, handler):
        """RPC-layer metrics wrapper: requests/errors/latency by
        method, plus per-client-host counts. Identity when metrics are
        off (no per-RPC overhead)."""
        if self._m_rpc is None:
            return handler
        from klogs_tpu.obs.trace import TRACER

        m = self._m_rpc
        req = m["req"].labels(method=method)
        err = m["err"].labels(method=method)
        lat = m["lat"].labels(method=method)

        async def wrapped(request: bytes, context) -> bytes:
            t0 = time.perf_counter()
            req.inc()
            m["client"].labels(
                client=_client_host(context.peer() or "")).inc()
            try:
                return await handler(request, context)
            except BaseException:
                # Aborts (UNAUTHENTICATED / INVALID_ARGUMENT) raise
                # through here too — they ARE failed RPCs.
                err.inc()
                raise
            finally:
                # Exemplar: the rpc.server span (still open — _traced
                # wraps outside this layer) links the latency sample to
                # its trace in the exposition.
                lat.observe(time.perf_counter() - t0,
                            exemplar=TRACER.exemplar())

        return wrapped

    def _traced(self, method: str, handler):
        """Tracing wrapper (outermost): continue the collector's batch
        trace across the wire — the traceparent metadata entry parents
        this RPC's ``rpc.server`` span under the client's ``rpc.client``
        span, so one trace covers collector sink -> shard routing ->
        RPC -> server coalescer -> device. Without the metadata (old
        client, tracing off) the RPC roots its own trace under local
        sampling; when neither side records, the handler runs bare."""
        from klogs_tpu.obs.trace import TRACER

        async def wrapped(request: bytes, context) -> bytes:
            ctx = transport.extract_trace(context.invocation_metadata())
            if ctx is None and not TRACER.enabled:
                return await handler(request, context)
            with TRACER.span("rpc.server", parent=ctx, method=method,
                             request_bytes=len(request)):
                return await handler(request, context)

        return wrapped

    async def _warmup(self) -> None:
        """Cold-start gate behind /readyz: push one real (tiny) framed
        batch through the coalescer and engine. Success proves the
        engine compiled, the device answered, and the coalescer loop
        runs — the three things 'ready' means here. Until then the
        server is live but NOT ready (routing traffic to a compiling
        filterd queues RPCs behind a multi-second jit trace)."""
        from klogs_tpu.filters.base import frame_lines

        try:
            if self._service is None:
                # Registry-only multi-set server: nothing compiles until
                # the first Register RPC, so the server is ready as soon
                # as it binds (each registration pays its own compile
                # off the event loop).
                self.health.mark_warm()
                return
            payload, offsets, _ = frame_lines([b"klogs-warmup probe"])
            await self._service.match_framed(payload, offsets)
            # mark_warm, not set_ready: a drain that raced the warmup
            # (rolling restart right after start) must stick.
            self.health.mark_warm()
        except Exception as e:
            print(f"klogs filterd: warmup batch failed ({e}); "
                  "/readyz stays unready", flush=True)

    def _capacity_keys(self) -> dict:
        """The fleet-capacity advertisement every Hello carries, next
        to metrics_port/device_sweep: the sharded client re-exports
        these per endpoint (klogs_fleet_endpoint_*) and may weigh
        routing by headroom later. Old clients ignore the keys."""
        cap = self.capacity.doc()
        return {
            "headroom": cap["headroom"],
            "fleet_offered_lines": cap["offered_lines"],
            "fleet_admitted_lines": cap["admitted_lines"],
        }

    async def _hello(self, request: bytes, context) -> bytes:
        await self._check_auth(context)
        if self.tenants is not None:
            return await self._hello_multi(request)
        return transport.pack({
            **self._capacity_keys(),
            "patterns": self.patterns,
            "exclude": self.exclude,
            "ignore_case": self.ignore_case,
            "backend": self.backend,
            "version": BUILD_VERSION,
            "framed": True,
            # Readiness discovery for the sharded client tier: where
            # /readyz lives (bound sidecar host+port), so a collector
            # can drain this server on rolling restarts without extra
            # configuration. port=None when the sidecar is off — the
            # client then relies on breakers alone. The host matters:
            # a loopback-bound sidecar is unreachable from a remote
            # collector, and the client must know NOT to probe it (a
            # refused probe would wrongly demote a healthy server).
            # Old clients ignore both keys.
            "metrics_port": self.metrics_port,
            "metrics_host": self.metrics_host,
            # Engine detail: whether the fused device literal sweep is
            # gating this server's kernel (thousand-pattern mode).
            # Old clients ignore the key.
            "device_sweep": self.device_sweep,
            "device": self.device,
        })

    async def _hello_multi(self, request: bytes) -> bytes:
        """Multi-set Hello: answer verify_patterns against the REGISTRY
        (match-by-fingerprint), not the single startup list — a second
        collector with a different set registers instead of hard-failing
        PatternMismatch. A request carrying the collector's invocation
        is echoed back when that fingerprint is registered (so the
        legacy client-side comparison passes); the legacy empty Hello
        gets the default (startup) set, keeping old collectors working
        against a multi-set server unchanged."""
        from klogs_tpu.service.shard import pattern_fingerprint

        want = transport.decode_hello_request(request)
        patterns, exclude, ignore_case = (self.patterns, self.exclude,
                                          self.ignore_case)
        set_id: "str | None" = self.default_set
        registered = self.default_set is not None
        if want is not None:
            set_id = pattern_fingerprint(want["patterns"], want["exclude"],
                                         want["ignore_case"])
            entry = self.tenants.get(set_id)
            registered = entry is not None
            if registered:
                patterns = entry.patterns
                exclude = entry.exclude
                ignore_case = entry.ignore_case
        sp = trace.TRACER.current_span()
        if sp is not None and set_id is not None:
            sp.set_attr("tenant", set_id)
        return transport.pack({
            **self._capacity_keys(),
            "patterns": patterns,
            "exclude": exclude,
            "ignore_case": ignore_case,
            "backend": self.backend,
            "version": BUILD_VERSION,
            "framed": True,
            "metrics_port": self.metrics_port,
            "metrics_host": self.metrics_host,
            "device_sweep": self.device_sweep,
            "device": self.device,
            # Registry mode: the client should Register its set (once)
            # and tag match RPCs with the returned id. "sets" is the
            # live registered count (banner/fleet debugging).
            "multi_set": True,
            "sets": self.tenants.count,
            "set": set_id,
            "registered": registered,
        })

    async def _register(self, request: bytes, context) -> bytes:
        """Register-once RPC: content-addressed, so two tenants with
        identical sets share one compiled engine (the engine-build
        counter must NOT advance on the second registration)."""
        await self._check_auth(context)
        if self.tenants is None:
            await context.abort(
                grpc.StatusCode.UNIMPLEMENTED,
                "this filterd runs in single-set mode (start it with "
                "--multi-set to accept registrations)")
        try:
            req = transport.decode_register_request(request)
        except (ValueError, KeyError, TypeError) as e:
            await context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                                f"bad register request: {e}")
        try:
            set_id, shared = await self.tenants.register(
                req["patterns"], req["exclude"], req["ignore_case"],
                weight=req["weight"])
        except ValueError as e:
            # RegexSyntaxError and friends: the tenant's OWN set is
            # broken — its registration fails, nobody else's.
            await context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                                f"cannot compile pattern set: {e}")
        sp = trace.TRACER.current_span()
        if sp is not None:
            sp.set_attr("tenant", set_id)
        return transport.encode_register_response(
            set_id, shared, self.tenants.count)

    def _route_set(self, set_id: "str | None") -> "str | None":
        """Which registry lane serves this request: its explicit set
        tag, else the default (startup) set."""
        return set_id if set_id is not None else self.default_set

    async def _tenant_match(self, set_id: "str | None", context, run):
        """Route one match RPC through the registry: admission, quota
        shed (RESOURCE_EXHAUSTED — the client degrades it through the
        existing --on-filter-error path), unknown/evicted set
        (FAILED_PRECONDITION — the client re-registers and retries)."""
        from klogs_tpu.service.tenancy import OverQuota, SetNotRegistered

        lane = self._route_set(set_id)
        if lane is None:
            await context.abort(
                grpc.StatusCode.FAILED_PRECONDITION,
                f"{transport.SET_NOT_REGISTERED}: this multi-set "
                "filterd has no default pattern set; register one "
                "first")
        sp = trace.TRACER.current_span()
        if sp is not None:
            sp.set_attr("tenant", lane)
        try:
            return await run(lane)
        except OverQuota as e:
            await context.abort(grpc.StatusCode.RESOURCE_EXHAUSTED,
                                f"{transport.OVER_QUOTA}: {e}")
        except SetNotRegistered as e:
            await context.abort(
                grpc.StatusCode.FAILED_PRECONDITION,
                f"{transport.SET_NOT_REGISTERED}: {e}")

    async def _match(self, request: bytes, context) -> bytes:
        await self._check_auth(context)
        try:
            lines, set_id = transport.decode_match_request(request)
        except (ValueError, KeyError, TypeError) as e:
            # Same contract as _match_framed: a malformed request fails
            # ITS OWN RPC with a clean status, never an UNKNOWN
            # traceback.
            await context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                                f"bad match request: {e}")
        # Capacity accounting: offered BEFORE admission, admitted only
        # when verdicts came back — an admission shed (OverQuota abort)
        # leaves the gap the autoscaling signal measures.
        self.capacity.note_offered(len(lines))
        if self.tenants is not None:
            mask = await self._tenant_match(
                set_id, context,
                lambda lane: self.tenants.match(lane, lines))
        else:
            mask = await self._service.match(lines)
        self.capacity.note_admitted(len(lines))
        return transport.encode_match_response(mask)

    async def _match_framed(self, request: bytes, context) -> bytes:
        """Framed hot path: payload+offsets in, raw mask bytes out —
        no per-line Python object anywhere server-side (the batch goes
        contiguous buffer -> C pack_classify_framed -> device -> numpy
        mask)."""
        await self._check_auth(context)
        try:
            payload, offsets, set_id = transport.decode_framed_request(
                request)
        except (ValueError, KeyError, TypeError) as e:
            # Malformed framing fails ITS OWN RPC with a clean status —
            # decode validation guarantees it can never reach the
            # coalescer shared with other collectors.
            await context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                                f"bad framed request: {e}")
        # Same offered/admitted discipline as _match (framed hot path:
        # two integer adds per BATCH, nothing per line).
        n_lines = max(len(offsets) - 1, 0)
        self.capacity.note_offered(n_lines)
        if self.tenants is not None:
            mask = await self._tenant_match(
                set_id, context,
                lambda lane: self.tenants.match_framed(
                    lane, payload, offsets))
        else:
            mask = await self._service.match_framed(payload, offsets)
        self.capacity.note_admitted(n_lines)
        return transport.encode_framed_response(mask)

    async def start(self) -> int:
        """Binds and starts serving; returns the bound port (useful when
        port=0 asks the OS for an ephemeral one)."""
        handler = grpc.method_handlers_generic_handler(
            transport.SERVICE,
            {
                "Hello": grpc.unary_unary_rpc_method_handler(
                    self._traced("Hello", self._instrumented(
                        "Hello", self._hello))),
                "Match": grpc.unary_unary_rpc_method_handler(
                    self._traced("Match", self._instrumented(
                        "Match", self._match))),
                "MatchFramed": grpc.unary_unary_rpc_method_handler(
                    self._traced("MatchFramed", self._instrumented(
                        "MatchFramed", self._match_framed))),
                "Register": grpc.unary_unary_rpc_method_handler(
                    self._traced("Register", self._instrumented(
                        "Register", self._register))),
            },
        )
        # Jumbo batches (thousands of long lines) exceed gRPC's 4 MB
        # default message cap; the batcher bounds real sizes well under
        # this.
        self._server = grpc.aio.server(options=[
            ("grpc.max_receive_message_length", 256 * 1024 * 1024),
            ("grpc.max_send_message_length", 256 * 1024 * 1024),
        ])
        self._server.add_generic_rpc_handlers((handler,))
        # A host of the form "unix:/path.sock" binds a Unix domain
        # socket (grpc-native scheme) — the co-located collector->
        # filterd deployment on one TPU host skips the TCP stack
        # entirely; port is meaningless there.
        if self.host.startswith("unix:"):
            addr = self.host
        else:
            addr = f"{self.host}:{self.port}"
        if self.tls_cert and self.tls_key:
            # One-time reads, but start() runs on the loop (an in-process
            # collector may already be streaming): disk I/O goes through
            # a worker thread like every other blocking read here.
            key = await asyncio.to_thread(_read_tls, self.tls_key,
                                          "TLS key")
            cert = await asyncio.to_thread(_read_tls, self.tls_cert,
                                           "TLS certificate")
            ca = (await asyncio.to_thread(_read_tls, self.tls_client_ca,
                                          "client CA bundle")
                  if self.tls_client_ca else None)
            creds = grpc.ssl_server_credentials(
                [(key, cert)], root_certificates=ca,
                require_client_auth=ca is not None)
            self.port = self._server.add_secure_port(addr, creds)
        else:
            self.port = self._server.add_insecure_port(addr)
        await self._server.start()
        if self.metrics_port is not None:
            from klogs_tpu.obs import MetricsHTTPServer

            self._http = MetricsHTTPServer(
                self.registry, health=self.health,
                host=self.metrics_host, port=self.metrics_port)
            try:
                self.metrics_port = await self._http.start()
            except BaseException as e:
                # Unbindable metrics port — or a cancellation landing
                # mid-bind: tear the already-started gRPC server down
                # (serve()'s finally is not armed yet). OSError gets
                # the friendly ValueError path; everything else
                # (CancelledError included) re-raises after teardown.
                self._http = None
                await self._server.stop(0)
                if self.tenants is not None:
                    self.tenants.close()
                if self._service is not None:
                    self._service.close()
                if isinstance(e, OSError):
                    raise ValueError(
                        f"cannot bind metrics port "
                        f"{self.metrics_host}:{self.metrics_port}: {e}"
                    ) from e
                raise
            # Readiness flips when the warmup batch lands — NOT here:
            # /readyz during the cold-start compile must answer 503
            # while /healthz already answers 200.
            self._warmup_task = asyncio.get_running_loop().create_task(
                self._warmup())
        if self.tenants is not None and self.tenants.idle_evict_s > 0:
            # Cold-set reaper: idle compiled engines are released (and
            # re-registerable — the on-disk DFA LRU makes that a table
            # load, not a determinization).
            self._sweep_stop = asyncio.Event()
            self._sweep_task = asyncio.get_running_loop().create_task(
                self.tenants.run_idle_sweeper(self._sweep_stop))
        return self.port

    async def wait(self) -> None:
        await self._server.wait_for_termination()

    async def stop(self, grace: float = 1.0) -> None:
        if self._warmup_task is not None:
            self._warmup_task.cancel()
            try:
                await self._warmup_task
            except (asyncio.CancelledError, Exception):
                pass
            self._warmup_task = None
        if self._sweep_task is not None:
            if self._sweep_stop is not None:
                self._sweep_stop.set()
            self._sweep_task.cancel()
            try:
                await self._sweep_task
            except (asyncio.CancelledError, Exception):
                pass
            self._sweep_task = None
        if self._http is not None:
            await self._http.stop()
            self._http = None
        if self._server is not None:
            await self._server.stop(grace)
        if self.tenants is not None:
            # Registered sets drain and close; the pinned startup
            # service is the server's own, closed below.
            await self.tenants.aclose()
        if self._service is not None:
            self._service.close()


def banner_line(server: "FilterServer", where: str, mode: str) -> str:
    """The startup 'serving ...' line: registry mode reports the LIVE
    set count (the operating number — the fixed startup list, possibly
    empty, is just one lane), single-set mode stays byte-identical."""
    if server.tenants is not None:
        return (f"klogs filterd: serving pattern-set registry "
                f"({server.tenants.count} live set(s), cap "
                f"{server.tenants.max_sets}) [{server.backend}] on "
                f"{where} ({mode})")
    return (f"klogs filterd: serving {len(server.patterns)} pattern(s) "
            f"[{server.backend}] on {where} ({mode})")


async def serve(patterns: list[str], backend: str, host: str, port: int,
                ignore_case: bool = False,
                trace_json: "str | None" = None,
                profile_json: "str | None" = None,
                multi_set: bool = False, **security) -> None:
    if trace_json is not None:
        # Server-side batch tracing: spans root at rpc.server (or
        # continue a collector's trace via the metadata traceparent)
        # and land in this file as JSON lines; /traces on the metrics
        # sidecar serves the same spans.
        from klogs_tpu.obs import trace as _trace

        _trace.TRACER.enable_default()
        _trace.TRACER.set_json_path(trace_json)
    # Continuous utilization profiling: --profile-json turns it fully
    # on (unless KLOGS_PROFILE_SAMPLE pins a rate — including 0, the
    # kill switch); the env knob alone also enables it, feeding
    # /profile on the metrics sidecar without a file sink.
    PROFILER.maybe_enable()
    if profile_json is not None and PROFILER.enable():
        PROFILER.set_json_path(profile_json)
    server = FilterServer(patterns, backend, host=host, port=port,
                          ignore_case=ignore_case, multi_set=multi_set,
                          **security)
    bound = await server.start()
    prof_stop: "asyncio.Event | None" = None
    prof_task: "asyncio.Task | None" = None
    # Everything past start() runs under the stop() finally: a raise
    # while printing the banner (or starting the profiler ticker) must
    # not leak the bound listener or the ticker task.
    try:
        if PROFILER.enabled:
            prof_stop = asyncio.Event()
            prof_task = asyncio.get_running_loop().create_task(
                PROFILER.run_ticker(prof_stop))
        mode = "TLS" if server.tls_cert else "plaintext"
        if server.tls_client_ca:
            mode = "mTLS"
        if server.auth_enabled:
            mode += "+bearer"
            if not server.tls_cert:
                print("klogs filterd: WARNING bearer auth over plaintext "
                      "sends the token in the clear; add --tls-cert/"
                      "--tls-key on untrusted networks", flush=True)
        where = (server.host if server.host.startswith("unix:")
                 else f"{server.host}:{bound}")
        print(banner_line(server, where, mode), flush=True)
        if server.metrics_port is not None:
            print(f"klogs filterd: metrics on http://{server.metrics_host}:"
                  f"{server.metrics_port}/metrics (health: /healthz, "
                  "readiness: /readyz)", flush=True)
        await server.wait()
    finally:
        try:
            await server.stop()
        finally:
            # Nested so a cancellation landing inside server.stop()
            # still reaps the ticker instead of abandoning it.
            if prof_task is not None:
                # Final tick lands inside run_ticker before it
                # returns, so the JSONL stream ends with the complete
                # picture.
                if prof_stop is not None:
                    prof_stop.set()
                try:
                    await prof_task
                except (asyncio.CancelledError, Exception):  # noqa: BLE001
                    pass
                PROFILER.set_json_path(None)
            # A degrade trigger armed near shutdown may have no
            # further local root span to ride — write it before the
            # process exits (mirrors the collector-side teardown in
            # app.py).
            from klogs_tpu.obs import trace as _trace2

            _trace2.RECORDER.flush()

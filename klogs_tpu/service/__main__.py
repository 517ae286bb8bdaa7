"""`python -m klogs_tpu.service` — run the filter service daemon."""

import argparse
import asyncio
import sys

from klogs_tpu.filters.compiler.parser import RegexSyntaxError
from klogs_tpu.filters.tpu import DeviceUnavailable
from klogs_tpu.service.server import serve


def main() -> None:
    ap = argparse.ArgumentParser(
        prog="klogs-filterd",
        description="klogs_tpu filter service: owns the TPU engine, "
        "serves Match RPCs to log collectors",
    )
    ap.add_argument("--match", action="append", default=[],
                    help="regex pattern to KEEP (repeatable)")
    ap.add_argument("--exclude", action="append", default=[],
                    help="regex pattern to DROP even when kept "
                    "(repeatable; alone = keep all non-matching)")
    ap.add_argument("--backend", choices=["cpu", "tpu"], default="tpu")
    ap.add_argument("--multi-set", action="store_true", dest="multi_set",
                    help="multi-tenant registry mode: collectors "
                    "register their own pattern sets (content-addressed "
                    "— identical sets share one compiled engine) and "
                    "are admitted weighted-fair with per-set quotas; "
                    "--match/--exclude become the optional default set "
                    "for legacy collectors (docs/TENANCY.md; "
                    "KLOGS_TENANT_* env knobs)")
    ap.add_argument("-I", "--ignore-case", action="store_true",
                    dest="ignore_case",
                    help="case-insensitive patterns (collectors must "
                    "connect with matching -I or the pattern handshake "
                    "rejects them)")
    ap.add_argument("--host", default="127.0.0.1",
                    help='bind address; "unix:/path.sock" serves a Unix '
                    "domain socket (co-located collector deployments)")
    ap.add_argument("--port", type=int, default=50051)
    ap.add_argument("--tls-cert", help="PEM server certificate (enables TLS)")
    ap.add_argument("--tls-key", help="PEM server private key")
    ap.add_argument("--tls-client-ca",
                    help="PEM CA bundle; require+verify client certs (mTLS)")
    ap.add_argument("--auth-token-file",
                    help="file with a shared bearer token (e.g. a mounted "
                    "Kubernetes Secret); RPCs without it are rejected")
    ap.add_argument("--metrics-port", type=int, default=None,
                    metavar="PORT",
                    help="serve Prometheus /metrics plus /healthz "
                    "(liveness) and /readyz (readiness; unready until "
                    "the warmup batch clears the cold-start compile) on "
                    "this HTTP port (0 = ephemeral; binds 127.0.0.1)")
    ap.add_argument("--trace-json", default=None, dest="trace_json",
                    metavar="PATH",
                    help="write every finished trace span as one JSON "
                    "line to PATH (server-side batch tracing; continues "
                    "a collector's trace when its RPC carries the "
                    "traceparent metadata). Implies KLOGS_TRACE_SAMPLE=1 "
                    "unless that variable is set")
    ap.add_argument("--profile-json", default=None, dest="profile_json",
                    metavar="PATH",
                    help="append one JSON line per profiler tick to "
                    "PATH: per-stage busy-seconds/utilization, queue/"
                    "in-flight samples, and the offered/admitted/"
                    "headroom capacity block. Enables the continuous "
                    "pipeline profiler (KLOGS_PROFILE_SAMPLE pins the "
                    "span-sampling rate; 0 disables). The same "
                    "snapshot serves /profile on --metrics-port "
                    "(docs/OBSERVABILITY.md)")
    ap.add_argument("--metrics-host", default="127.0.0.1",
                    metavar="HOST",
                    help="metrics/health bind address. Cross-node "
                    "sharded collectors drain this server via its "
                    "advertised /readyz, which they can only reach when "
                    "this binds a routable address (e.g. 0.0.0.0); the "
                    "loopback default keeps the sidecar private and "
                    "collectors then rely on breakers alone")
    ns = ap.parse_args()
    if ns.auth_token_file:
        # Fail fast on a bad path/empty file; the server re-reads the
        # file per RPC afterwards so Secret rotation needs no restart.
        try:
            with open(ns.auth_token_file) as f:
                if not f.read().strip():
                    ap.error(f"--auth-token-file {ns.auth_token_file} is empty")
        except OSError as e:
            ap.error(f"cannot read --auth-token-file: {e}")
    if ns.backend == "tpu":
        from klogs_tpu.utils.cache import enable_compile_cache

        enable_compile_cache()
    try:
        asyncio.run(serve(ns.match, ns.backend, ns.host, ns.port,
                          ignore_case=ns.ignore_case,
                          multi_set=ns.multi_set,
                          tls_cert=ns.tls_cert, tls_key=ns.tls_key,
                          tls_client_ca=ns.tls_client_ca,
                          auth_token_file=ns.auth_token_file,
                          exclude=ns.exclude,
                          metrics_port=ns.metrics_port,
                          metrics_host=ns.metrics_host,
                          trace_json=ns.trace_json,
                          profile_json=ns.profile_json))
    except KeyboardInterrupt:
        pass
    except DeviceUnavailable as e:
        print(f"klogs-filterd: {e}", file=sys.stderr)
        raise SystemExit(1)
    except RegexSyntaxError as e:  # subclasses ValueError: catch first
        print(f"unsupported --match pattern: {e}", file=sys.stderr)
        raise SystemExit(1)
    except ValueError as e:
        # FilterServer validates TLS pairing (cert+key, client-ca needs
        # both) — surface as the friendly one-liner.
        print(f"klogs-filterd: {e}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()

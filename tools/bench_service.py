"""Remote filter service throughput (the host<->TPU gRPC transport —
the framework's DCN-boundary analog, SURVEY.md §5 "Distributed
communication backend").

Spawns filterd in a subprocess (owns the device), then drives it from
this process with N concurrent Match RPCs over one HTTP/2 channel —
the collector-side shape (many FilteredSink flushes pipelining through
RemoteFilterClient). Reports sustained lines/s at several concurrency
levels and batch sizes; appends SERVICE_BENCH.json at the repo root.

    python tools/bench_service.py --backend cpu   # transport-only
    python tools/bench_service.py --backend tpu   # server owns the TPU
"""

import argparse
import asyncio
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402
from klogs_tpu.service.client import RemoteFilterClient  # noqa: E402

PORT = 50917


async def run_bench(backend: str, seconds: float, target: str,
                    patterns: "list[str]") -> dict:
    client = RemoteFilterClient(target)
    # Wait for the server to come up (TPU attach can take ~20-40s).
    deadline = time.monotonic() + 120
    while True:
        try:
            await client.verify_patterns(patterns)
            break
        except Exception:
            if time.monotonic() > deadline:
                raise
            await asyncio.sleep(1.0)

    from klogs_tpu.filters.base import frame_lines

    lines = [ln.rstrip(b"\n") for ln in bench.make_lines(262144)]
    results = []
    # Legacy per-line rows (the round-4 configs, for trend comparison)
    # then framed rows: same volume, O(1) wire cost per batch. The
    # jumbo framed configs are the production collector shape (a 1000-
    # pod follow fans into few coalesced flushes).
    configs = [
        ("legacy", 1024, 4), ("legacy", 8192, 8), ("legacy", 8192, 16),
        ("framed", 8192, 8), ("framed", 8192, 16),
        ("framed", 65536, 8), ("framed", 65536, 16),
        ("framed", 262144, 8),
    ]
    for mode, batch_lines, conc in configs:
        if mode == "framed":
            batches = [frame_lines(lines[i : i + batch_lines])[:2]
                       for i in range(0, len(lines), batch_lines)]
            await client.match_framed(*batches[0])  # warm jit caches

            async def one(k, batches=batches):
                await client.match_framed(*batches[k % len(batches)])
        else:
            batches = [lines[i : i + batch_lines]
                       for i in range(0, len(lines), batch_lines)]
            await client.match(batches[0])

            async def one(k, batches=batches):
                await client.match(batches[k % len(batches)])

        done = 0
        stop_at = time.monotonic() + seconds

        async def worker():
            nonlocal done
            k = 0
            while time.monotonic() < stop_at:
                await one(k)
                done += batch_lines
                k += 1

        t0 = time.perf_counter()
        await asyncio.gather(*[worker() for _ in range(conc)])
        lps = done / (time.perf_counter() - t0)
        results.append({"mode": mode, "batch_lines": batch_lines,
                        "concurrency": conc, "lines_per_s": round(lps, 1)})
        print(f"{mode} batch={batch_lines} conc={conc}: {lps:,.0f} lines/s",
              flush=True)
    await client.aclose()
    return {"backend": backend, "runs": results}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", choices=["cpu", "tpu"], default="tpu")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--uds", action="store_true",
                    help="unix-domain-socket loopback instead of TCP")
    ap.add_argument("--null-engine", action="store_true",
                    help="serve the match-all pattern (engine cost "
                    "zero): measures the PURE transport+coalescing "
                    "ceiling of the service path")
    ns = ap.parse_args()
    patterns = [""] if ns.null_engine else bench.PATTERNS

    if ns.uds:
        target = f"unix:/tmp/klogs_bench_{os.getpid()}.sock"
        argv = [sys.executable, "-m", "klogs_tpu.service",
                "--host", target, "--backend", ns.backend]
    else:
        target = f"127.0.0.1:{PORT}"
        argv = [sys.executable, "-m", "klogs_tpu.service",
                "--port", str(PORT), "--backend", ns.backend]
    for p in patterns:
        argv += ["--match", p]
    env = dict(os.environ)
    if ns.backend == "cpu" or ns.null_engine:
        # Null-engine runs never touch the device (match-all shortcuts
        # at dispatch): keep the server off the chip so the row
        # isolates transport.
        env["JAX_PLATFORMS"] = "cpu"
    server = subprocess.Popen(argv, env=env,
                              stdout=subprocess.DEVNULL,
                              stderr=subprocess.DEVNULL)
    try:
        res = asyncio.run(run_bench(ns.backend, ns.seconds, target,
                                    patterns))
        if ns.uds:
            res["transport"] = "uds"
        if ns.null_engine:
            res["null_engine"] = True
    finally:
        server.terminate()
        server.wait()
    from datetime import date

    res["date"] = date.today().isoformat()
    res["n_patterns"] = len(patterns)
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "SERVICE_BENCH.json")
    doc = []
    if os.path.exists(path):
        with open(path) as f:
            doc = json.load(f)
    doc.append(res)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"wrote {path}", flush=True)


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Drive klogs-tpu's main path on a TPU and check it against Python `re`.

    python chip_smoke.py                 # one chip: phases A-D
    python chip_smoke.py --chips 4       # four chips: phase A on the mesh
    python chip_smoke.py --rehearse-cpu  # CPU rehearsal at tiny sizes

The north-star deployment of BASELINE.json (32 patterns x 256 pods on
v5e-1), through the entry points a user calls:

  A  collector, K=32: `python -m klogs_tpu.cli -a --cluster fake
     --backend tpu`, 256 pods x 2 containers x 2000 lines;
  B  rule set, K=1024 (bench.make_patterns): 256 pods x 1 x 1000 lines,
     which selects the device literal sweep and the gated kernel;
  C  long lines: `--source replay:` over 200,000 seeded lines, ~2% of
     4-64 KB (the chunk kernel) and a few over 128 KB (ops/seqscan);
  D  served path: a filterd child owns the chip and a collector child
     runs phase A's corpus through it with --remote, importing no JAX.

The parent never imports JAX: each phase runs in its own child, one at a
time, because a chip belongs to one process. The parent makes every
input from --seed and computes the expected output files with
RegexFilter (K sequential `re.search` calls per line) in a process pool.
A phase fails on any mismatch, on a kernel other than `pallas`, or on a
non-zero klogs_engine_degrade_total. The last line of stdout is one JSON
object, printed only when every phase passed:

  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

With --rehearse-cpu the children run on JAX_PLATFORMS=cpu with the
Pallas kernels in interpret mode, at tiny sizes, and the last line says
"ok": false: a rehearsal is never a chip result.
"""

import argparse
import glob
import json
import multiprocessing
import os
import random
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
DEADLINE_S = 1140  # the whole run, compiles included, stays under 1200 s

# (pods, containers, lines per container) per phase; C's line count.
FULL = {"A": (256, 2, 2000), "B": (256, 1, 1000), "C": 200_000}
TINY = {"A": (8, 2, 40), "B": (4, 1, 25), "C": 400}


def fail(msg: str) -> "SystemExit":
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    return SystemExit(1)


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def read_bytes(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read()


def load_json(path: str):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


# -- the oracle (runs in pool workers; RegexFilter, never the engine) --

def _oracle_fake(job):
    """Expected output files for a slice of the fake cluster: the
    matched lines of each <pod>__<container>.log, in order."""
    from klogs_tpu.cluster.fake import synthetic_line
    from klogs_tpu.filters.cpu import RegexFilter

    patterns, pods, n_containers, n_lines, epoch = job
    rf = RegexFilter(patterns)
    files, n_matched = {}, 0
    for p in pods:
        pod = f"pod-{p:04d}"
        for c in range(n_containers):
            cont = f"c{c}"
            lines = [synthetic_line(pod, cont, i, epoch - (n_lines - 1 - i))
                     for i in range(n_lines)]
            keep = [ln for ln, m in zip(lines, rf.match_lines(lines)) if m]
            n_matched += len(keep)
            files[f"{pod}__{cont}.log"] = b"".join(keep)
    return files, n_matched


def _oracle_lines(job):
    from klogs_tpu.filters.cpu import RegexFilter

    patterns, lines = job
    return RegexFilter(patterns).match_lines(lines)


def oracle_pool():
    # spawn: workers start clean (the parent holds no JAX, but its
    # pool must not depend on that).
    n = max(1, min(12, (os.cpu_count() or 2) - 1))
    return multiprocessing.get_context("spawn").Pool(n)


def expected_fake(pool, patterns, shape, epoch):
    pods, conts, n = shape
    step = max(1, pods // (4 * pool._processes))
    jobs = [(patterns, range(i, min(pods, i + step)), conts, n, epoch)
            for i in range(0, pods, step)]
    files, matched = {}, 0
    for part, m in pool.map(_oracle_fake, jobs):
        files.update(part)
        matched += m
    return files, matched, pods * conts * n


# -- inputs ------------------------------------------------------------

_WORDS = [f"{k}={v}" for k in ("rid", "op", "node", "shard", "span", "lvl",
                               "gen", "zone", "term", "idx")
          for v in ("a1", "b7", "c3f", "d09", "e4", "f55", "0x1c", "9e")]
_NEEDLES = ["panic: runtime error", "oom-killer invoked", "code=503",
            "x-request-id: 4f9a0c", "retry 3/5", "GET /api/v2 404",
            "disk /var full", "seq=99999", "uid=123456", "deadline exceeded"]


def replay_corpus(path: str, n: int, rng: random.Random) -> list:
    """n seeded log lines: ~2% of 4-64 KB (chunk kernel), a few over
    128 KB (seqscan), the rest ~100-200 B; needles planted at random
    offsets in some, so matched and unmatched lines of every length
    class occur. The filler vocabulary holds no pattern literal."""
    huge_at = set(rng.sample(range(n), max(1, min(5, n // 1000))))
    lines = []
    for i in range(n):
        if i in huge_at:
            size = rng.randint(130_000, 300_000)
        elif rng.random() < 0.02:
            size = rng.randint(4_097, 65_536)
        else:
            size = rng.randint(80, 200)
        words, length = [], 0
        while length < size:
            w = rng.choice(_WORDS)
            words.append(w)
            length += len(w) + 1
        if rng.random() < 0.3:
            words.insert(rng.randrange(len(words) + 1), rng.choice(_NEEDLES))
        lines.append((" ".join(words) + "\n").encode())
    with open(path, "wb") as f:
        f.writelines(lines)
    return lines


# -- children ----------------------------------------------------------

class Run:
    """One smoke run: its settings, work dir, child env and deadline."""

    def __init__(self, ns, work: str, native_so: "str | None"):
        self.ns = ns
        self.work = work
        self.t_end = time.monotonic() + DEADLINE_S
        self.sizes = TINY if ns.rehearse_cpu else FULL
        self.epoch = 1_760_000_000 + ns.seed
        env = dict(os.environ)
        env.pop("KLOGS_NO_NATIVE", None)  # the parent's own setting
        env["PYTHONUNBUFFERED"] = "1"
        if native_so:
            env["KLOGS_NATIVE_SO"] = native_so
        else:
            env["KLOGS_NO_NATIVE"] = "1"
        if ns.rehearse_cpu:
            env["JAX_PLATFORMS"] = "cpu"
            env["KLOGS_TPU_KERNEL"] = "interpret"
            if ns.chips == 4:
                env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                    " --xla_force_host_platform_device_count=4")
        self.env = env
        self.kernel = "interpret" if ns.rehearse_cpu else "pallas"
        self.procs: list = []

    def remaining(self) -> float:
        left = self.t_end - time.monotonic()
        if left < 10:
            raise fail("out of time (the run must end within 1200 s)")
        return left

    def spawn(self, name: str, argv: list, extra_env=None):
        env = dict(self.env, **(extra_env or {}))
        with open(os.path.join(self.work, f"{name}.log"), "wb") as out:
            proc = subprocess.Popen(argv, cwd=ROOT, env=env, stdout=out,
                                    stderr=subprocess.STDOUT)
        self.procs.append(proc)
        return proc

    def wait(self, name: str, proc) -> float:
        """Wait for a child; its wall seconds. Non-zero exit fails."""
        t0 = time.monotonic()
        try:
            rc = proc.wait(timeout=self.remaining())
        except subprocess.TimeoutExpired:
            proc.kill()
            raise fail(f"{name}: timed out; log tail:\n{self.tail(name)}")
        if rc != 0:
            raise fail(f"{name}: exit {rc}; log tail:\n{self.tail(name)}")
        return time.monotonic() - t0

    def tail(self, name: str, n: int = 4000) -> str:
        return read_bytes(os.path.join(self.work, f"{name}.log"))[-n:].decode(
            errors="replace")

    def stop_all(self) -> None:
        for proc in self.procs:
            if proc.poll() is None:
                proc.send_signal(signal.SIGINT)
        for proc in self.procs:
            try:
                proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()


def collector_argv(patterns, out_dir, stats, source=None) -> list:
    argv = [sys.executable, "-m", "klogs_tpu.cli", "-a"]
    argv += ["--source", source] if source else ["--cluster", "fake"]
    for p in patterns:
        argv += ["--match", p]
    return argv + ["--backend", "tpu", "-p", out_dir, "--stats-json", stats]


def fake_env(shape, epoch) -> dict:
    pods, conts, n = shape
    return {"KLOGS_FAKE_PODS": str(pods), "KLOGS_FAKE_CONTAINERS": str(conts),
            "KLOGS_FAKE_LINES": str(n), "KLOGS_FAKE_EPOCH": str(epoch)}


def metric(doc: dict, name: str, **labels) -> float:
    """Sum of a --stats-json metric's samples whose labels include
    ``labels``."""
    fam = doc["metrics"].get(name, {"samples": []})
    return sum(s["value"] for s in fam["samples"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def degrades(doc: dict) -> dict:
    fam = doc["metrics"].get("klogs_engine_degrade_total", {"samples": []})
    return {s["labels"].get("path", ""): s["value"]
            for s in fam["samples"] if s["value"]}


def compare_files(name: str, out_dir: str, expected: dict) -> None:
    """Every expected file (matched lines only) equals what was written,
    line for line; no other file was written."""
    got = {os.path.basename(p) for p in glob.glob(os.path.join(out_dir, "*"))}
    extra = got - set(expected)
    if extra:
        raise fail(f"{name}: unexpected output files {sorted(extra)[:5]}")
    for fname, want in expected.items():
        path = os.path.join(out_dir, fname)
        have = read_bytes(path) if os.path.exists(path) else b""
        if have != want:
            w, h = want.splitlines(), have.splitlines()
            i = next((k for k, (a, b) in enumerate(zip(w, h)) if a != b),
                     min(len(w), len(h)))
            raise fail(f"{name}: {fname} differs from the re oracle at line "
                       f"{i} ({len(h)} lines written, {len(w)} expected): "
                       f"want {w[i][:160] if i < len(w) else None!r}, "
                       f"got {h[i][:160] if i < len(h) else None!r}")


def check_device(run: Run, name: str, dev: "dict | None") -> dict:
    if not dev:
        raise fail(f"{name}: the child reported no device block")
    want_platform = "cpu" if run.ns.rehearse_cpu else "tpu"
    if dev["platform"] != want_platform:
        raise fail(f"{name}: ran on {dev['platform']!r}, not {want_platform}")
    if dev["kernel"] != run.kernel:
        raise fail(f"{name}: kernel {dev['kernel']!r}, not {run.kernel}")
    if dev["count"] != run.ns.chips:
        raise fail(f"{name}: {dev['count']} devices, expected {run.ns.chips}")
    return dev


def report(name: str, doc: dict, dev: dict, wall: float, extra="") -> None:
    s = doc["summary"]
    row = {"phase": name, "lines": s["lines_in"], "matched": s["lines_matched"],
           "wall_s": round(wall, 3), "compile_s": dev.get("compile_s"),
           "compile_cache_hits": dev.get("compile_cache_hits"),
           "kernel": dev["kernel"], "native": dev.get("native"),
           "device_kind": dev["device_kind"], "count": dev["count"]}
    if "mesh" in dev:
        row["mesh"] = dev["mesh"]
    say(json.dumps(row) + extra)


def collector_phase(run: Run, pool, name: str, patterns, shape):
    """A, B and the 4-chip phase: the collector on the fake cluster."""
    out_dir = os.path.join(run.work, name)
    stats = os.path.join(run.work, f"{name}.json")
    proc = run.spawn(name, collector_argv(patterns, out_dir, stats),
                     fake_env(shape, run.epoch))
    expected, n_match, n_lines = expected_fake(pool, patterns, shape,
                                               run.epoch)
    wall = run.wait(name, proc)
    doc = load_json(stats)
    dev = check_device(run, name, doc.get("device"))
    check_counts(name, doc, n_lines, n_match)
    compare_files(name, out_dir, expected)
    return doc, dev, wall


def check_counts(name: str, doc: dict, n_lines: int, n_match: int) -> None:
    s = doc["summary"]
    if not 0 < n_match < n_lines:
        raise fail(f"{name}: the corpus needs matched and unmatched lines "
                   f"({n_match}/{n_lines} match)")
    if (s["lines_in"], s["lines_matched"]) != (n_lines, n_match):
        raise fail(f"{name}: filtered {s['lines_matched']}/{s['lines_in']} "
                   f"lines, the oracle says {n_match}/{n_lines}")
    bad = degrades(doc)
    if bad:
        raise fail(f"{name}: kernels degraded: {bad}")


def phase_c(run: Run, pool, patterns) -> dict:
    rng = random.Random(run.ns.seed)
    corpus = os.path.join(run.work, "replay.corpus")
    lines = replay_corpus(corpus, run.sizes["C"], rng)
    out_dir = os.path.join(run.work, "C")
    stats = os.path.join(run.work, "C.json")
    proc = run.spawn("C", collector_argv(patterns, out_dir, stats,
                                         source=f"replay:{corpus}"))
    step = max(1, len(lines) // (8 * pool._processes))
    verdicts = []
    for part in pool.map(_oracle_lines, [(patterns, lines[i:i + step])
                                         for i in range(0, len(lines), step)]):
        verdicts += part
    wall = run.wait("C", proc)
    doc = load_json(stats)
    dev = check_device(run, "C", doc.get("device"))
    check_counts("C", doc, len(lines), sum(verdicts))
    outs = glob.glob(os.path.join(out_dir, "*.log"))
    if len(outs) != 1:
        raise fail(f"C: expected one output file, found {outs}")
    expected = {os.path.basename(outs[0]):
                b"".join(ln for ln, m in zip(lines, verdicts) if m)}
    compare_files("C", out_dir, expected)
    sizes = [(len(ln), m) for ln, m in zip(lines, verdicts)]
    classes = {
        "long_lines": sum(1 for n, _ in sizes if 4096 < n <= 131072),
        "long_matched": sum(1 for n, m in sizes if 4096 < n <= 131072 and m),
        "huge_lines": sum(1 for n, _ in sizes if n > 131072),
        "huge_matched": sum(1 for n, m in sizes if n > 131072 and m)}
    report("C", doc, dev, wall, " " + json.dumps(classes))
    return dev


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_get(port: int, path: str) -> "tuple[int, bytes]":
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                    timeout=10) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, b""
    except OSError:
        return 0, b""


def phase_d(run: Run, pool, patterns, shape) -> dict:
    """filterd owns the chip; a collector with --remote runs phase A's
    corpus through it and must not import JAX at all."""
    port, mport = free_port(), free_port()
    argv = [sys.executable, "-m", "klogs_tpu.service", "--backend", "tpu",
            "--port", str(port), "--metrics-port", str(mport)]
    for p in patterns:
        argv += ["--match", p]
    t0 = time.monotonic()
    server = run.spawn("D-filterd", argv)
    while http_get(mport, "/readyz")[0] != 200:
        if server.poll() is not None:
            raise fail("D: filterd exited before ready; log tail:\n"
                       + run.tail("D-filterd"))
        if run.remaining() < 60:
            raise fail("D: filterd never became ready")
        time.sleep(1)
    ready_s = time.monotonic() - t0
    out_dir = os.path.join(run.work, "D")
    stats = os.path.join(run.work, "D.json")
    cli = collector_argv(patterns, out_dir, stats)[3:]
    cli[cli.index("--backend"):cli.index("--backend") + 2] = [
        "--remote", f"127.0.0.1:{port}"]
    # The CLI's own __main__, then one line saying whether JAX was ever
    # imported: it must not be, or it would contend for the chip.
    code = ("import runpy, sys\n"
            f"sys.argv = ['klogs'] + {cli!r}\n"
            "try:\n"
            "    runpy.run_module('klogs_tpu.cli', run_name='__main__')\n"
            "except SystemExit as e:\n"
            "    rc = e.code or 0\n"
            "print('JAX_IMPORTED=%d' % ('jax' in sys.modules))\n"
            "sys.exit(rc)\n")
    proc = run.spawn("D", [sys.executable, "-c", code],
                     fake_env(shape, run.epoch))
    expected, n_match, n_lines = expected_fake(pool, patterns, shape,
                                               run.epoch)
    wall = run.wait("D", proc)
    if "JAX_IMPORTED=0" not in run.tail("D", 400):
        raise fail("D: the --remote collector imported JAX")
    doc = load_json(stats)
    check_counts("D", doc, n_lines, n_match)
    compare_files("D", out_dir, expected)
    status, body = http_get(mport, "/profile")
    dev = check_device(run, "D", json.loads(body).get("device")
                       if status == 200 else None)
    status, body = http_get(mport, "/metrics")
    bad = [ln for ln in body.decode().splitlines()
           if ln.startswith("klogs_engine_degrade_total")
           and float(ln.rsplit(" ", 1)[1]) != 0]
    if status != 200 or bad:
        raise fail(f"D: filterd /metrics {status}, degrades {bad}")
    server.send_signal(signal.SIGINT)
    try:
        rc = server.wait(timeout=30)
    except subprocess.TimeoutExpired:
        server.kill()
        raise fail("D: filterd did not stop on SIGINT")
    if rc != 0:
        raise fail(f"D: filterd exit {rc} on SIGINT; log tail:\n"
                   + run.tail("D-filterd"))
    report("D", doc, dev, wall, f" filterd_ready_s={ready_s:.3f}")
    return dev


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run phase A on the 4-chip mesh, nothing else")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="JAX_PLATFORMS=cpu, interpret kernels, tiny sizes; "
                    "the last line says ok: false")
    ap.add_argument("--keep", action="store_true",
                    help="keep the work directory (inputs, outputs, logs)")
    ns = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "klogs_tpu")):
        raise fail(f"no klogs_tpu package next to {__file__}: run it from "
                   "a checkout of the repo")
    if not ns.rehearse_cpu:
        if os.environ.get("KLOGS_TPU_KERNEL"):
            raise fail("KLOGS_TPU_KERNEL is set; a chip run takes the "
                       "kernel the engine selects")
        plats = os.environ.get("JAX_PLATFORMS", "")
        if plats and "tpu" not in plats.split(","):
            raise fail(f"JAX_PLATFORMS={plats!r} rules out the TPU")
    # The parent is the oracle: plain `re`, no native layer, no JAX.
    os.environ["KLOGS_NO_NATIVE"] = "1"
    sys.path.insert(0, ROOT)
    import bench
    from klogs_tpu import native

    work = tempfile.mkdtemp(prefix="chip_smoke-")
    so = os.path.join(work, "_hostops" + native._EXT)
    native_so = so if native.build(so) else None
    say(f"native host layer: {'built from ' + native._SRC if native_so else 'build failed; pure-Python fallback'}")
    run = Run(ns, work, native_so)
    pool = oracle_pool()
    k32, k1024 = bench.PATTERNS, bench.make_patterns(1024)
    devs = []
    try:
        if ns.chips == 4:
            doc, dev, wall = collector_phase(run, pool, "A4", k32,
                                             run.sizes["A"])
            mesh = dev.get("mesh") or {}
            if mesh.get("devices") != 4 or mesh.get("verdict_devices") != 4:
                raise fail(f"A4: the mesh does not span 4 devices: {mesh}")
            report("A4", doc, dev, wall)
            devs.append(dev)
        else:
            doc, dev, wall = collector_phase(run, pool, "A", k32,
                                             run.sizes["A"])
            report("A", doc, dev, wall)
            devs.append(dev)
            extra = {"KLOGS_TPU_SWEEP": "1"} if ns.rehearse_cpu else {}
            run.env.update(extra)  # interpret mode never auto-selects it
            doc, dev, wall = collector_phase(run, pool, "B", k1024,
                                             run.sizes["B"])
            swept = metric(doc, "klogs_sweep_batches_total", path="device")
            if not swept or metric(doc, "klogs_sweep_fallback_total"):
                raise fail("B: the device sweep did not carry the batches")
            report("B", doc, dev, wall, f" device_sweep_batches={swept:g}")
            devs.append(dev)
            for k in extra:
                del run.env[k]
            devs.append(phase_c(run, pool, k32))
            devs.append(phase_d(run, pool, k32, run.sizes["A"]))
    finally:
        run.stop_all()
        pool.terminate()
        pool.join()
        if ns.keep:
            say(f"work directory kept: {work}")
        else:
            import shutil

            shutil.rmtree(work, ignore_errors=True)
    kinds = {(d["platform"], d["device_kind"], d["count"]) for d in devs}
    if len(kinds) != 1:
        raise fail(f"phases ran on different devices: {sorted(kinds)}")
    platform, kind, count = kinds.pop()
    line = {"ok": not ns.rehearse_cpu,
            "device": {"platform": platform, "kind": kind, "count": count}}
    if ns.rehearse_cpu:
        line["rehearsal"] = "cpu"
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Top-level run orchestration.

Reference parity: rootCmd.Run (cmd/root.go:442-474) — splash, client +
namespace config, pod selection (label union vs interactive/all), log
fan-out, wait-or-keypress, final size table. Structured as testable
functions over an injected ClusterBackend instead of the reference's
package globals (cmd/root.go:36-49).
"""

import asyncio
import os
import signal
import threading
from typing import Iterable

from klogs_tpu.cli import Options
from klogs_tpu.cluster.backend import ClusterBackend
from klogs_tpu.cluster.types import LogOptions, PodInfo
from klogs_tpu.runtime.fanout import (
    FanoutRunner,
    StreamJob,
    plan_jobs,
    plan_source_jobs,
)
from klogs_tpu.ui import interactive, term, widgets
from klogs_tpu.utils.env import read as env_read
from klogs_tpu.utils import convert_bytes, parse_duration, split_log_file_name
from klogs_tpu.utils.duration import DurationError


def make_backend(opts: Options) -> ClusterBackend:
    if opts.cluster == "fake":
        from klogs_tpu.cluster.fake import FakeCluster

        n_pods = int(env_read("KLOGS_FAKE_PODS", "6"))
        n_containers = int(env_read("KLOGS_FAKE_CONTAINERS", "2"))
        n_lines = int(env_read("KLOGS_FAKE_LINES", "300"))
        # KLOGS_FAKE_EPOCH pins the clock the synthetic history ends at,
        # so an oracle outside the process can rebuild every line.
        kw = {}
        if env_read("KLOGS_FAKE_EPOCH"):
            epoch = float(env_read("KLOGS_FAKE_EPOCH"))
            kw["clock"] = lambda: epoch
        fc = FakeCluster.synthetic(
            n_pods=n_pods, n_containers=n_containers,
            lines_per_container=n_lines, **kw)
        fc.add_namespace("kube-system")
        return fc

    from klogs_tpu.cluster.kube import KubeBackend

    return KubeBackend.from_kubeconfig(opts.kubeconfig)


async def resolve_namespace(
    backend: ClusterBackend, opts: Options,
    select_keys: Iterable[str] | None = None,
) -> str:
    """configNamespace analog (cmd/root.go:90-103): explicit -n, else the
    kubeconfig current-context namespace; verify existence; on miss, warn
    and fall into the interactive picker (selection not re-validated,
    SURVEY.md §3.4)."""
    namespace = opts.namespace
    if not namespace:
        context, namespace = backend.current_context()
        term.info("Using Context %s", term.green(context))
    if not await backend.namespace_exists(namespace):
        term.warning("Namespace %s not found", namespace)
        names = await backend.list_namespaces()
        namespace = interactive.interactive_select(
            names, "Select a Namespace", keys=select_keys
        )
    term.info("Using Namespace %s", term.green(namespace))
    return namespace


async def select_noninteractive(
    backend: ClusterBackend, namespace: str, opts: Options,
    quiet: bool = False,
) -> list[PodInfo]:
    """The re-runnable core of pod selection: label union
    (cmd/root.go:455-461) or all-Ready (cmd/root.go:137-143). Shared by
    the startup path and the --watch-new re-poll so both always select
    the same pod set; ``quiet`` suppresses the per-call chatter during
    polling."""
    if opts.labels:
        pods: list[PodInfo] = []
        for label in opts.labels:
            if not quiet:
                term.info("Getting Pods with label %s\n", term.green(label))
            found = await backend.list_pods(namespace, label_selector=label)
            if not found and not quiet:
                term.error(
                    "No pods found in namespace %s with label %s\n", namespace, label
                )
            # Union semantics, no dedup across labels (cmd/root.go:458-460).
            pods.extend(found)
        return pods
    all_pods = await backend.list_pods(namespace)
    return [p for p in all_pods if p.ready]  # cmd/root.go:137-143


async def select_pods(
    backend: ClusterBackend, namespace: str, opts: Options,
    select_keys: Iterable[str] | None = None,
) -> list[PodInfo]:
    """Pod selection: label union (cmd/root.go:455-461) or
    listAllPods with Ready filter + optional multiselect (cmd/root.go:126-164)."""
    if opts.labels:
        return await select_noninteractive(backend, namespace, opts)

    ready = await select_noninteractive(backend, namespace, opts)
    if not ready:
        term.error("No pods found in namespace %s", namespace)
        return []
    if not opts.all_pods:
        by_name = {p.name: p for p in ready}
        chosen = interactive.interactive_multiselect(
            [p.name for p in ready], "Select Pods to get logs", keys=select_keys
        )
        if not chosen:
            term.error("No pods selected")
            return []
        return [by_name[n] for n in chosen]
    return ready


def build_log_options(opts: Options) -> LogOptions:
    """getLopOpts analog (cmd/root.go:201-221), plus the kubectl-parity
    additions --previous/--timestamps (PodLogOptions.Previous/
    .Timestamps — server-side, like since/tail/follow)."""
    if opts.previous and opts.follow:
        # kubectl parity: "only one of follow or previous may be true".
        term.fatal("--previous is incompatible with -f/--follow "
                   "(a terminated instance cannot stream)")
    if opts.since and opts.since_time:
        term.fatal("at most one of -s/--since and --since-time may be "
                   "given (kubectl parity)")
    lo = LogOptions(follow=opts.follow, previous=opts.previous,
                    timestamps=opts.timestamps)
    if opts.since:
        try:
            lo.since_seconds = int(parse_duration(opts.since))
        except DurationError as e:
            term.fatal("%s", e)
    if opts.since_time:
        from datetime import datetime

        try:
            dt = datetime.fromisoformat(
                opts.since_time.replace("Z", "+00:00"))
            if dt.tzinfo is None:  # see cli.main: naive is not RFC3339
                raise ValueError("missing timezone offset")
        except ValueError:
            # Backstop for library callers; cli.main rejects earlier.
            term.fatal("invalid --since-time %r (want RFC3339 with a "
                       "timezone)", opts.since_time)
        lo.since_time = opts.since_time
    if opts.tail != -1:
        lo.tail_lines = opts.tail
    return lo


def print_plan(pods: list[PodInfo], jobs: list[StreamJob]) -> None:
    """The pod/container tree + counts (cmd/root.go:231-274)."""
    term.info(
        "Found %s Pod(s) %s Container(s)",
        term.green(str(len(pods))), term.green(str(len(jobs))),
    )
    jobs_by_pod: dict[str, list[StreamJob]] = {}
    for j in jobs:
        jobs_by_pod.setdefault(j.pod, []).append(j)
    for i, pod in enumerate(pods):
        children = [
            j.container + (term.gray(" [init]") if j.init else "")
            for j in jobs_by_pod.get(pod.name, [])
        ]
        widgets.render_tree(f"{pod.name} {term.blue(f'[Pod #{i + 1}]')}", children)
    term.info("Acquiring logs \U0001f680")


def print_log_size(log_files: list[str], log_path: str) -> None:
    """printLogSize analog (cmd/root.go:279-309)."""
    if not log_files:
        term.error("No logs saved")
        return
    term.info("Logs saved to %s", term.green(log_path))
    table = [["Pod", "Container", "Size"]]
    previous_pod = ""
    for path in log_files:
        try:
            size = os.stat(path).st_size
        except OSError:
            continue  # soft-skip, cmd/root.go:292-293
        pod, container = split_log_file_name(path)
        label = term.gray(pod) if pod == previous_pod else pod
        table.append([label, container, convert_bytes(size)])
        previous_pod = pod
    widgets.render_table(table)


def _print_backfill_summary(pipeline) -> None:
    """--backfill exit accounting (match/shed), printed whether or not
    --stats was given — a run-to-completion mode owes its verdict."""
    if pipeline is None:
        term.info("Backfill complete (no --match/--exclude: every line "
                  "written)")
        return
    s = pipeline.stats
    term.info(
        "Backfill complete: %s lines in, %s matched (%.2f%%), %s shed",
        f"{s.lines_in:,}", f"{s.lines_matched:,}", s.matched_pct(),
        f"{s.degraded_lines:,}")


async def _watch_for_quit(
    stop: asyncio.Event, message: str, done: "threading.Event",
    spinner: bool = True,
) -> None:
    """pressKeyToExit analog (cmd/root.go:399-421): open the controlling
    terminal (go-tty opens /dev/tty, not stdin), raw-mode key loop until
    q/Q under a spinner, then trigger explicit shutdown.

    Improvements over the reference: without a controlling terminal we
    warn and stop streaming rather than panicking (root.go:402-403), and
    the reader polls ``done`` so the thread exits (restoring the
    terminal) when the streams finish on their own. With ``-o
    stdout|both`` the spinner is replaced by one static line
    (``spinner=False``): a repainting spinner would garble the live
    log stream sharing the terminal."""
    loop = asyncio.get_running_loop()

    def read_q() -> None:
        import select
        import termios
        import tty

        with open("/dev/tty", "rb", buffering=0) as t:
            fd = t.fileno()
            old = termios.tcgetattr(fd)
            try:
                tty.setcbreak(fd)
                while not done.is_set():
                    r, _, _ = select.select([fd], [], [], 0.2)
                    if r and t.read(1) in (b"q", b"Q"):
                        return
            finally:
                termios.tcsetattr(fd, termios.TCSADRAIN, old)

    try:
        if spinner:
            async with widgets.Spinner(message):
                await loop.run_in_executor(None, read_q)
        else:
            term.info("%s", message)
            await loop.run_in_executor(None, read_q)
    except Exception as e:  # no controlling tty, termios failure
        term.warning("No controlling terminal for q-to-quit (%s); stopping", e)
    stop.set()


def make_inner_sink_factory(opts: Options):
    """``-o`` routing for where lines land (PARITY.md: additive beyond
    the reference, which only writes files): None = reference FileSink
    behavior; ``stdout`` = stern-style prefixed console stream;
    ``both`` = tee to file and console."""
    if opts.output == "files":
        if opts.format != "text":
            term.warning("--format %s only applies with -o stdout|both; "
                         "ignoring", opts.format)
        return None
    from klogs_tpu.runtime.sink import FileSink
    from klogs_tpu.runtime.stdout import (
        JsonStdoutSink,
        StdoutSink,
        TeeSink,
        compile_highlights,
    )

    if opts.format == "json":
        console = lambda job: JsonStdoutSink(job.pod, job.container)
    else:
        hl = compile_highlights(opts.match, opts.ignore_case)
        console = lambda job: StdoutSink(job.pod, job.container,
                                         highlight=hl)
    if opts.output == "stdout":
        return console
    return lambda job: TeeSink(FileSink(job.path), console(job))


def make_pipeline_for(opts: Options, registry=None):
    """The --match/--exclude filter pipeline (None = unfiltered
    reference path). ``registry`` (an obs.Registry) backs the stats
    when --metrics-port / --stats-json want them scrapable."""
    if not opts.match and not opts.exclude:
        return None
    import re as _re

    from klogs_tpu.filters.sink import make_pipeline

    from klogs_tpu.filters.compiler.parser import RegexSyntaxError
    from klogs_tpu.filters.tpu import DeviceUnavailable

    if (opts.backend == "tpu" and opts.remote is None
            and opts.resolver is None):
        from klogs_tpu.utils.cache import enable_compile_cache

        enable_compile_cache()
    try:
        return make_pipeline(opts.match, opts.backend, remote=opts.remote,
                             ignore_case=opts.ignore_case,
                             exclude=opts.exclude, registry=registry,
                             on_filter_error=opts.on_filter_error,
                             shard_mode=opts.shard_mode,
                             resolver=opts.resolver,
                             kubeconfig=opts.kubeconfig or None)
    except _re.error as e:
        term.fatal("invalid --match/--exclude pattern %r: %s", e.pattern, e)
    except RegexSyntaxError as e:
        # NFA-compiler rejections (unsupported constructs like
        # possessive quantifiers or backrefs) get the same friendly
        # exit as re syntax errors, not a traceback.
        term.fatal("unsupported --match/--exclude pattern: %s", e)
    except ImportError as e:
        term.fatal("--backend %s is unavailable: %s", opts.backend, e)
    except DeviceUnavailable as e:
        term.fatal("%s", e)


def _write_stats_json(path: str, registry, pipeline) -> None:
    """--stats-json: one-shot metrics dump at exit — the scrapeless
    option for batch (non-follow, non-server) runs. The full registry
    snapshot plus the --stats summary numbers, derived from the SAME
    metric objects a /metrics scrape reads."""
    import json

    from klogs_tpu.obs import snapshot
    from klogs_tpu.obs.profiler import refresh_process_metrics

    # Final process-gauge refresh so the dump carries exit-time
    # uptime/RSS, like a last scrape would.
    refresh_process_metrics(registry)
    doc: dict = {"metrics": snapshot(registry)}
    if pipeline is not None:
        s = pipeline.stats
        # p90 added next to the existing keys (additive only — the
        # key layout is a golden consumers parse).
        doc["summary"] = {
            "lines_in": s.lines_in,
            "lines_matched": s.lines_matched,
            "matched_pct": s.matched_pct(),
            "lines_per_sec": s.lines_per_sec(),
            "batches": s.batches,
            "batch_latency_p50_s": s.percentile_latency_s(50),
            "batch_latency_p90_s": s.percentile_latency_s(90),
            "batch_latency_p99_s": s.percentile_latency_s(99),
        }
        if s.has_service_latencies:
            doc["summary"].update({
                "queue_p50_s": s.percentile_queue_s(50),
                "queue_p90_s": s.percentile_queue_s(90),
                "queue_p99_s": s.percentile_queue_s(99),
                "device_p50_s": s.percentile_device_s(50),
                "device_p90_s": s.percentile_device_s(90),
                "device_p99_s": s.percentile_device_s(99),
            })
        if s.device_doc is not None:
            doc["device"] = s.device_doc()
    try:
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
        term.info("Metrics dump written to %s", term.green(path))
    except OSError as e:
        term.error("cannot write --stats-json %s: %s", path, e)


async def run_async(
    opts: Options,
    backend: ClusterBackend | None = None,
    stop: asyncio.Event | None = None,
    select_keys: Iterable[str] | None = None,
) -> int:
    if opts.output != "files":
        # Console modes: log lines own stdout (stern-style); all UI
        # (splash, plan, warnings, prompts) moves to stderr so a piped
        # `klogs -o stdout | grep` sees only log lines and UI text can
        # never interleave into the byte stream.
        import sys as _sys

        term.set_ui_stream(_sys.stderr)
    try:
        return await _run_async_inner(opts, backend, stop, select_keys)
    finally:
        if opts.output != "files":
            term.set_ui_stream(None)


async def _run_async_inner(
    opts: Options,
    backend: ClusterBackend | None = None,
    stop: asyncio.Event | None = None,
    select_keys: Iterable[str] | None = None,
) -> int:
    widgets.splash_screen()
    # Chaos layer: a KLOGS_FAULTS spec scripts the registered fault
    # points for this run (grammar in docs/RESILIENCE.md). Loud when
    # armed — nobody should discover a forgotten fault spec from
    # mystery retries in production.
    from klogs_tpu.resilience import FAULTS, FaultSpecError

    fault_spec = env_read("KLOGS_FAULTS")
    if fault_spec:
        try:
            FAULTS.load_spec(fault_spec)
        except FaultSpecError as e:
            term.fatal("invalid KLOGS_FAULTS: %s", e)
        term.warning("Fault injection ACTIVE (KLOGS_FAULTS=%s)", fault_spec)
    # --source/--backfill: a non-kube Source replaces the cluster
    # backend wholesale — no namespace resolution, no pod selection,
    # no kube client. cli.main validates the spec; this is the
    # library-caller backstop.
    from klogs_tpu.sources import SourceError, make_source

    try:
        source = make_source(opts)
    except SourceError as e:
        term.fatal("%s", e)
    if source is None:
        backend = backend or make_backend(opts)
    from klogs_tpu.obs import trace as _trace

    profiling = False
    if opts.profile:
        # Optional tracing hook (SURVEY.md §5: the reference has none;
        # the TPU build adds jax profiler capture for the filter path).
        try:
            import jax.profiler
        except ImportError as e:
            term.fatal("--profile requires jax: %s", e)
        jax.profiler.start_trace(opts.profile)
        # The per-group spans annotate the capture, on its clock.
        _trace.TRACER.device_clock(True)
        profiling = True
        term.info("Profiling to %s", term.green(opts.profile))
    try:
        container_re = exclude_container_re = None
        log_opts = build_log_options(opts)
        if source is not None:
            namespace = "local"
            pods: list[PodInfo] = []
            await source.start()
            refs = await source.discover()
            jobs = plan_source_jobs(refs, opts.log_path)
            log_files = [j.path for j in jobs]
            mode = "backfilling" if opts.backfill else "streaming"
            term.info("Found %s %s stream(s), %s",
                      term.green(str(len(jobs))), source.kind, mode)
            for j in jobs[:12]:
                term.info("  %s", j.pod)
            if len(jobs) > 12:
                term.info("  … and %d more", len(jobs) - 12)
        else:
            namespace = await resolve_namespace(backend, opts, select_keys)
            pods = await select_pods(backend, namespace, opts, select_keys)
            import re as _re

            # Backstop for library callers; cli.main rejects earlier.
            if opts.container:
                try:
                    container_re = _re.compile(opts.container)
                except _re.error as e:
                    term.fatal("invalid -c/--container pattern %r: %s",
                               opts.container, e)
            if opts.exclude_container:
                try:
                    exclude_container_re = _re.compile(opts.exclude_container)
                except _re.error as e:
                    term.fatal("invalid -E/--exclude-container pattern "
                               "%r: %s", opts.exclude_container, e)
            jobs = plan_jobs(pods, opts.log_path, opts.init_containers,
                             container_re=container_re,
                             exclude_container_re=exclude_container_re)
            log_files = [j.path for j in jobs]
            if (container_re is not None
                    or exclude_container_re is not None) \
                    and pods and not jobs:
                # A filter miss must be distinguishable from an empty
                # cluster (≙ the empty-label-result error that continues,
                # cmd/root.go:392-394).
                term.error("No containers left after -c/-E filtering in "
                           "%d selected pod(s)", len(pods))
            if jobs:
                if container_re is not None \
                        or exclude_container_re is not None:
                    # With -c/-E active, pods whose containers were all
                    # filtered out contribute no streams — counting or
                    # rendering them would misstate the plan.
                    streaming = {j.pod for j in jobs}
                    print_plan([p for p in pods if p.name in streaming],
                               jobs)
                else:
                    print_plan(pods, jobs)
        if opts.timestamps and (opts.match or opts.exclude):
            # grep-parity semantics: the server-side stamp is part of
            # the line the filter sees (as it would be for kubectl
            # --timestamps | grep). Say so once — a ^-anchored pattern
            # silently matching nothing is a support ticket.
            term.info("note: --timestamps prefixes are part of the line "
                      "--match/--exclude see (anchor accordingly)")

        # Observability (opt-in): one registry backs the pipeline
        # stats, the fan-out instrumentation, and — with
        # --metrics-port — a live /metrics + /healthz HTTP sidecar.
        # Per-RUN (not the process-global obs.REGISTRY): a second
        # run_async in the same process must not inherit the first
        # run's counters into its summary/dump.
        obs_registry = None
        metrics_srv = None
        if opts.metrics_port is not None or opts.stats_json is not None:
            from klogs_tpu import obs

            obs_registry = obs.Registry()
            obs.register_all(obs_registry)
            from klogs_tpu.version import BUILD_VERSION as _ver

            obs_registry.family("klogs_build_info").labels(
                version=_ver).set(1)
        # Tracing (opt-in): --trace-json turns head sampling fully on
        # (unless KLOGS_TRACE_SAMPLE pins a rate) and appends every
        # finished span to the file; with KLOGS_TRACE_SAMPLE alone the
        # spans still feed /traces (--metrics-port sidecar) and the
        # degrade flight recorder. Trace counters ride the run
        # registry when one exists.
        if opts.trace_json is not None:
            _trace.TRACER.enable_default()
            _trace.TRACER.set_json_path(opts.trace_json)
        if obs_registry is not None:
            _trace.TRACER.bind_registry(obs_registry)
            _trace.RECORDER.bind_registry(obs_registry)
        # Continuous utilization profiling (opt-in): --profile-json
        # appends one snapshot per tick; KLOGS_PROFILE_SAMPLE alone
        # also enables it (feeding /profile on --metrics-port without
        # a file sink). KLOGS_PROFILE_SAMPLE=0 is the kill switch even
        # against the explicit flag.
        from klogs_tpu.obs.profiler import PROFILER

        PROFILER.maybe_enable()
        if opts.profile_json is not None and PROFILER.enable():
            PROFILER.set_json_path(opts.profile_json)
        if PROFILER.enabled and obs_registry is not None:
            PROFILER.bind_registry(obs_registry)
        prof_stop: asyncio.Event | None = None
        prof_task: asyncio.Task | None = None
        tune_stop: asyncio.Event | None = None
        tune_task: asyncio.Task | None = None
        # Resilience observability rides the same per-run registry:
        # fault firings, kube retry attempts (the backend exists before
        # the registry, hence the late bind), breaker state (bound in
        # the remote client via make_pipeline's registry).
        FAULTS.bind_registry(obs_registry)
        backend_bind = getattr(backend, "bind_registry", None)
        if backend_bind is not None and obs_registry is not None:
            backend_bind(obs_registry)
        if source is not None and obs_registry is not None:
            source.bind_registry(obs_registry)

        pipeline = make_pipeline_for(opts, registry=obs_registry)
        inner_factory = make_inner_sink_factory(opts)
        loop_prof = None
        try:
            if obs_registry is not None:
                # The event loop's own accounting: stage samples and
                # thread CPU, read from outside the loop.
                from klogs_tpu.obs.loopprof import LoopProfiler

                loop_prof = LoopProfiler(obs_registry).start()
            if PROFILER.enabled:
                # Started inside this try so the finally below always
                # reaps the ticker (a fatal during pipeline start must
                # not leak the task into loop teardown).
                prof_stop = asyncio.Event()
                prof_task = asyncio.create_task(
                    PROFILER.run_ticker(prof_stop))
            if pipeline is not None:
                await pipeline.start()  # remote: verify patterns up front
                pipeline.inner_factory = inner_factory
                # KLOGS_TUNE=auto: the adaptive operating-point
                # controller (ops/tune.py) drives the coalescer/
                # in-flight knobs from live /profile signals. Off by
                # default — nothing is even constructed, so fixed-flag
                # behavior stays byte-identical.
                from klogs_tpu.ops.tune import maybe_controller

                try:
                    ctrl = maybe_controller(pipeline.service,
                                            registry=obs_registry)
                except ValueError as e:
                    term.fatal("%s", e)
                if ctrl is not None:
                    if not PROFILER.enabled and not PROFILER.enable():
                        term.warning(
                            "KLOGS_TUNE=auto needs profiler signals but "
                            "KLOGS_PROFILE_SAMPLE=0 disables them; the "
                            "controller will hold the fixed flags")
                    elif prof_task is None:
                        # Tuning enabled the profiler itself: it still
                        # needs the ticker for live samples.
                        if obs_registry is not None:
                            PROFILER.bind_registry(obs_registry)
                        prof_stop = asyncio.Event()
                        prof_task = asyncio.create_task(
                            PROFILER.run_ticker(prof_stop))
                    tune_stop = asyncio.Event()
                    tune_task = asyncio.create_task(ctrl.run(tune_stop))
            runner = FanoutRunner(
                backend, namespace, log_opts,
                sink_factory=(pipeline.sink_factory if pipeline
                              else inner_factory),
                create_files=opts.output != "stdout",
                registry=obs_registry,
                source=source,
            )
            if opts.metrics_port is not None:
                from klogs_tpu import obs

                health = obs.Health()
                # The collector has no cold-start compile gate of its
                # own (the engine warms on first batch; a --remote
                # engine warms in filterd): it is ready once streaming
                # is set up.
                health.set_ready()
                health.add_live_check("runner",
                                      lambda: not runner._stopping)
                metrics_srv = obs.MetricsHTTPServer(
                    obs_registry, health=health, port=opts.metrics_port)
                try:
                    bound_metrics = await metrics_srv.start()
                except OSError as e:
                    # Friendly one-liner like every other bad-flag
                    # path, not a traceback out of asyncio.run.
                    term.fatal("cannot bind --metrics-port %s: %s",
                               opts.metrics_port, e)
                term.info("Metrics on %s",
                          term.green(f"http://127.0.0.1:{bound_metrics}"
                                     "/metrics"))
            # --watch-new: stern-style dynamic discovery. Only a
            # NON-interactive selection can be re-planned (the user's
            # one-off multiselect cannot); re-run the same -a/-l
            # selection and let the runner diff.
            plan_new = None
            if source is not None:
                if opts.follow:
                    # Sources re-discover for free (glob expansion, new
                    # socket connections): follow mode always watches.
                    _src = source

                    async def plan_new() -> list[StreamJob]:
                        return plan_source_jobs(await _src.discover(),
                                                opts.log_path)
                if opts.watch_new and not opts.follow:
                    term.warning("--watch-new only applies with -f; "
                                 "ignoring")
            elif opts.watch_new and opts.follow:
                if opts.all_pods or opts.labels:
                    async def plan_new() -> list[StreamJob]:
                        pods = await select_noninteractive(
                            backend, namespace, opts, quiet=True)
                        return plan_jobs(
                            pods, opts.log_path, opts.init_containers,
                            container_re=container_re,
                            exclude_container_re=exclude_container_re)
                else:
                    term.warning(
                        "--watch-new needs -a or -l (an interactive pod "
                        "pick cannot be re-run); ignoring")
            elif opts.watch_new:
                term.warning("--watch-new only applies with -f; ignoring")
            # With discovery active, an EMPTY initial selection still
            # waits (the point of starting the watch before deploying).
            interrupted = False
            if opts.follow and (jobs or plan_new is not None):
                own_stop = stop is None
                if own_stop:
                    stop = asyncio.Event()
                # The flusher gets the stop event so an
                # --on-filter-error=abort escalation from an idle
                # stream's stale flush tears the run down instead of
                # dying silently in a background task.
                flusher = (
                    asyncio.create_task(pipeline.run_deadline_flusher(stop))
                    if pipeline is not None else None
                )
                sigint_installed = False
                if own_stop:
                    # Ctrl-C parity+: the reference exits with streams
                    # still running and buffers unflushed (SURVEY §3.3
                    # quirk class). First SIGINT = graceful stop (same
                    # teardown as q: close streams, flush every sink,
                    # render the size table) but still exit 130 like
                    # kubectl; second SIGINT = give up immediately.
                    loop = asyncio.get_running_loop()

                    def on_sigint() -> None:
                        nonlocal interrupted
                        if interrupted:
                            # Force quit must NOT re-enter the event
                            # loop (a raised KeyboardInterrupt funnels
                            # through asyncio.run's cleanup, which can
                            # block on the very await that wedged the
                            # graceful path — e.g. backend.close on a
                            # dead connection). Die by signal, like the
                            # default handler would.
                            signal.signal(signal.SIGINT, signal.SIG_DFL)
                            os.kill(os.getpid(), signal.SIGINT)
                            return
                        interrupted = True
                        term.warning(
                            "Interrupt: stopping streams (Ctrl-C again "
                            "to force quit)")
                        stop.set()

                    try:
                        loop.add_signal_handler(signal.SIGINT, on_sigint)
                        sigint_installed = True
                    except (NotImplementedError, RuntimeError):
                        pass  # non-main thread / platform without support
                    watcher_done = threading.Event()
                    if opts.output == "stdout":
                        quit_msg = (f"Press {term.green('q')} to stop "
                                    "streaming logs")
                    else:
                        quit_msg = (f"Press {term.green('q')} to stop "
                                    "streaming logs in "
                                    f"{term.green(opts.log_path)}")
                    watcher = asyncio.create_task(
                        _watch_for_quit(stop, quit_msg, watcher_done,
                                        spinner=opts.output == "files")
                    )
                else:
                    watcher = watcher_done = None
                try:
                    interval = 5.0
                    if plan_new is not None:  # knob is irrelevant otherwise
                        raw = env_read("KLOGS_WATCH_INTERVAL_S", "5")
                        try:
                            # Floor of 0.2s: a zero/negative value would
                            # busy-poll the apiserver all session.
                            interval = max(0.2, float(raw))
                        except ValueError:
                            term.fatal(
                                "KLOGS_WATCH_INTERVAL_S must be a number, "
                                "got %r", raw)
                    results = await runner.run(
                        jobs, stop=stop, plan_new=plan_new,
                        discover_interval_s=interval)
                    # Late-discovered streams must appear in the size
                    # table too.
                    log_files = [r.job.path for r in results]
                finally:
                    if sigint_installed:
                        asyncio.get_running_loop().remove_signal_handler(
                            signal.SIGINT)
                    if watcher is not None:
                        # Unblock the /dev/tty reader thread so the
                        # terminal is restored and the process can exit.
                        watcher_done.set()
                        await watcher
                    if flusher is not None:
                        flusher.cancel()
                        try:
                            await flusher
                        except asyncio.CancelledError:
                            pass
            else:
                await runner.run(jobs)
                if opts.backfill:
                    # Run-to-completion contract: always account for
                    # what was matched vs shed, --stats or not.
                    _print_backfill_summary(pipeline)

            if opts.output != "stdout":
                # No files exist in stdout-only mode; the size table
                # (cmd/root.go:279-309) only describes written files.
                print_log_size(log_files, opts.log_path)
            if pipeline is not None and opts.stats:
                pipeline.print_summary()
            if opts.stats_json is not None:
                if pipeline is not None:
                    # Sharded remote tier: pull each endpoint's final
                    # capacity advertisement so the dump carries the
                    # fleet's offered/admitted totals (a short batch
                    # run ends before the prober's refresh cadence).
                    refresh = getattr(pipeline.service,
                                      "refresh_capacity", None)
                    if refresh is not None:
                        await refresh()
                _write_stats_json(opts.stats_json, obs_registry, pipeline)
            # Interrupted-but-graceful: everything is flushed and
            # reported, yet scripts still see the conventional 130.
            return 130 if interrupted else 0
        finally:
            if loop_prof is not None:
                loop_prof.stop()
            # Close inside the loop even on error/Ctrl-C paths — an
            # unawaited grpc channel or in-flight batch task would be
            # destroyed pending at loop teardown.
            if tune_task is not None:
                if tune_stop is not None:
                    tune_stop.set()
                try:
                    await tune_task
                except (asyncio.CancelledError, Exception):  # noqa: BLE001
                    pass
            if prof_task is not None:
                # run_ticker's final tick completes the JSONL stream
                # before the task returns.
                if prof_stop is not None:
                    prof_stop.set()
                try:
                    await prof_task
                except (asyncio.CancelledError, Exception):  # noqa: BLE001
                    pass
                PROFILER.set_json_path(None)
            if metrics_srv is not None:
                await metrics_srv.stop()
            if pipeline is not None:
                await pipeline.aclose()
            # A degrade trigger armed near the end of the run may have
            # no further root span to ride — write it now, and stop
            # appending spans to this run's --trace-json file.
            _trace.RECORDER.flush()
            if opts.trace_json is not None:
                _trace.TRACER.set_json_path(None)
    finally:
        if profiling:
            import jax.profiler

            _trace.TRACER.device_clock(False)
            try:
                jax.profiler.stop_trace()
            except Exception as e:
                # Trace serialization failure must not skip backend
                # cleanup or mask an in-flight exception.
                term.warning("Failed to write profiler trace: %s", e)
        if backend is not None:
            await backend.close()
        if source is not None:
            await source.close()


def run(opts: Options) -> int:
    return asyncio.run(run_async(opts))

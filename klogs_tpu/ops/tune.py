"""Kernel autotuning: pick (tile_b, interleave) by measurement.

The grouped kernel's best configuration depends on hardware details the
code cannot see (VMEM per core, MXU/VPU overlap behavior, dispatch
latency of the attach), so it is measured, not guessed: a short
pipelined sweep on the live device, cached per (automaton shape, batch
geometry, device kind) in ``~/.cache/klogs_tpu/tune.json``.

Hooked in two places:
- NFAEngineFilter reads KLOGS_TPU_TILE / KLOGS_TPU_INTERLEAVE /
  KLOGS_TPU_MASK_BLOCK / KLOGS_TPU_FUSED_GROUPS env overrides, else
  measured defaults. (The on-disk cache written here is consumed by
  operators/bench runs that call tune_grouped or load_cached
  explicitly; the hot path stays env-driven so a stale cache can never
  silently change production behavior.)
- bench.py / operators run ``tune_grouped`` explicitly (KLOGS_BENCH_TUNE=1).
"""

import asyncio
import json
import os
import time
from typing import Any, Callable

CANDIDATE_TILES = (1024, 2048, 4096, 8192)
CANDIDATE_INTERLEAVE = (1, 2)
# Chain restructurings swept alongside (tile, interleave): mask_block=K
# precomputes K step masks off the serial chain; fused runs all groups
# in one grid cell with a shared one-hot. Both parity-tested; whether
# either wins is hardware-empirical (pallas_nfa.py docstrings).
CANDIDATE_VARIANTS = (
    {},  # plain
    {"mask_block": 4},
    {"mask_block": 8},
    {"fused": True},
)


def _cache_path() -> str:
    base = os.environ.get("XDG_CACHE_HOME",
                          os.path.join(os.path.expanduser("~"), ".cache"))
    return os.path.join(base, "klogs_tpu", "tune.json")


def _key(dp: Any, batch_shape: Any, device_kind: str) -> str:
    G = dp.follow.shape[0]
    return f"{device_kind}|G{G}|S{dp.n_states}|C{dp.n_classes}|B{batch_shape[0]}x{batch_shape[1]}"


def load_cached(dp: Any, batch_shape: Any,
                device_kind: str) -> "dict | None":
    try:
        with open(_cache_path()) as f:
            return json.load(f).get(_key(dp, batch_shape, device_kind))
    except (OSError, ValueError):
        return None


def _store(dp: Any, batch_shape: Any, device_kind: str,
           cfg: dict) -> None:
    path = _cache_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    try:
        with open(path) as f:
            all_cfg = json.load(f)
    except (OSError, ValueError):
        all_cfg = {}
    all_cfg[_key(dp, batch_shape, device_kind)] = cfg
    with open(path, "w") as f:
        json.dump(all_cfg, f, indent=1)


def tune_grouped(dp: Any, live: int, acc: int, batch: Any, lengths: Any,
                 repeats: int = 3, n_flight: int = 6,
                 runner: "Callable[..., float] | None" = None,
                 quiet: bool = False, cls: Any = None,
                 registry: Any = None) -> dict:
    """Sweep the candidate grid on the live device; returns the winning
    {"tile_b", "interleave", "lines_per_s"} and caches it.

    ``runner(tile_b, interleave) -> lines_per_s`` is injectable for
    tests; the default measures the grouped kernel pipelined
    (N dispatches in flight, one sync — per-call blocking would measure
    the attach round trip, not the kernel). When ``cls`` (host-classified
    [B, T] i8 ids) is given, the hot-path entry match_cls_grouped_pallas
    is swept instead of the byte-consuming one.
    """
    import jax

    from klogs_tpu.ops.pallas_nfa import (
        match_batch_grouped_pallas,
        match_cls_grouped_pallas,
    )

    B = batch.shape[0] if cls is None else cls.shape[0]

    def default_runner(tile_b: int, interleave: int,
                       **variant: Any) -> float:
        # Non-divisor tiles are fine: the kernel wrapper pads the batch
        # up to a tile multiple internally.
        if cls is not None:
            run = lambda: match_cls_grouped_pallas(
                dp, live, acc, cls,
                tile_b=tile_b, interleave=interleave, **variant,
            )
        else:
            run = lambda: match_batch_grouped_pallas(
                dp, live, acc, batch, lengths,
                tile_b=tile_b, interleave=interleave, **variant,
            )
        run().block_until_ready()  # compile
        best = 0.0
        for _ in range(repeats):
            t0 = time.perf_counter()
            outs = [run() for _ in range(n_flight)]
            outs[-1].block_until_ready()
            best = max(best, n_flight * B / (time.perf_counter() - t0))
        return best

    runner = runner or default_runner
    # Injected test runners may predate the variant kwargs; detect by
    # signature instead of catching TypeError (which JAX also raises
    # for real kernel bugs — swallowing those would silently "measure"
    # only the plain config).
    import inspect

    params = inspect.signature(runner).parameters.values()
    runner_takes_variants = any(
        p.kind == inspect.Parameter.VAR_KEYWORD for p in params)
    results = []
    seen = set()
    for tile in (min(t, B) for t in CANDIDATE_TILES):
        for il in CANDIDATE_INTERLEAVE:
            if tile % il or tile // il < 8:
                continue
            for variant in CANDIDATE_VARIANTS:
                if variant and il != 1:
                    continue  # restructurings are interleave-exclusive
                if variant and not runner_takes_variants:
                    continue
                key = (tile, il, tuple(sorted(variant.items())))
                if key in seen:
                    continue
                seen.add(key)
                desc = " ".join(f"{k}={v}" for k, v in variant.items())
                try:
                    lps = runner(tile, il, **variant)
                except Exception as e:  # VMEM overflow / compile failure
                    if not quiet:
                        print(f"tune: tile={tile} interleave={il} {desc} "
                              f"failed: {str(e)[:80]}")
                    continue
                if lps > 0:
                    results.append({"tile_b": tile, "interleave": il,
                                    **variant,
                                    "lines_per_s": round(lps, 1)})
                    if not quiet:
                        print(f"tune: tile={tile} interleave={il} {desc}"
                              f" -> {lps:,.0f} lines/s")
    if not results:
        raise RuntimeError("kernel tuning failed for every candidate config")
    best = max(results, key=lambda r: r["lines_per_s"])
    # Sweep telemetry: into the caller's registry when one is threaded
    # through (a process serving a sidecar should scrape its own tune
    # events), else the process-global default for standalone
    # bench/operator runs.
    if registry is None:
        from klogs_tpu.obs import REGISTRY as registry

    registry.family("klogs_engine_tune_runs_total").inc()
    registry.family("klogs_engine_tune_best_lines_per_second").set(
        best["lines_per_s"])
    try:
        import jax

        device_kind = jax.devices()[0].device_kind
    except Exception:
        device_kind = "unknown"
    _store(dp, batch.shape if cls is None else cls.shape, device_kind, best)
    return best


def env_overrides() -> dict:
    """KLOGS_TPU_TILE / KLOGS_TPU_INTERLEAVE / KLOGS_TPU_FUSED_GROUPS /
    KLOGS_TPU_MASK_BLOCK, when set. Callers pass the result straight
    into match_cls_grouped_pallas / match_batch_grouped_pallas kwargs."""
    from klogs_tpu.utils.env import read as env_read

    out = {}
    if env_read("KLOGS_TPU_TILE"):
        out["tile_b"] = int(env_read("KLOGS_TPU_TILE"))
    if env_read("KLOGS_TPU_INTERLEAVE"):
        out["interleave"] = int(env_read("KLOGS_TPU_INTERLEAVE"))
    if env_read("KLOGS_TPU_FUSED_GROUPS") == "1":
        out["fused"] = True
    if env_read("KLOGS_TPU_MASK_BLOCK"):
        out["mask_block"] = int(env_read("KLOGS_TPU_MASK_BLOCK"))
    return out


# Hardware default chain: mask_block=4 pulls each block's four step
# masks (one-hot + char-mask matmul, state-independent work) off the
# serial chain. A pre-PR-3 kernel-variant A/B on v5e favoured it over
# the plain chain; mask_block=8/16 failed to compile under Mosaic there.
# Not measured on today's code.
HW_DEFAULT_MASK_BLOCK = 4


def chain_selection(on_hardware: bool,
                    allow_fused: bool = True) -> tuple[dict, bool, bool]:
    """THE chain-variant policy — every consumer (single-chip engine,
    mesh per-shard, bench) derives its kernel kwargs here so the rules
    live in one place. Returns ``(kw, chain_defaulted, dropped_fused)``:

    - ``kw``: env_overrides() plus the measured hardware default — on a
      real TPU backend, when the env picks no conflicting chain variant,
      mask_block=HW_DEFAULT_MASK_BLOCK. KLOGS_TPU_MASK_BLOCK=1 forces
      the plain chain; KLOGS_TPU_INTERLEAVE=1 restates the interleave
      default and does NOT suppress the mask_block default (only
      interleave>1 actually conflicts — pallas rejects the combo
      loudly). Interpret/CPU paths keep the plain chain (no hardware
      pipeline to win on, and hermetic tests should exercise the same
      default they can verify).
    - ``chain_defaulted``: the mask_block came from the DEFAULT, not the
      env — eligible for degrade-to-plain on compile/exec failure. An
      env-forced variant is never defaulted: the operator asked to
      measure exactly that kernel, so failures stay loud.
    - ``dropped_fused``: allow_fused=False (mesh per-shard compute,
      where one body backs both the plain and gated builds and fused
      has no gated sibling) removed an env-requested fused=True; the
      caller must WARN (silently measuring a different kernel corrupts
      pick-by-measurement). With fused dropped the chain is unpicked
      again, so the default re-applies."""
    env = env_overrides()
    kw = dict(env)
    dropped_fused = bool(not allow_fused and kw.pop("fused", False))
    picked_variant = ("mask_block" in kw or kw.get("fused")
                      or kw.get("interleave", 1) != 1)
    if on_hardware and not picked_variant:
        kw["mask_block"] = HW_DEFAULT_MASK_BLOCK
    chain_defaulted = (kw.get("mask_block", 1) > 1
                       and "mask_block" not in env)
    return kw, chain_defaulted, dropped_fused


def kernel_kwargs(on_hardware: bool) -> dict:
    """chain_selection()'s kwargs alone, for callers that manage their
    own variant sweep (bench tools)."""
    return chain_selection(on_hardware)[0]


# -- adaptive operating point (collector-side controller) --------------
#
# The kernel autotuner above picks a KERNEL config offline; the
# controller below adjusts the PIPELINE's operating point online —
# coalescer group sizing and device in-flight depth — from the live
# /profile signals (queue depth, in-flight occupancy, bottleneck).
# It is deliberately conservative: bounded multiplicative steps,
# consecutive-tick hysteresis with a cooldown after every move, and
# hard floor/ceiling anchored to OPERATING_SURFACE. KLOGS_TUNE=off
# (the default) means the controller is never constructed —
# fixed-flag behavior, byte-identical.

DEFAULT_TUNE_INTERVAL_S = 5.0
DEFAULT_TUNE_STEP = 0.5  # fractional step: up = x(1+step), down = /(1+step)
_TUNE_UP_AFTER = 2    # consecutive pressure ticks before stepping up
_TUNE_DOWN_AFTER = 4  # consecutive idle ticks before stepping down
                      # (down > up: shedding capacity needs more proof)
_TUNE_COOLDOWN = 2    # quiet ticks after ANY step — the pipeline must
                      # show the new point's behavior before we judge it


def tune_mode() -> str:
    """KLOGS_TUNE: ``off`` (default; fixed flags, no controller built)
    or ``auto``. Anything else fails loudly — a typoed mode silently
    running fixed flags would be the worst kind of knob."""
    from klogs_tpu.utils.env import read as env_read

    raw = env_read("KLOGS_TUNE")
    mode = (raw or "off").strip().lower()
    if mode not in ("off", "auto"):
        raise ValueError(
            f"KLOGS_TUNE must be 'off' or 'auto', got {raw!r}")
    return mode


# The controller's roaming range: the search range of the old
# operating-point sweep (batches of 262,144 to 1,048,576 lines, 8 to 64
# dispatches in flight). It bounds where the controller may look; it
# is not a measured rate, and nothing in it was measured on today's
# code.
COALESCE_LINES_RANGE = (262_144, 1_048_576)
MAX_IN_FLIGHT_RANGE = (8, 64)
OPERATING_SURFACE = {"coalesce_lines": COALESCE_LINES_RANGE,
                     "max_in_flight": MAX_IN_FLIGHT_RANGE}


class AdaptiveController:
    """Close the loop between /profile and the pipeline's knobs.

    Decision policy per tick (one ``step_once`` on the live profile
    doc):

    - *pressure* — in-flight slots saturated with callers queued, or
      the coalescer backlog exceeding a full group: after
      ``_TUNE_UP_AFTER`` consecutive pressure ticks, step the binding
      parameter UP one bounded multiplicative step.
    - *idle* — in-flight occupancy under a quarter of depth with an
      empty coalescer: after ``_TUNE_DOWN_AFTER`` consecutive idle
      ticks, step back DOWN toward the flag values (latency recovery).
    - anything else resets both streaks; every applied step starts a
      ``_TUNE_COOLDOWN``-tick quiet period. Together these are the
      hysteresis: a signal oscillating tick-to-tick moves nothing.

    Bounds per parameter are ``[min(initial, surface_min),
    max(initial, surface_max)]`` from ``OPERATING_SURFACE`` — the
    controller can roam that search range and can always return to
    the operator's flags, but never leaves both.

    ``service`` duck-types ``coalesce_lines`` / ``max_in_flight``
    read properties and ``apply_tuning(coalesce_lines=, max_in_flight=)``
    (filters/async_service.py). Mutated fields (streaks, cooldown,
    current values) are only touched from ``run``'s single task —
    loop-confined, no lock.
    """

    PARAMS = ("coalesce_lines", "max_in_flight")

    def __init__(self, service: Any, *,
                 registry: Any = None,
                 profile_fn: "Callable[[], dict] | None" = None,
                 interval_s: "float | None" = None,
                 step: "float | None" = None,
                 surface: "dict[str, tuple[int, int]] | None" = None
                 ) -> None:
        from klogs_tpu.utils.env import positive_float

        self._service = service
        if profile_fn is None:
            from klogs_tpu.obs.profiler import PROFILER

            profile_fn = PROFILER.profile_doc
        self._profile_fn = profile_fn
        self._interval_s = (interval_s if interval_s is not None
                            else positive_float("KLOGS_TUNE_INTERVAL_S",
                                                DEFAULT_TUNE_INTERVAL_S))
        self._step = (step if step is not None
                      else positive_float("KLOGS_TUNE_STEP",
                                          DEFAULT_TUNE_STEP))
        self.values: "dict[str, int]" = {
            "coalesce_lines": int(service.coalesce_lines),
            "max_in_flight": int(service.max_in_flight),
        }
        surf = OPERATING_SURFACE if surface is None else surface
        self.bounds: "dict[str, tuple[int, int]]" = {}
        for param, initial in self.values.items():
            lo, hi = surf.get(param, (initial, initial))
            self.bounds[param] = (min(initial, lo), max(initial, hi))
        self._press = 0
        self._idle = 0
        self._cooldown = 0
        self.steps_applied = 0  # for tests / soak assertions
        self._m_steps: Any = None
        self._m_value: Any = None
        if registry is not None:
            self._m_steps = registry.family("klogs_tune_steps_total")
            self._m_value = registry.family("klogs_tune_value")
            for param, value in self.values.items():
                self._m_value.labels(param=param).set(value)

    async def _apply(self, param: str, new: int,
                     direction: str) -> None:
        self.values[param] = new
        self._service.apply_tuning(**{param: new})
        self.steps_applied += 1
        self._press = 0
        self._idle = 0
        self._cooldown = _TUNE_COOLDOWN
        if self._m_steps is not None:
            self._m_steps.labels(param=param, direction=direction).inc()
        if self._m_value is not None:
            self._m_value.labels(param=param).set(new)
        from klogs_tpu.ui import term

        term.info("tune: %s %s -> %d (operating-point controller)",
                  param, direction, new)

    async def _step_up(self, param: str) -> bool:
        cur = self.values[param]
        hi = self.bounds[param][1]
        if cur >= hi:
            return False
        new = min(hi, max(cur + 1, int(cur * (1.0 + self._step))))
        await self._apply(param, new, "up")
        return True

    async def _step_down(self, param: str) -> bool:
        cur = self.values[param]
        lo = self.bounds[param][0]
        if cur <= lo:
            return False
        new = max(lo, min(cur - 1, int(cur / (1.0 + self._step))))
        await self._apply(param, new, "down")
        return True

    async def step_once(self, doc: dict
                        ) -> "tuple[str, str] | None":
        """One control decision from one profile snapshot. Returns the
        (param, direction) applied, or None (held). A pure state
        machine over the doc — directly testable without a pipeline —
        kept async so every mutation stays event-loop-confined (the
        lock-discipline contract for controller state)."""
        if not doc.get("enabled"):
            return None  # no signals, no opinion — hold the point
        samples = doc.get("samples") or {}

        def sample(name: str) -> float:
            v = samples.get(name)
            return float(v) if isinstance(v, (int, float)) else 0.0

        depth = sample("coalescer.queue_depth")
        pending = sample("coalescer.pending_lines")
        used = sample("device.in_flight_used")
        if self._cooldown > 0:
            self._cooldown -= 1
            return None
        flight = self.values["max_in_flight"]
        group = self.values["coalesce_lines"]
        # Pressure: the dispatch pipe is the wall (all slots busy AND
        # callers queued behind it), or groups overflow before the
        # kick (a full group's worth pending — bigger groups amortize
        # the per-dispatch fixed cost).
        press_flight = used >= flight - 0.5 and depth > 0
        press_group = pending >= group
        idle = (used <= max(1.0, 0.25 * flight)
                and depth <= 0 and pending < 0.25 * group)
        if press_flight or press_group:
            self._press += 1
            self._idle = 0
        elif idle:
            self._idle += 1
            self._press = 0
        else:
            self._press = 0
            self._idle = 0
        if self._press >= _TUNE_UP_AFTER:
            if press_flight and await self._step_up("max_in_flight"):
                return ("max_in_flight", "up")
            if press_group and await self._step_up("coalesce_lines"):
                return ("coalesce_lines", "up")
            self._press = 0  # pinned at the ceiling: stop counting
            return None
        if self._idle >= _TUNE_DOWN_AFTER:
            # Unwind depth first (memory + queueing latency), group
            # size second (per-batch latency).
            if await self._step_down("max_in_flight"):
                return ("max_in_flight", "down")
            if await self._step_down("coalesce_lines"):
                return ("coalesce_lines", "down")
            self._idle = 0  # already at the floor
            return None
        return None

    async def run(self, stop: "asyncio.Event") -> None:
        """Tick loop (stop-aware poller idiom). The ``tune.step`` fault
        point wraps each decision: an armed fault skips that tick and
        MUST NOT kill the loop — a chaos script proves the pipeline
        keeps flowing at the held operating point."""
        from klogs_tpu.resilience import FAULTS
        from klogs_tpu.ui import term

        while not stop.is_set():
            try:
                await asyncio.wait_for(stop.wait(),
                                       timeout=self._interval_s)
            except asyncio.TimeoutError:
                pass
            if stop.is_set():
                return
            try:
                if FAULTS.active:
                    await FAULTS.fire("tune.step")
                await self.step_once(self._profile_fn())
            except asyncio.CancelledError:
                raise
            except Exception as e:  # noqa: BLE001
                # InjectedFault or a profile/apply surprise: hold the
                # current point, keep the loop alive.
                term.warning("tune: skipped a control tick (%s)", e)


def maybe_controller(service: Any, registry: Any = None
                     ) -> "AdaptiveController | None":
    """The app-side gate: None when KLOGS_TUNE=off (default — nothing
    is constructed, fixed-flag behavior byte-identical), None when the
    pipeline's filter service has no tuning surface (CPU batch path,
    remote tier), else a ready-to-run controller. Bad KLOGS_TUNE*
    values raise ValueError for the caller's friendly-fatal path."""
    if tune_mode() == "off":
        return None
    if getattr(service, "apply_tuning", None) is None:
        return None
    return AdaptiveController(service, registry=registry)

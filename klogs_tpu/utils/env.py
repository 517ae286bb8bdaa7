"""Shared access point for every ``KLOGS_*`` environment knob.

The fleet grew ~40 env knobs across five subsystems, and the hardest
review findings of PRs 5-10 were knob-parsing bugs: ``KLOGS_HEDGE_S=
nan`` flowing into ``asyncio.wait(timeout=nan)``, a negative DFA cache
cap evicting every table on every write, zero timeouts DEADLINE-
EXCEEDing every RPC with an error that never named the variable. The
fix each time was the same — validate at the read site, loudly, naming
the knob — so the read sites now share ONE module. ``tools/analysis``'s
``env-discipline`` pass enforces the funnel: a raw ``os.environ[...]``
/ ``os.getenv`` read of a ``KLOGS_*`` key anywhere else in the tree is
a finding, and every knob read here must appear in the README env
table (both directions).

Three validation dialects exist on purpose (callers pick per knob):

- **raise** (:func:`positive_float`, :func:`nonneg_float`): a bad
  value crashes naming the variable. For knobs where silently running
  with a default hides real regressions (timeouts, degrade
  thresholds).
- **warn-and-default** (:func:`warn_positive_int`,
  :func:`warn_nonneg_float`): a bad value prints one stderr notice and
  keeps the default. For server-side knobs where a typo must not kill
  a multi-tenant daemon at import time.
- **passthrough** (:func:`read` / :func:`is_set`): string knobs (file
  paths, mode selectors, fault scripts) whose validation is inherently
  site-specific; the site keeps its logic but the read still flows
  through here so the discipline pass can see it.
"""

from __future__ import annotations

import math
import os


def read(name: str, default: "str | None" = None) -> "str | None":
    """THE raw environment read. Every KLOGS_* knob in the tree flows
    through this module; see the module docstring for why."""
    return os.environ.get(name, default)


def is_set(name: str) -> bool:
    """Whether the knob is present at all (some knobs distinguish
    'unset' from any value — e.g. KLOGS_TRACE_SAMPLE=0 vs absent)."""
    return os.environ.get(name) is not None


def jax_cpu_requested() -> bool:
    """JAX_PLATFORMS names the CPU and nothing else: how the tests and
    the CPU rehearsals say that a CPU run is what they mean. Anything
    else asks for the accelerator, which --backend tpu then requires."""
    raw = os.environ.get("JAX_PLATFORMS", "")
    return {p.strip() for p in raw.split(",") if p.strip()} == {"cpu"}


def positive_float(name: str, default: float,
                   exc: type = ValueError) -> float:
    """Strict positive finite float; zero/negative/nan/inf/garbage
    raises ``exc`` naming the variable (a bad knob must not surface as
    a mystery timeout downstream). nan compares False against
    everything and inf is no deadline at all — both are garbage for a
    knob documented as a positive number of seconds."""
    raw = read(name)
    if raw is None:
        return default
    try:
        value = float(raw)
        if not math.isfinite(value) or value <= 0:
            raise ValueError("must be positive and finite")
    except ValueError as e:
        raise exc(
            f"{name} must be a positive number, got {raw!r}") from e
    return value


def nonneg_float(name: str, default: float) -> float:
    """Strict non-negative finite float; malformed values raise
    (silent misconfiguration of a degrade knob hides real
    regressions)."""
    raw = read(name)
    if raw is None:
        return default
    try:
        v = float(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r}: expected a number") from None
    if not math.isfinite(v) or v < 0:
        raise ValueError(f"{name}={raw!r}: expected a finite value >= 0")
    return v


def warn_positive_int(name: str, default: int) -> int:
    """Positive-int knob; malformed values warn and fall back rather
    than crashing module import with a bare ValueError."""
    raw = read(name)
    if raw is None:
        return default
    try:
        val = int(raw)
    except ValueError:
        val = 0
    if val < 1:
        import sys

        print(f"klogs: ignoring invalid {name}={raw!r} (want a positive "
              f"integer); using {default}", file=sys.stderr)
        return default
    return val


def warn_nonneg_float(name: str, default: float) -> float:
    """Non-negative float knob (0 commonly means 'disabled'); a bad
    value degrades to the default loudly instead of killing the
    server."""
    raw = read(name)
    if raw is None:
        return default
    try:
        val = float(raw)
        if not math.isfinite(val) or val < 0:
            raise ValueError
    except ValueError:
        import sys

        print(f"klogs: ignoring invalid {name}={raw!r} (want a "
              f"non-negative number); using {default}", file=sys.stderr)
        return default
    return val

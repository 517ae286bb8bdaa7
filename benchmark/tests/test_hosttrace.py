"""Idle device time put down to host stages (hosttrace.py) and the
readers of this PR's counters and host spans, on hand-made events and
runs, and on recorded chip traces (fixtures/)."""

import glob
import gzip
import json
import os

import pytest

import hosttrace
import run as harness
import trace
from test_metrics import reader

HERE = os.path.dirname(os.path.abspath(__file__))
PLANE = "/device:TPU:0"


def hand_made():
    """The window is [0, 100) of the trace. The device idles in [0, 10),
    [30, 60) and [70, 100)."""
    return {"start_ns": 1000, "devices": {PLANE: [
        ["op", 10, 20],
        ["op", 60, 10],
    ]}, "host": [
        ["coalescer.dispatch", 5, 30],   # two that overlap: union 5..40
        ["coalescer.dispatch", 20, 20],
        ["device.fetch", 40, 25],
        ["device.frame", 80, 5],         # nested in the next
        ["device.frame", 78, 14],
        ["python", 200, 10],             # after the window
    ]}


def test_idle_time_by_host_stage():
    d = hosttrace.reduce(hand_made(), 1000, 1100)["devices"][PLANE]
    assert d["busy_ns"] == 30
    assert d["idle_host_ns"] == {"coalescer.dispatch": 5 + 10,
                                 "device.fetch": 20, "device.frame": 14}


def test_each_gap_names_the_stages_that_overlap_it_most():
    d = hosttrace.reduce(hand_made(), 1000, 1100)["devices"][PLANE]
    assert d["gaps"] == [[70, 30], [30, 30], [0, 10]]
    assert d["gap_hosts"] == [
        [["device.frame", 14]],
        [["device.fetch", 20], ["coalescer.dispatch", 10]],
        [["coalescer.dispatch", 5]],
    ]
    assert hosttrace.gap_name(PLANE, 30, 30, d["gap_hosts"][1]) == (
        "/device:TPU:0 idle from +0.000000 s; host: device.fetch 67%, "
        "coalescer.dispatch 33%")
    assert hosttrace.gap_name(PLANE, 0, 10, []).endswith("; host: none")


def test_at_most_three_stages_per_gap():
    t = {"start_ns": 0, "devices": {PLANE: []},
         "host": [[f"s{i}", 0, 10 + i] for i in range(5)]}
    d = hosttrace.reduce(t, 0, 100)["devices"][PLANE]
    assert d["gap_hosts"] == [[["s4", 14], ["s3", 13], ["s2", 12]]]


def test_window_events_keep_the_host_events_in_the_window():
    w = hosttrace.window_events(hand_made(), 1000, 1100)
    assert [e[0] for e in w["host"]] == ["coalescer.dispatch"] * 2 + [
        "device.fetch", "device.frame", "device.frame"]
    assert w["devices"] == trace.window_events(hand_made(), 1000,
                                               1100)["devices"]


FIXTURES = sorted(glob.glob(os.path.join(HERE, "fixtures", "*.json.gz")))


def load(path):
    with gzip.open(path, "rt") as f:
        t = json.load(f)
    evs = [e for v in t["devices"].values() for e in v]
    w0 = t["start_ns"] + min(e[1] for e in evs)
    w1 = t["start_ns"] + max(e[1] + e[2] for e in evs)
    return t, w0, w1


@pytest.mark.parametrize("path", FIXTURES, ids=os.path.basename)
def test_device_keys_are_trace_reduce_byte_for_byte(path):
    t, w0, w1 = load(path)
    ours = hosttrace.reduce(t, w0, w1)
    theirs = trace.reduce(t, w0, w1)
    assert ours["window_ns"] == theirs["window_ns"]
    for plane, d in theirs["devices"].items():
        mine = {k: ours["devices"][plane][k] for k in d}
        assert json.dumps(mine) == json.dumps(d)


@pytest.mark.parametrize("path", FIXTURES, ids=os.path.basename)
def test_host_time_in_idle_time_is_bounded_by_both(path):
    t, w0, w1 = load(path)
    r = hosttrace.reduce(t, w0, w1)
    for d in r["devices"].values():
        idle_ns = r["window_ns"] - d["busy_ns"]
        for ns in d["idle_host_ns"].values():
            assert 0 <= ns <= idle_ns
        for (at, dur), hosts in zip(d["gaps"], d["gap_hosts"]):
            assert len(hosts) <= 3
            assert all(0 < ns <= dur for _, ns in hosts)


def test_follow_gaps_are_put_down_to_host_stages():
    """1.2 s of a traced k32-follow window on the chip (fixtures/), around
    its longest idle gaps. In every gap a coalesced group was in flight
    on the host (coalescer.dispatch among its top three); one gap held a
    compile of a new shape inside device.enqueue, and names it."""
    t, w0, w1 = load(os.path.join(HERE, "fixtures",
                                  "k32-follow.window.json.gz"))
    d = hosttrace.reduce(t, w0, w1)["devices"][PLANE]
    assert len(d["gaps"]) == len(d["gap_hosts"]) == trace.TOP
    assert all(hosts for hosts in d["gap_hosts"])
    assert all("coalescer.dispatch" in [n for n, _ in hosts]
               for hosts in d["gap_hosts"])
    assert d["idle_host_ns"]["PJRT_Client_Compile"] > 0.1e9
    assert "device.enqueue" in [n for hosts in d["gap_hosts"]
                                for n, _ in hosts]
    assert d["idle_host_ns"]["sink.flusher"] > 0


# -- the readers --------------------------------------------------------


def test_histogram_means_in_the_window():
    key = (lambda n, **lab: (n, tuple(sorted(lab.items()))))
    r = harness.Run()
    r.m0, r.m1 = {}, {}
    for fam, (s0, n0, s1, n1) in {
            "klogs_loop_lag_seconds": (1.0, 100, 1.5, 200),
            "klogs_sink_flusher_seconds": (2.0, 100, 2.4, 200),
            "klogs_sink_pending_wait_seconds": (10.0, 500, 13.0, 600),
            "klogs_sink_flush_lock_wait_seconds": (0.0, 500, 0.2, 600),
    }.items():
        r.m0[key(fam + "_sum")], r.m0[key(fam + "_count")] = s0, n0
        r.m1[key(fam + "_sum")], r.m1[key(fam + "_count")] = s1, n1
    r.m0[key("klogs_source_bytes_total", kind="socket")] = 0
    r.m1[key("klogs_source_bytes_total", kind="socket")] = 64 * 1024 * 30
    r.m1[key("klogs_source_bytes_total", kind="file")] = 10**9
    r.m0[key("klogs_source_reads_total", kind="socket")] = 10
    r.m1[key("klogs_source_reads_total", kind="socket")] = 70
    assert reader("loop_lag_ms.follow")(r) == pytest.approx(5.0)
    assert reader("flusher_ms.follow")(r) == pytest.approx(4.0)
    assert reader("sink_pending_ms.follow")(r) == pytest.approx(30.0)
    assert reader("flush_lock_ms.follow")(r) == pytest.approx(2.0)
    assert reader("read_kib.bulk")(r) == pytest.approx(32.0)


def test_idle_shares_by_host_stage():
    r = harness.Run()
    r.trace = hosttrace.reduce(hand_made(), 1000, 1100)
    # idle 70 of 100; 15 of it under a coalesced group, 14 packing
    assert reader("idle_no_group.follow")(r) == pytest.approx(55.0)
    assert reader("idle_pack.bulk")(r) == pytest.approx(14.0)
    # two devices: the mean
    t = hand_made()
    t["devices"]["/device:TPU:1"] = [["op", 0, 100]]
    r.trace = hosttrace.reduce(t, 1000, 1100)
    assert reader("idle_no_group.follow")(r) == pytest.approx(55.0 / 2)


def test_idle_shares_need_the_host_plane():
    r = harness.Run()
    r.trace = trace.reduce(hand_made(), 1000, 1100)  # no host keys
    assert reader("idle_no_group.follow")(r) is None
    t = hand_made()
    t["host"] = [e for e in t["host"] if e[0] != "device.frame"]
    r.trace = hosttrace.reduce(t, 1000, 1100)
    assert reader("idle_pack.bulk")(r) is None
    assert reader("idle_no_group.follow")(r) is not None

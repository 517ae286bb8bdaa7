"""The cap_close_share.bulk reader: the share of coalesced groups closed
at the line ceiling, and nothing on a collector without the counter."""

import importlib.util
import os

import pytest

import run as harness

READER = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics", "cap_close_share.bulk.py")


def read(run):
    spec = importlib.util.spec_from_file_location("m_cap_close_share_bulk",
                                                  READER)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def key(name):
    return (name, ())


def test_cap_close_share_is_the_window_share_of_groups():
    r = harness.Run()
    r.m0 = {key("klogs_coalescer_cap_closes_total"): 5,
            key("klogs_coalescer_groups_total"): 10}
    r.m1 = {key("klogs_coalescer_cap_closes_total"): 50,
            key("klogs_coalescer_groups_total"): 60}
    assert read(r) == pytest.approx(90.0)


def test_cap_close_share_without_the_counter_reads_nothing():
    # an older collector: groups but no cap-close counter reads None, not 0%
    r = harness.Run()
    r.m0 = {key("klogs_coalescer_groups_total"): 10}
    r.m1 = {key("klogs_coalescer_groups_total"): 60}
    assert read(r) is None


def test_cap_close_share_with_no_group_reads_nothing():
    r = harness.Run()
    r.m0 = {key("klogs_coalescer_cap_closes_total"): 3,
            key("klogs_coalescer_groups_total"): 10}
    r.m1 = dict(r.m0)
    assert read(r) is None

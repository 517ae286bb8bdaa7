"""% of the window in which the device idled and no coalesced group was
in flight on the host (no coalescer.dispatch span open), the mean over
devices. Needs the host plane in the reduced trace (hosttrace.py)."""

from _window import idle_share_with


def read(run):
    return idle_share_with(run, "coalescer.dispatch", inside=False)

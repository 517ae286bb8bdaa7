"""% of the window in which the device idled while the host packed a
width bucket (a device.frame span open), the mean over devices. Needs
the host plane in the reduced trace (hosttrace.py)."""

from _window import idle_share_with


def read(run):
    return idle_share_with(run, "device.frame", inside=True)

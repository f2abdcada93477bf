"""Megabytes (10^6) of acknowledged writes per second, from the
window's start to the last acknowledgement."""

from benchmark.lib.stats import rate


def read(run):
    r = rate(run.window)
    return None if r is None else r / 1e6

"""95th percentile, over every write of the window, of milliseconds
from issuing a write to its acknowledgement; a failed write counts as
infinitely late."""

from benchmark.lib.stats import latency_percentile


def read(run):
    p = latency_percentile(run.window, 95)
    return None if p is None else p * 1e3

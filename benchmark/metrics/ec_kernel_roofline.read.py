"""Percent of its roofline reached by the fused GF kernel's decode
calls (k rows in, k rows out) in the traced window; a decode is never
padded, so every call's lanes are one chunk."""

from benchmark.lib import trace as T
from benchmark.lib.readers import gf_roofline


def read(run):
    k = run.facts["ec_k"]
    if run.trace is None or run.trace.window is None:
        return None
    calls = sum(1 for e in T.gf_kernel_calls(run.trace)
                if (e[1], e[2]) == (k, k))
    return gf_roofline(run, k, k, calls)

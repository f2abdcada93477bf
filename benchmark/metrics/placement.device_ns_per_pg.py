"""Device nanoseconds of the placement pipeline program per PG mapped
in the traced window."""

from benchmark.lib import trace as T


def read(run):
    if run.trace is None or run.trace.window is None:
        return None
    ns = T.module_ns(run.trace, 0, run.facts["pipeline_module"])
    pgs = sum(o.units for o in run.window.done())
    return ns / pgs if ns > 0 and pgs else None

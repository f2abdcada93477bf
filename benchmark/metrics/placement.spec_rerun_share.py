"""Percent of the PGs mapped in the window that the speculative
lowering's first pass left to its full retry loops (``crush.mapper``
``spec_rerun_pgs``, booked by ``PoolMapper``)."""


def read(run):
    n = run.counters.get("crush.mapper.spec_rerun_pgs")
    pgs = sum(o.units for o in run.window.done())
    return 100.0 * n / pgs if n is not None and pgs else None

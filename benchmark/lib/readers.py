"""Arithmetic the metric readers share."""

from __future__ import annotations

import sys
from typing import Optional

from . import trace as T
from .kernel_cost import gf_matmul_cost, roofline_share
from .peaks import peaks


def idle_share(run) -> Optional[float]:
    """Percent of the traced window in which no operation ran on the
    device, averaged over the cell's chips."""
    tr = run.trace
    if tr is None or tr.window is None or not tr.ops:
        return None
    span = tr.window[1] - tr.window[0]
    busy = sum(T.busy_ns(tr, d) for d in tr.ops) / len(tr.ops)
    return 100.0 * (1.0 - busy / span)


def gf_roofline(run, rows_in: int, rows_out: int,
                calls: int) -> Optional[float]:
    """Percent of the GF kernel's roofline: the least time ``calls``
    unpadded calls (``rows_in`` rows in, ``rows_out`` out, one chunk of
    lanes each) could take, over the summed device time of every such
    kernel call in the trace.  None when the trace holds no call."""
    if run.trace is None or run.trace.window is None or calls <= 0:
        return None
    events = [e for e in T.gf_kernel_calls(run.trace)
              if (e[1], e[2]) == (rows_in, rows_out)]
    seconds = sum(e[0] for e in events) / 1e9
    if seconds <= 0:
        return None
    ops, nbytes = gf_matmul_cost(rows_in, rows_out,
                                 run.facts["chunk_bytes"])
    share, bound = roofline_share(calls * ops, calls * nbytes, seconds,
                                  peaks(run.device_kind))
    print(f"gf kernel {rows_in}->{rows_out}: {len(events)} launches, "
          f"{seconds:.6f} s on the device for {calls} unpadded calls; "
          f"bound by {bound}", file=sys.stderr)
    return share

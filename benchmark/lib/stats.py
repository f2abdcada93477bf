"""Rates and percentiles over every request of a window."""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional


@dataclass
class Op:
    """One request of the window: when it was sent and answered, the
    work it carried (bytes or placements), and whether it succeeded."""

    key: int
    units: int
    t_submit: float
    t_done: Optional[float] = None
    ok: bool = False


@dataclass
class Window:
    """Every request of one measured window, on the host's monotonic
    clock (``time.perf_counter``)."""

    t0: float
    ops: List[Op] = field(default_factory=list)

    def done(self) -> List[Op]:
        return [o for o in self.ops if o.ok and o.t_done is not None]

    @property
    def attempted(self) -> int:
        return len(self.ops)

    @property
    def failed(self) -> int:
        return sum(1 for o in self.ops if not o.ok)


def rate(win: Window) -> Optional[float]:
    """Units of the completed requests per second, from the window's
    start to the last completion."""
    done = win.done()
    if not done:
        return None
    t_end = max(o.t_done for o in done)
    if t_end <= win.t0:
        return None
    return sum(o.units for o in done) / (t_end - win.t0)


def latency_percentile(win: Window, q: float) -> Optional[float]:
    """The q-th percentile (nearest rank) of submit-to-answer seconds
    over every request of the window; one that failed or never came
    counts as infinitely late."""
    lats = sorted(o.t_done - o.t_submit if o.ok and o.t_done is not None
                  else math.inf for o in win.ops)
    if not lats:
        return None
    return lats[max(0, math.ceil(q / 100.0 * len(lats)) - 1)]

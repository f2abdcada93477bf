"""CrushTester — the `crushtool --test` stats engine.

The role of src/crush/CrushTester.cc:432-747: run a rule over a range
of inputs, tally per-device utilization against the weight-proportional
expectation, report result-size statistics, bad mappings, and compare
two maps.  Where the reference loops ``crush.do_rule`` one x at a time
(:573, the hot loop the 50x BASELINE target measures), this engine maps
the whole x range in batched launches sized to the device
(``BatchedMapper``) and derives every statistic from the result
arrays; ``scalar=True`` routes through the executable spec instead
(tiny runs, no compile cost).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..crush.hash import hash32_2_int
from ..crush.map import CrushMap
from ..crush.mapper_ref import crush_do_rule
from ..crush.wrapper import CrushWrapper


@dataclass
class RuleReport:
    """Stats for one (rule, num_rep) sweep."""

    ruleno: int
    num_rep: int
    min_x: int
    max_x: int
    total: int = 0
    size_counts: Dict[int, int] = field(default_factory=dict)
    device_stored: Optional[np.ndarray] = None
    device_expected: Optional[np.ndarray] = None
    bad: List[Tuple[int, List[int]]] = field(default_factory=list)
    mappings: Optional[List[List[int]]] = None

    @property
    def batch_size(self) -> int:
        return self.max_x - self.min_x + 1


class CrushTester:
    def __init__(self, wrapper: CrushWrapper,
                 weights: Optional[List[int]] = None):
        self.w = wrapper
        n = max(1, wrapper.crush.max_devices)
        self.weights = list(weights) if weights is not None \
            else [0x10000] * n
        while len(self.weights) < n:
            self.weights.append(0x10000)

    def set_device_weight(self, dev: int, weight: float) -> None:
        """--weight <dev> <w> (CrushTester.cc:454-462 semantics:
        fraction of full weight)."""
        self.weights[dev] = int(weight * 0x10000)

    # -- the sweep -----------------------------------------------------
    def test_rule(self, ruleno: int, num_rep: int, min_x: int = 0,
                  max_x: int = 1023, pool: Optional[int] = None,
                  scalar: bool = False, native: bool = False,
                  collect_mappings: bool = False,
                  mesh=None) -> RuleReport:
        """``mesh``: a ``jax.sharding.Mesh`` runs the sweep through
        ``parallel.PlacementPlane`` — ONE pjit launch maps the whole x
        range across every chip, and the per-device utilization tally
        comes back as the plane's all-reduced counts instead of a
        host-side loop (the CrushTester.cc:588-648 stats pass executed
        on-device)."""
        cmap = self.w.crush
        xs = np.arange(min_x, max_x + 1, dtype=np.uint32)
        if pool is not None:
            xs = np.asarray([hash32_2_int(int(x), pool) for x in xs],
                            np.uint32)  # CrushTester.cc:570-572
        weights = np.asarray(self.weights, np.uint32)
        counts = None
        if scalar:
            rows = [crush_do_rule(cmap, ruleno, int(x), num_rep,
                                  self.weights) for x in xs]
            ln = np.asarray([len(r) for r in rows], np.int32)
            res = np.full((len(xs), max(1, num_rep)), -1, np.int32)
            for i, r in enumerate(rows):
                res[i, :len(r)] = r
        elif mesh is not None:
            from ..parallel.placement import PlacementPlane

            plane = PlacementPlane(cmap, mesh=mesh)
            res, ln, counts = plane.map_batch(
                ruleno, xs, num_rep, weights, gather_stats=True)
        elif native:
            from ..crush.native import NativeMapper

            res, ln = NativeMapper(cmap).map_batch(
                ruleno, xs, num_rep, weights)
        else:
            from ..crush.lowering import BatchedMapper

            res, ln = BatchedMapper(cmap).map_batch(
                ruleno, xs, num_rep, weights)
        res, ln = np.asarray(res), np.asarray(ln)

        rep = RuleReport(ruleno, num_rep, min_x, max_x)
        rep.total = len(xs)
        n_dev = cmap.max_devices
        sizes, nsize = np.unique(ln, return_counts=True)
        rep.size_counts = {int(s): int(c) for s, c in zip(sizes, nsize)}
        if counts is not None:
            # the plane's all-reduced on-device tally IS the stats pass
            stored = np.asarray(counts).astype(np.int64)
        else:
            placed = res[np.arange(res.shape[1])[None, :] < ln[:, None]]
            placed = placed[(placed >= 0) & (placed < n_dev)]
            stored = np.bincount(placed, minlength=n_dev).astype(np.int64)
        rep.device_stored = stored
        # expected: weight-proportional share of all placed replicas
        wv = np.asarray(self.weights[:n_dev], np.float64)
        placed = stored.sum()
        rep.device_expected = (wv / wv.sum() * placed) if wv.sum() \
            else np.zeros(n_dev)
        for i in np.flatnonzero(ln != num_rep):
            rep.bad.append((int(xs[i]), res[i, :ln[i]].tolist()))
        if collect_mappings:
            rep.mappings = [r[:n].tolist() for r, n in zip(res, ln)]
        return rep

    # -- compare (CrushTester.cc:682-747) ------------------------------
    def compare(self, other: "CrushTester", ruleno: int, num_rep: int,
                min_x: int = 0, max_x: int = 1023,
                scalar: bool = False) -> Tuple[int, int]:
        """Returns (#different mappings, total)."""
        a = self.test_rule(ruleno, num_rep, min_x, max_x,
                           scalar=scalar, collect_mappings=True)
        b = other.test_rule(ruleno, num_rep, min_x, max_x,
                            scalar=scalar, collect_mappings=True)
        diff = sum(1 for x, y in zip(a.mappings, b.mappings) if x != y)
        return diff, a.total


def format_report(rep: RuleReport, w: CrushWrapper,
                  show_utilization: bool = False,
                  show_statistics: bool = False,
                  show_bad_mappings: bool = False,
                  show_mappings: bool = False) -> str:
    """The crushtool --test output shapes (CrushTester.cc:588-680)."""
    name = w.get_rule_name(rep.ruleno)
    out = [f"rule {rep.ruleno} ({name}), x = {rep.min_x}..{rep.max_x}, "
           f"numrep = {rep.num_rep}..{rep.num_rep}"]
    if show_mappings and rep.mappings is not None:
        for i, m in enumerate(rep.mappings):
            out.append(f"CRUSH rule {rep.ruleno} x {rep.min_x + i} "
                       f"{list(m)}")
    if show_statistics:
        for size in sorted(rep.size_counts):
            out.append(f"rule {rep.ruleno} ({name}) num_rep "
                       f"{rep.num_rep} result size == {size}:\t"
                       f"{rep.size_counts[size]}/{rep.total}")
    if show_bad_mappings:
        for x, m in rep.bad:
            out.append(f"bad mapping rule {rep.ruleno} x {x} "
                       f"num_rep {rep.num_rep} result {list(m)}")
    if show_utilization:
        for dev in range(len(rep.device_stored)):
            st = int(rep.device_stored[dev])
            ex = float(rep.device_expected[dev])
            out.append(f"  device {dev}:\t\t stored : {st}\t "
                       f"expected : {ex:.6g}")
    return "\n".join(out)

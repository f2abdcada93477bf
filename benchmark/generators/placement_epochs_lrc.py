"""Placement after every OSDMap epoch, for a pool whose CRUSH rule its
erasure-code profile writes.

The epoch stream, the window and the check are ``placement_epochs``'s.
The pool is made as ``ceph osd erasure-code-profile set`` and ``ceph
osd pool create <pool> erasure <profile>`` make it: the profile's
plugin (``ErasureCodeLrc`` for an LRC profile) writes the pool's rule
on the named CRUSH map (``create_rule``, under the root ``default``)
and gives the pool its size (``get_chunk_count``).  The plain reference
(``benchmark/reference/crush_rules.py``) reads the rule from the steps
the configuration file writes out, so the check also holds the
program's ``create_rule`` to Ceph's.

Set-up stops where ``PoolMapper`` takes the general rule VM for the
pool (``crush.mapper`` counts no ``lowered_spec``): that program cannot
map every PG of such a pool in one launch on one chip.  Counters:
``crush.mapper.spec_rerun_pgs``, the PGs that the speculative
lowering's first pass left to its retry loops.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from benchmark.generators import placement_epochs
from benchmark.lib.crushmap_steps import build_map_with_steps
from benchmark.reference import crush_rules as ref_crush
from benchmark.reference import placement as ref_place

OUT_KEYS = placement_epochs.OUT_KEYS
EXISTS, UP = placement_epochs.EXISTS, placement_epochs.UP


class Generator(placement_epochs.Generator):
    def __init__(self, config: Dict, traffic: Dict, seed: int,
                 trace: bool):
        # the program's map holds no rule until the plugin writes one
        super().__init__(dict(config, crush=dict(config["crush"],
                                                 rules=[])),
                         traffic, seed, trace)
        self.bare_map = self.map_dict
        self.map_dict = build_map_with_steps(config["crush"])

    def setup(self) -> None:
        import jax

        from ceph_tpu.crush.map import CrushMap
        from ceph_tpu.crush.wrapper import CrushWrapper
        from ceph_tpu.ec.registry import profile_factory
        from ceph_tpu.osdmap.osdmap import (OSDMap, PgPool,
                                            POOL_TYPE_ERASURE)
        from ceph_tpu.osdmap.pipeline_jax import PoolMapper

        crush = CrushWrapper(CrushMap.from_dict(self.bare_map))
        crush.set_item_name(self.bare_map["buckets"][-1]["id"], "default")
        p = self.pool
        code = profile_factory(dict(p["profile"]))
        rule = code.create_rule(self.traffic["pool"], crush)
        if code.get_chunk_count() != p["size"]:
            raise ValueError(f"the profile gives {code.get_chunk_count()} "
                             f"chunks, the configuration {p['size']}")
        m = OSDMap(crush.crush)
        for osd in range(self.n_osd):
            m.add_osd(osd)
        m.pools[p["id"]] = PgPool(
            pool_type=POOL_TYPE_ERASURE, size=code.get_chunk_count(),
            min_size=p["min_size"], pg_num=p["pg_num"], crush_rule=rule)
        taken = self._lowered()
        self.mapper = PoolMapper(m, p["id"])
        if self._lowered() == taken:
            # the general rule VM: one launch over every PG needs tens
            # of GB here, more than a chip holds, and its compile alone
            # runs for minutes before the refusal
            raise RuntimeError("the pool's rule did not take the "
                               "speculative lowering")
        self.weight = np.full(self.n_osd, 0x10000, np.uint32)
        self.state = np.full(self.n_osd, EXISTS | UP, np.int32)
        self.failed = []
        self.held = {}
        self.epoch_rng = self._rng(1)
        self.remapped = 0
        with jax.profiler.TraceAnnotation("bench.warmup"):
            self.prev = self._map()

    @staticmethod
    def _mapper_counters() -> Dict:
        from ceph_tpu.common.perf_counters import collection

        return collection().dump("crush.mapper")["crush.mapper"]

    def _lowered(self) -> int:
        """Rules lowered onto the speculative program so far (none
        where the program does not count them)."""
        return self._mapper_counters().get("lowered_spec", 0)

    def counters(self) -> Dict[str, float]:
        return {"crush.mapper.spec_rerun_pgs":
                float(self._mapper_counters()["spec_rerun_pgs"])}

    def check(self) -> Dict[str, tuple]:
        epochs = [k["epoch"] for k in self.kept]
        # the window's last answer always, and others drawn from the seed
        n = max(0, min(self.traffic["check_epochs"], len(epochs)) - 1)
        pick = set(self._rng(3).choice(epochs[:-1], n,
                                       replace=False).tolist())
        pick.update(epochs[-1:])
        cmap = ref_crush.Map(self.map_dict)
        bad = checked = 0
        for k in self.kept:
            if k["epoch"] not in pick:
                continue
            weight = k["weight"].tolist()
            state = k["state"].tolist()
            rows = k["rows"]
            for j, ps in enumerate(k["ps"].tolist()):
                want = ref_place.up_acting(cmap, self.pool, ps, weight,
                                           state)
                got = (rows["up"][j, :rows["up_len"][j]].tolist(),
                       int(rows["up_primary"][j]),
                       rows["acting"][j, :rows["acting_len"][j]].tolist(),
                       int(rows["acting_primary"][j]))
                checked += 1
                bad += got != want
        return {"mismatched_pgs": (bad, 0),
                "no_pgs_checked": (0 if checked else 1, 0)}

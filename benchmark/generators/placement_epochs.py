"""Placement after every OSDMap epoch: the monitor's and manager's work.

Set-up builds the configuration's CRUSH map and an OSDMap with every
OSD up and in and the traffic's pool, and maps the pool once (which
compiles the pipeline, or finds it in the cache).  Each step of the
window is one epoch: drawn from the seed, with odds ``fail_share``,
either one random up OSD fails (marked down with weight 0, as ``ceph
osd down`` followed by ``osdmaptool --mark-out`` leaves it) or the
oldest failed OSD returns; the first epoch always fails one, and at
most ``max_failed`` are down at once.  The stream depends on the seed
alone, so the cells of one seed see the same epochs.  Then
``PoolMapper.map_all``
maps every PG under the new weights and states, the up and acting sets
come to the host, and the PGs whose sets changed are counted.

The check compares, in ``check_epochs`` epochs drawn from the seed, a
seeded uniform sample of PGs and every PG whose up set held the
epoch's changed OSD before or after it (for a returning OSD, also every
PG that held it in the last epoch before it failed, so that an answer
which leaves it out is compared too), with the plain reference
(``benchmark/reference/placement.py``) under that epoch's weights and
states.
"""

from __future__ import annotations

import sys
import time
from typing import Dict, List

import numpy as np

from benchmark.lib.crushmap import build_map
from benchmark.lib.stats import Op, Window
from benchmark.reference import crush as ref_crush
from benchmark.reference import placement as ref_place

OUT_KEYS = ("up", "up_len", "up_primary", "acting", "acting_len",
            "acting_primary")
EXISTS, UP = ref_place.EXISTS, ref_place.UP


class Generator:
    def __init__(self, config: Dict, traffic: Dict, seed: int,
                 trace: bool):
        from benchmark.lib.harness import seed_sequence

        self.traffic = traffic
        self.pool = dict(config["pools"][traffic["pool"]])
        self.seed = seed_sequence(seed)
        self.map_dict = build_map(config["crush"])
        self.n_osd = self.map_dict["max_devices"]
        self.kept: List[Dict] = []       # per epoch: state + kept rows

    # -- set-up ---------------------------------------------------------
    def setup(self) -> None:
        import jax

        from ceph_tpu.crush.map import CrushMap
        from ceph_tpu.osdmap.osdmap import (OSDMap, PgPool,
                                            POOL_TYPE_ERASURE,
                                            POOL_TYPE_REPLICATED)
        from ceph_tpu.osdmap.pipeline_jax import PoolMapper

        m = OSDMap(CrushMap.from_dict(self.map_dict))
        for osd in range(self.n_osd):
            m.add_osd(osd)
        p = self.pool
        m.pools[p["id"]] = PgPool(
            pool_type=(POOL_TYPE_REPLICATED if p["type"] == "replicated"
                       else POOL_TYPE_ERASURE),
            size=p["size"], min_size=p["min_size"], pg_num=p["pg_num"],
            crush_rule=p["crush_rule"])
        self.mapper = PoolMapper(m, p["id"])
        self.weight = np.full(self.n_osd, 0x10000, np.uint32)
        self.state = np.full(self.n_osd, EXISTS | UP, np.int32)
        self.failed: List[int] = []
        self.held: Dict[int, np.ndarray] = {}   # failed OSD -> its PGs
        self.epoch_rng = self._rng(1)
        self.remapped = 0
        with jax.profiler.TraceAnnotation("bench.warmup"):
            self.prev = self._map()

    def _rng(self, *key: int) -> np.random.Generator:
        return np.random.default_rng(np.random.SeedSequence(
            self.seed.entropy, spawn_key=key))

    def _map(self) -> Dict[str, np.ndarray]:
        import jax

        with jax.profiler.TraceAnnotation("bench.map_all"):
            out = self.mapper.map_all(weight=self.weight,
                                      state=self.state)
        with jax.profiler.TraceAnnotation("bench.fetch"):
            return {k: np.asarray(out[k]) for k in OUT_KEYS}

    # -- the window -----------------------------------------------------
    def _next_change(self):
        """Apply the next epoch's change to weight/state; returns the
        OSD it touched and whether it failed."""
        rng = self.epoch_rng
        fail = rng.random() < self.traffic["fail_share"]
        if not self.failed or len(self.failed) >= \
                self.traffic["max_failed"]:
            fail = not self.failed
        if fail:
            while True:
                osd = int(rng.integers(self.n_osd))
                if osd not in self.failed:
                    break
            self.failed.append(osd)
            self.weight[osd] = 0
            self.state[osd] = EXISTS
            return osd, True
        osd = self.failed.pop(0)
        self.weight[osd] = 0x10000
        self.state[osd] = EXISTS | UP
        return osd, False

    def _keep(self, epoch: int, out, prev, osd, failed: bool) -> Dict:
        """The rows the check will compare: a seeded uniform sample,
        every PG whose up set holds the changed OSD now or before, and,
        where it returns, the PGs that held it before it failed."""
        idx = self._rng(2, epoch).choice(
            self.pool["pg_num"], self.traffic["check_uniform"],
            replace=False)
        was = (prev["up"] == osd).any(1)
        if failed:
            self.held[osd] = np.nonzero(was)[0]
            before = np.empty(0, np.int64)
        else:
            before = self.held.pop(osd)
        hit = np.nonzero((out["up"] == osd).any(1) | was)[0]
        idx = np.union1d(np.union1d(idx, hit), before)
        return {"epoch": epoch, "weight": self.weight.copy(),
                "state": self.state.copy(), "ps": idx,
                "rows": {k: out[k][idx].copy() for k in OUT_KEYS}}

    def window(self, seconds: float) -> Window:
        import jax

        win = Window(t0=time.perf_counter())
        deadline = win.t0 + seconds
        epoch = 0
        self.failed_counts: List[int] = []
        while time.perf_counter() < deadline:
            epoch += 1
            op = Op(key=epoch, units=self.pool["pg_num"],
                    t_submit=time.perf_counter())
            win.ops.append(op)
            with jax.profiler.TraceAnnotation("bench.epoch"):
                with jax.profiler.TraceAnnotation("bench.map_update"):
                    osd, failed = self._next_change()
                out = self._map()
                with jax.profiler.TraceAnnotation("bench.diff"):
                    moved = int(np.count_nonzero(
                        (out["up"] != self.prev["up"]).any(1) |
                        (out["acting"] != self.prev["acting"]).any(1)))
                    self.kept.append(self._keep(epoch, out, self.prev, osd,
                                                failed))
            op.t_done, op.ok = time.perf_counter(), True
            self.remapped += moved
            self.failed_counts.append(len(self.failed))
            self.prev = out
        print("epoch seconds: " + ", ".join(
            f"{o.t_done - o.t_submit:.3f}" for o in win.ops) +
            "; OSDs down: " + ", ".join(map(str, self.failed_counts)) +
            f"; PGs remapped: {self.remapped}", file=sys.stderr)
        return win

    # -- readings and the check -----------------------------------------
    def counters(self) -> Dict[str, float]:
        return {}

    def facts(self) -> Dict:
        return {"pipeline_module": "jit_single_pg"}

    def release(self) -> None:
        self.mapper = None
        self.prev = None

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

    def close(self) -> None:
        self.mapper = None

"""Reads from an erasure-coded pool while one OSD is down.

Set-up starts the configuration's cluster, warms the EC shapes, writes
``objects`` seeded objects of ``object_bytes`` (about one per PG) with
``fill_in_flight`` writes outstanding, and checks that every shard of
every object has landed on the OSD stores: the configuration's
guarantee, which also leaves the OSDs no recovery to run.  Then it
stops one OSD drawn from the seed and marks it down at the monitor
(down, not out), waits until the client's map shows it down, and reads
``warm_reads`` objects, which compiles (or finds in the cache) the
decode kernel.  In the window, ``readers`` threads each read uniformly
random objects of the set, the next when the last returns.  Where the
stopped OSD holds a data shard of an object, the client decodes it from
the survivors on the device.

The check compares a seeded share (``keep_share``) of the window's
reads, kept as they arrive, with the regenerated payloads; a read that
failed also fails the check, and so does each set-up write acknowledged
as landed on fewer than all shards, and each shard missing from the
stores once all are acknowledged.
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Dict, List

import numpy as np

from benchmark.lib import rados as R
from benchmark.lib.stats import Op, Window
from benchmark.reference import gf256


class Generator:
    def __init__(self, config: Dict, traffic: Dict, seed: int,
                 trace: bool):
        from benchmark.lib.harness import seed_sequence

        self.config, self.traffic = config, traffic
        self.seed = seed_sequence(seed)
        self.pool = config["pool"]
        self.k = int(self.pool["profile"]["k"])
        self.size = int(traffic["object_bytes"])
        self.n_obj = int(traffic["objects"])
        self.payload = R.Payloads(self.seed, self.size)
        self.cluster = self.client = None
        self.kept: List[tuple] = []

    def name(self, i: int) -> str:
        return f"bench_{i}"

    def _rng(self, *key: int) -> np.random.Generator:
        return np.random.default_rng(np.random.SeedSequence(
            self.seed.entropy, spawn_key=key))

    def setup(self) -> None:
        import jax

        phases = R.Phases("setup")
        self.threads_before = set(threading.enumerate())
        self.cluster, self.client, self.acks = R.start_cluster(
            self.config, False, self.traffic["readers"])
        phases.mark("cluster and pool")
        pool_id = self.pool["id"]
        with jax.profiler.TraceAnnotation("bench.warmup"):
            R.warm_ec_shapes(self.pool["profile"], self.payload(0),
                             self.traffic["warm_batches"])
        phases.mark("EC shapes")
        with jax.profiler.TraceAnnotation("bench.fill"):
            fill = R.write_loop(self.client, pool_id, self.name,
                                self.payload,
                                self.traffic["fill_in_flight"],
                                count=self.n_obj)
        phases.mark(f"{self.n_obj} writes")
        if fill.failed:
            raise RuntimeError(f"{fill.failed} of {self.n_obj} set-up "
                               f"writes failed")
        self.unlanded = self.acks.degraded + R.missing_shards(
            self.cluster, R.Placement(self.cluster, self.config),
            [self.name(i) for i in range(self.n_obj)],
            gf256.chunk_size(self.size, self.k))
        phases.mark(f"shards checked ({self.acks.degraded} degraded "
                    f"acknowledgements, {self.unlanded} in all)")
        self.down = int(self._rng(13).integers(self.config["osds"]))
        self.cluster.kill_osd(self.down)
        self.cluster.mon_command({"type": "mark_down", "osd": self.down})
        deadline = time.monotonic() + 30.0
        while self.client.map.is_up(self.down):
            if time.monotonic() > deadline:
                raise TimeoutError(f"osd.{self.down} never shown down")
            time.sleep(0.05)
            self.client.refresh_map()
        phases.mark(f"osd.{self.down} down")
        took = []
        with jax.profiler.TraceAnnotation("bench.warmup"):
            for i in range(self.traffic["warm_reads"]):
                t0 = time.monotonic()
                if self.client.get(pool_id, self.name(i)) != \
                        self.payload(i):
                    raise RuntimeError(f"warm-up read of object {i} "
                                       f"returned other bytes")
                took.append(time.monotonic() - t0)
        phases.mark("warm-up reads (each " + ", ".join(
            f"{t:.2f}" for t in took) + " s)")

    def window(self, seconds: float) -> Window:
        import jax

        rngs = [self._rng(17, r) for r in range(self.traffic["readers"])]
        keep = self.traffic["keep_share"]
        pool_id = self.pool["id"]

        def one(r: int, op: Op) -> bool:
            i = int(rngs[r].integers(self.n_obj))
            kept = rngs[r].random() < keep
            op.key = i
            with jax.profiler.TraceAnnotation("bench.get"):
                data = self.client.get(pool_id, self.name(i))
            op.units = len(data)
            if kept:
                self.kept.append((i, data))
            return True

        recovered = R.recovered_objects(self.cluster)
        self.win = R.read_loop(self.traffic["readers"], seconds, one)
        R.report_background(self.cluster)
        self.recovered = R.recovered_objects(self.cluster) - recovered
        print(f"objects recovered in the window: {self.recovered}",
              file=sys.stderr)
        return self.win

    def counters(self) -> Dict[str, float]:
        return R.ec_counters()

    def facts(self) -> Dict:
        return {"ec_k": self.k,
                "chunk_bytes": gf256.chunk_size(self.size, self.k),
                "down_osd": self.down,
                "recovered_in_window": self.recovered}

    def release(self) -> None:
        pass

    def check(self) -> Dict[str, tuple]:
        bad = sum(data != self.payload(i) for i, data in self.kept)
        return {"unlanded_setup_shards": (self.unlanded, 0),
                "failed_reads": (self.win.failed, 0),
                "mismatched_reads": (bad, 0),
                "no_reads_checked": (0 if self.kept else 1, 0)}

    def close(self) -> None:
        if self.cluster is not None:
            R.stop(self.cluster, self.threads_before)
            self.cluster = None

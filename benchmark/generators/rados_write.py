"""``rados bench write`` against an erasure-coded pool.

Set-up starts the configuration's cluster, creates its pool, compiles
(or finds in the cache) the encode kernel at each batch size the OSDs'
encode batcher can form and the decode that background recovery runs,
and writes one warm-up round.  The window is a
closed loop: one client keeps ``in_flight`` whole-object writes of
``object_bytes`` outstanding, each a fresh object with a seeded
payload.  The configuration's guarantee: a write is acknowledged only
once all k+m shards have landed on the OSD stores.

The check reads back a seeded sample of the acknowledged objects
through the client, and compares each of their k+m shards, in the store
of the OSD that the plain placement (``benchmark/reference``, over the
cluster's CRUSH map) puts at that position, with a plain Reed-Solomon
encode of the regenerated payload (``benchmark/reference/gf256.py``);
a position CRUSH leaves empty counts as a wrong shard.  Every write the
primary acknowledged as landed on fewer than all k+m shards, from the
warm-up on, breaks the guarantee and counts against it, although
recovery may close the gap before the check looks.  A write that
failed or never answered also fails the check.
"""

from __future__ import annotations

import sys
import threading
from typing import Dict

import numpy as np

from benchmark.lib import rados as R
from benchmark.lib.stats import Window
from benchmark.reference import crush as ref_crush
from benchmark.reference import gf256


class Generator:
    def __init__(self, config: Dict, traffic: Dict, seed: int,
                 trace: bool):
        from benchmark.lib.harness import seed_sequence

        self.config, self.traffic, self.trace = config, traffic, trace
        self.seed = seed_sequence(seed)
        self.pool = config["pool"]
        self.k = int(self.pool["profile"]["k"])
        self.m = int(self.pool["profile"]["m"])
        self.size = int(traffic["object_bytes"])
        self.payload = R.Payloads(self.seed, self.size)
        self.cluster = self.client = None
        self.stages: Dict[str, float] = {}

    def name(self, i: int) -> str:
        return f"bench_{i}"

    def setup(self) -> None:
        import jax

        phases = R.Phases("setup")
        self.threads_before = set(threading.enumerate())
        self.cluster, self.client, self.acks = R.start_cluster(
            self.config, self.trace, self.traffic["in_flight"])
        phases.mark("cluster and pool")
        with jax.profiler.TraceAnnotation("bench.warmup"):
            R.warm_ec_shapes(self.pool["profile"], self.payload(0),
                             self.traffic["warm_batches"])
            phases.mark("EC shapes")
            warm = R.write_loop(self.client, self.pool["id"],
                                lambda i: f"warmup_{i}", self.payload,
                                self.traffic["in_flight"],
                                count=2 * self.traffic["in_flight"])
        phases.mark("warm-up writes")
        if warm.failed:
            raise RuntimeError(f"{warm.failed} warm-up writes failed")

    def window(self, seconds: float) -> Window:
        import jax

        self.win = R.write_loop(self.client, self.pool["id"], self.name,
                                self.payload, self.traffic["in_flight"],
                                seconds=seconds,
                                annotate=jax.profiler.TraceAnnotation)
        R.report_background(self.cluster)
        if self.trace:
            self.stages = R.stage_totals(self.cluster, self.client)
            print("critical-path seconds by stage: " + ", ".join(
                f"{k} {v:.3f}" for k, v in self.stages.items()),
                file=sys.stderr)
        return self.win

    def counters(self) -> Dict[str, float]:
        return R.ec_counters()

    def facts(self) -> Dict:
        return {"ec_k": self.k, "ec_m": self.m,
                "chunk_bytes": gf256.chunk_size(self.size, self.k),
                "objects_written": len(self.win.done()),
                "stages": self.stages}

    def release(self) -> None:
        pass

    def check(self) -> Dict[str, tuple]:
        acked = [o.key for o in self.win.done()]
        rng = np.random.default_rng(np.random.SeedSequence(
            self.seed.entropy, spawn_key=(5,)))
        n = min(self.traffic["check_objects"], len(acked))
        sample = sorted(set(rng.choice(acked, n, replace=False).tolist())
                        | set(acked[-1:]))
        place = R.Placement(self.cluster, self.config)
        bad_reads = bad_shards = 0
        for i in sample:
            name, data = self.name(i), self.payload(i)
            try:
                got = self.client.get(self.pool["id"], name)
            except Exception:
                got = None
            bad_reads += got != data
            ps, acting = place(name)
            for pos, shard in enumerate(gf256.encode(data, self.k, self.m)):
                osd = acting[pos]
                try:
                    held = (None if osd == ref_crush.ITEM_NONE else
                            self.cluster.osds[osd].store.read(
                                f"{place.pool_id}.{ps}", f"{name}.s{pos}"))
                except KeyError:
                    held = None
                bad_shards += held != shard
        return {"failed_writes": (self.win.failed, 0),
                "degraded_acks": (self.acks.degraded, 0),
                "mismatched_readbacks": (bad_reads, 0),
                "mismatched_shards": (bad_shards, 0),
                "no_objects_checked": (0 if sample else 1, 0)}

    def close(self) -> None:
        if self.cluster is not None:
            R.stop(self.cluster, self.threads_before)
            self.cluster = None

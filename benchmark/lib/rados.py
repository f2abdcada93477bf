"""What the RADOS traffic generators share: the cluster a configuration
describes, seeded object payloads, and the closed-loop load of
``rados bench``.

The load generators are copied from the program's
``ceph_tpu/tools/rados_bench.py`` ``ObjBencher`` (``write_aio``: one
submitter keeping ``in_flight`` ops outstanding; ``_run``: N threads
each issuing its next op when the last returns), with two changes:
every object's payload is made from the seed, and a write's latency is
timed from the moment it is sent, after it has a slot in the window,
as ``rados bench`` times it.
"""

from __future__ import annotations

import contextlib
import hashlib
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from benchmark.reference import crush as ref_crush
from benchmark.reference import placement as ref_place

from .stats import Op, Window

PAYLOAD_SPREAD = 1 << 16     # distinct offsets into the seeded base


class Payloads:
    """Object ``i``'s bytes: 16 bytes naming (seed, i), then a slice of
    a seeded random block at an offset set by ``i``.  Regenerated from
    the seed by the check."""

    def __init__(self, seed_seq, size: int):
        rng = np.random.default_rng(np.random.SeedSequence(
            seed_seq.entropy, spawn_key=(101,)))
        self.size = size
        self.tag = int(seed_seq.entropy % (1 << 64)).to_bytes(8, "little")
        self.base = rng.bytes(size + PAYLOAD_SPREAD)

    def __call__(self, i: int) -> bytes:
        off = (i * 4099) % PAYLOAD_SPREAD
        return (self.tag + i.to_bytes(8, "little") +
                self.base[off:off + self.size - 16])


def object_ps(name: str, pg_num: int) -> int:
    """The system's object locator: the low 32 bits of SHA-256 of the
    name, little-endian, modulo pg_num."""
    return int.from_bytes(hashlib.sha256(name.encode()).digest()[:4],
                          "little") % pg_num


def start_cluster(config: Dict, trace: bool, in_flight: int):
    """A MiniCluster as the configuration describes it, with its pool,
    one client, and an ``AckTap`` on that client.  Traced runs record every span (sample rate 1) in
    rings large enough for the whole window."""
    from ceph_tpu.common.config import Config
    from ceph_tpu.services.cluster import MiniCluster

    conf = Config()
    for k, v in config["settings"].items():
        conf.set(k, v)
    conf.set("client_aio_window", in_flight)
    conf.set("trace_sample_rate", 1.0 if trace else 0.0)
    if trace:
        conf.set("trace_ring_size", config["trace_ring_size"])
    cluster = MiniCluster(config["osds"], hosts=config["hosts"],
                          config=conf).start()
    try:
        pool = config["pool"]
        cluster.create_ec_pool(pool["id"], pool["profile_name"],
                               dict(pool["profile"]),
                               pg_num=pool["pg_num"])
        client = cluster.client("bench")
    except BaseException:
        cluster.shutdown()
        raise
    return cluster, client, AckTap(client)


def warm_ec_shapes(profile: Dict, raw: bytes, batches) -> None:
    """Compile (or load from the cache) what the EC data path runs on
    the device for objects like ``raw``: the encode at each batch size
    the OSDs' encode batcher forms, and a decode that rebuilds a data
    and a parity chunk (degraded reads and recovery)."""
    from ceph_tpu.ec.registry import profile_factory

    code = profile_factory(dict(profile))
    n, k = code.get_chunk_count(), code.get_data_chunk_count()
    for b in batches:
        out = (code.encode(range(n), raw) if b == 1 else
               code.encode_batched(range(n), [raw] * b)[0])
        np.asarray(out[n - 1])
    have = {i: np.asarray(out[i]) for i in range(n) if i not in (0, k)}
    dec = code.decode(set(range(n)), have)
    np.asarray(dec[0])
    np.asarray(dec[k])
    assert code.decode_concat(have)[:len(raw)] == raw


def write_loop(client, pool_id: int, names: Callable[[int], str],
               payload: Callable[[int], bytes], in_flight: int,
               seconds: Optional[float] = None,
               count: Optional[int] = None,
               annotate: Callable = None) -> Window:
    """Closed loop of whole-object writes: at most ``in_flight``
    outstanding; runs for ``seconds`` or ``count`` writes, then waits
    (up to a minute) for every write it sent."""
    slots = threading.Semaphore(in_flight)
    win = Window(t0=time.perf_counter())
    deadline = win.t0 + seconds if seconds is not None else None
    i = 0
    annotate = annotate or (lambda _name: contextlib.nullcontext())
    while (count is None or i < count) and \
            (deadline is None or time.perf_counter() < deadline):
        data = payload(i)
        with annotate("bench.wait_slot"):
            slots.acquire()
        op = Op(key=i, units=len(data), t_submit=time.perf_counter())
        win.ops.append(op)

        def done(c, op=op):
            op.t_done = time.perf_counter()
            op.ok = c.error is None
            slots.release()

        with annotate("bench.aio_put"):
            client.aio_put(pool_id, names(i), data, on_complete=done)
        i += 1
    end = time.monotonic() + 60.0
    with annotate("bench.drain"):
        for _ in range(in_flight):   # every slot back: every op answered
            if not slots.acquire(
                    timeout=max(0.0, end - time.monotonic())):
                break
    return win


def read_loop(readers: int, seconds: float,
              one: Callable[[int, Op], bool]) -> Window:
    """``readers`` threads, each issuing its next read when the last
    returns, until ``seconds`` have passed; ``one(reader, op)`` does a
    read and returns whether it succeeded."""
    win = Window(t0=time.perf_counter())
    deadline = win.t0 + seconds
    lock = threading.Lock()

    def worker(r: int) -> None:
        while time.perf_counter() < deadline:
            op = Op(key=-1, units=0, t_submit=time.perf_counter())
            with lock:
                win.ops.append(op)
            try:
                op.ok = one(r, op)
            except Exception:
                op.ok = False
            op.t_done = time.perf_counter()

    threads = [threading.Thread(target=worker, args=(r,), daemon=True,
                                name=f"bench-reader-{r}")
               for r in range(readers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + 120.0)
    return win


def stop(cluster, before, timeout: float = 5.0) -> None:
    """Shut the cluster down and wait, up to ``timeout`` seconds, for
    every thread started since ``before`` (the threads alive before the
    cluster started) to end."""
    cluster.shutdown()
    end = time.monotonic() + timeout
    new = [t for t in threading.enumerate() if t not in before]
    for t in new:
        t.join(max(0.0, end - time.monotonic()))
    left = [t.name for t in new if t.is_alive()]
    if left:
        print(f"cluster threads still running after {timeout:.0f} s: "
              f"{len(left)} ({', '.join(sorted(left)[:8])})",
              file=sys.stderr)


class Phases:
    """Seconds of each set-up phase, printed to standard error."""

    def __init__(self, what: str):
        self.what, self.t = what, time.monotonic()

    def mark(self, phase: str) -> None:
        now = time.monotonic()
        print(f"{self.what}: {phase} {now - self.t:.3f} s",
              file=sys.stderr, flush=True)
        self.t = now


def report_background(cluster) -> None:
    """The OSDs' recovery and scrub work so far, to standard error:
    background work competes with the window's requests for the host."""
    sums: Dict[str, float] = {}
    for svc in cluster.osds.values():
        for fam in (svc.pc, svc.rec_pc):
            for k, v in fam.dump().items():
                if isinstance(v, (int, float)):
                    sums[k] = sums.get(k, 0) + v
    keys = ("ops_w", "ops_r", "recovered_objects", "recovery_bytes",
            "degraded_reads", "helper_reads", "strategy_full")
    print("osd totals: " + ", ".join(f"{k} {sums.get(k, 0):.0f}"
                                     for k in keys), file=sys.stderr)


class AckTap:
    """Reads the PG primary's answer to each of the client's whole-object
    EC writes as it reaches the client, and counts the acknowledgements
    that say the write landed on fewer than all k+m shards (``degraded``
    in the answer, which the client itself does not look at)."""

    def __init__(self, client):
        self.degraded = 0
        self._lock = threading.Lock()
        call = client.msgr.call

        def tapped(addr, msg, *a, **kw):
            rep = call(addr, msg, *a, **kw)
            if msg.get("type") == "ec_write" and isinstance(rep, dict) \
                    and rep.get("ok") and rep.get("degraded"):
                with self._lock:
                    self.degraded += 1
            return rep

        client.msgr.call = tapped


def recovered_objects(cluster) -> int:
    """Objects the OSDs' recovery has rebuilt (or, for a position whose
    OSD is down, decoded) so far."""
    return int(sum(fam.dump().get("recovered_objects", 0)
                   for svc in cluster.osds.values()
                   for fam in (svc.pc, svc.rec_pc)))


class Placement:
    """Where the plain placement (``benchmark/reference``, over the
    cluster's own CRUSH map, its input) puts each position of an object
    of the configuration's pool with every OSD up and in."""

    def __init__(self, cluster, config: Dict):
        p = config["pool"]
        self.pool_id, self.pg_num = p["id"], p["pg_num"]
        size = int(p["profile"]["k"]) + int(p["profile"]["m"])
        self.cmap = ref_crush.Map(cluster.wrapper.crush.to_dict())
        self.pool = {"id": self.pool_id, "type": "erasure", "size": size,
                     "pg_num": self.pg_num,
                     "crush_rule": cluster.ec_rule}
        n_osd = config["osds"]
        self.weight = [0x10000] * n_osd
        self.state = [ref_place.EXISTS | ref_place.UP] * n_osd

    def __call__(self, name: str):
        """(ps, the OSD at each position; ``ref_crush.ITEM_NONE`` where
        CRUSH found none)."""
        ps = object_ps(name, self.pg_num)
        _up, _p, acting, _ap = ref_place.up_acting(
            self.cmap, self.pool, ps, self.weight, self.state)
        return ps, acting


def missing_shards(cluster, placement: Placement, names,
                   shard_bytes: int) -> int:
    """Positions of the named objects whose shard is not in the store of
    the OSD the placement puts there, at full length; a position CRUSH
    left empty counts as missing."""
    missing = 0
    for name in names:
        ps, acting = placement(name)
        for pos, osd in enumerate(acting):
            st = (None if osd == ref_crush.ITEM_NONE else
                  cluster.osds[osd].store.stat(
                      f"{placement.pool_id}.{ps}", f"{name}.s{pos}"))
            missing += st is None or st["size"] != shard_bytes
    return missing


def ec_counters() -> Dict[str, float]:
    from ceph_tpu.common.perf_counters import collection

    c = collection().dump("ec.engine")["ec.engine"]
    return {"ec.encode_ops": c["encode_ops"],
            "ec.decode_ops": c["decode_ops"]}


def stage_totals(cluster, client) -> Dict[str, float]:
    """Seconds per critical-path stage summed over every finished
    client op in the daemons' span rings (the benchmark's copy of the
    program's stage fold)."""
    from .attribution import STAGES, fold_spans

    spans: List[Dict] = list(client.tracer.dump()["spans"])
    for svc in cluster.osds.values():
        spans.extend(svc.tracer.dump()["spans"])
    totals = {s: 0.0 for s in STAGES}
    totals["total"] = 0.0
    for fold in fold_spans(spans):
        totals["total"] += fold["total"]
        for s, v in fold["stages"].items():
            totals[s] += v
    return totals

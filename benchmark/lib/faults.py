"""Breakages of the timed path, planted under a run to show that its
check comes out false.

Each entry patches the system under test in place for the life of a
``with`` block.  ``CONTROLS`` hold the guarantee-breaking control of
each traffic generator (the tempting shortcut: a one-epoch-stale placement, an
acknowledgement before the parity is stored, a degraded read that skips
the decode); ``FAULTS`` hold the faults every cell is checked against:
a step that returns its state unchanged, half of the batch left out,
an answer altered where it is produced.  (The exchange between chips
has no cell on more than one chip yet.)  Used by
``benchmark/controls.py`` on the chip and by ``benchmark/tests``.
"""

from __future__ import annotations

import contextlib
from typing import Callable, Dict

import numpy as np


@contextlib.contextmanager
def _patched(owner, attr: str, make: Callable):
    orig = getattr(owner, attr)
    setattr(owner, attr, make(orig))
    try:
        yield
    finally:
        setattr(owner, attr, orig)


def _host(out: Dict) -> Dict[str, np.ndarray]:
    return {k: np.array(v) for k, v in out.items()}


# -- placement (PoolMapper.map_all) --------------------------------------
def _pool_mapper():
    from ceph_tpu.osdmap.pipeline_jax import PoolMapper

    return PoolMapper


def placement_stale_epoch():
    """Control: every epoch is mapped with the previous epoch's weights
    and states (a cached, one-epoch-stale placement)."""
    def make(orig):
        last = {}

        def map_all(self, weight=None, state=None, paff=None):
            w, s = last.get(id(self), (weight, state))
            last[id(self)] = (np.array(weight), np.array(state))
            return orig(self, weight=w, state=s, paff=paff)
        return map_all
    return _patched(_pool_mapper(), "map_all", make)


def placement_unchanged_state():
    """Every call returns the first placement it computed."""
    def make(orig):
        first = {}

        def map_all(self, *a, **kw):
            if id(self) not in first:
                first[id(self)] = _host(orig(self, *a, **kw))
            return first[id(self)]
        return map_all
    return _patched(_pool_mapper(), "map_all", make)


def placement_half_batch():
    """Only the first half of the PGs is mapped; the rest of the
    output is left as unwritten (zero) rows."""
    def make(orig):
        def map_all(self, *a, **kw):
            out = _host(orig(self, *a, **kw))
            half = len(out["up"]) // 2
            for v in out.values():
                v[half:] = 0
            return out
        return map_all
    return _patched(_pool_mapper(), "map_all", make)


def placement_altered_answer():
    """Every 8th PG's first OSD is replaced by its neighbour's id."""
    def make(orig):
        def map_all(self, *a, **kw):
            out = _host(orig(self, *a, **kw))
            n = max(1, self.D)
            for key in ("up", "acting"):
                out[key][::8, 0] = (out[key][::8, 0] + 1) % n
            return out
        return map_all
    return _patched(_pool_mapper(), "map_all", make)


# -- erasure-coded writes and reads --------------------------------------
def write_ack_at_k():
    """Control: the primary acknowledges once the k data shards are
    stored; the parity shards are never written."""
    from ceph_tpu.services.osd_service import OSDService

    def make(orig):
        def push(self, pool_id, ps, osd, oid, shard, *a, **kw):
            code = self._code_for(self.map.pools[pool_id])
            if shard >= code.get_data_chunk_count():
                return {"ok": True, "epoch": self.epoch}
            return orig(self, pool_id, ps, osd, oid, shard, *a, **kw)
        return push
    return _patched(OSDService, "_push_shard", make)


def write_lost_push():
    """The push of each object's last parity shard gets no answer: the
    primary acknowledges the write with one shard short, and recovery
    rebuilds that shard later."""
    from ceph_tpu.services.osd_service import OSDService

    def make(orig):
        def push(self, pool_id, ps, osd, oid, shard, *a, **kw):
            if kw.get("qos") == "client" and \
                    shard == self.map.pools[pool_id].size - 1:
                return None
            return orig(self, pool_id, ps, osd, oid, shard, *a, **kw)
        return push
    return _patched(OSDService, "_push_shard", make)


def write_unchanged_state():
    """A shard write answers success and stores nothing."""
    from ceph_tpu.services.osd_service import OSDService

    def make(_orig):
        def write(self, msg):
            return {"ok": True, "epoch": self.epoch}
        return write
    return _patched(OSDService, "_do_shard_write", make)


def _kernel(transform):
    from ceph_tpu.ec import pallas_kernels as PK

    def make(orig):
        def fused(bm_bits, data, interpret=False):
            return transform(np.array(orig(bm_bits, data,
                                           interpret=interpret)))
        return fused
    return _patched(PK, "fused_gf2_matmul_w8", make)


def kernel_half_batch():
    """The GF kernel computes the first half of its lanes only; the
    rest of its output is left as zeros."""
    def half(out):
        out[:, out.shape[1] // 2:] = 0
        return out
    return _kernel(half)


def kernel_altered_answer():
    """The first byte of every row of every GF kernel output is
    flipped."""
    def alter(out):
        out[:, 0] ^= 0x5A
        return out
    return _kernel(alter)


def read_unchanged_state():
    """Every read returns the bytes of the first read the client made."""
    from ceph_tpu.services.client import Client

    def make(orig):
        first = []

        def get(self, *a, **kw):
            data = orig(self, *a, **kw)
            if not first:
                first.append(data)
            return first[0]
        return get
    return _patched(Client, "get", make)


def read_no_decode():
    """Control: a degraded read returns the surviving data chunks with
    the missing ones as zeros instead of decoding them."""
    from ceph_tpu.ec.interface import ErasureCode

    def make(_orig):
        def decode_concat(self, chunks):
            k = self.get_data_chunk_count()
            size = len(next(iter(chunks.values())))
            return b"".join(
                np.asarray(chunks[i], np.uint8).tobytes()
                if i in chunks else bytes(size) for i in range(k))
        return decode_concat
    return _patched(ErasureCode, "decode_concat", make)


CONTROLS = {
    "placement_epochs": placement_stale_epoch,
    "rados_write": write_ack_at_k,
    "rados_degraded_read": read_no_decode,
}

FAULTS = {
    "placement_epochs": {"unchanged_state": placement_unchanged_state,
                         "half_batch": placement_half_batch,
                         "altered_answer": placement_altered_answer},
    "rados_write": {"unchanged_state": write_unchanged_state,
                    "half_batch": kernel_half_batch,
                    "altered_answer": kernel_altered_answer},
    "rados_degraded_read": {"unchanged_state": read_unchanged_state,
                            "half_batch": kernel_half_batch,
                            "altered_answer": kernel_altered_answer},
}

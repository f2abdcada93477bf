"""ECUtil — stripe math and the stripe-looped EC data path.

The bridge from "codec" to "data path" (src/osd/ECUtil.{h,cc}):

- ``StripeInfo``: the logical↔chunk offset arithmetic of
  ``stripe_info_t`` (ECUtil.h:27-80) — stripe_width bytes of logical
  object data become one chunk_size slice on each of the k+m shards.
- ``encode``: ECUtil::encode (ECUtil.cc:123-162).  The reference loops
  stripes calling ``ErasureCodeInterface::encode`` once per stripe and
  appends per-shard buffers; byte lanes are independent in the GF
  engine, so here ALL stripes encode in one batched call — the
  per-shard concatenation the reference builds buffer-by-buffer is just
  a reshape.
- ``decode``: ECUtil.cc:50-121 — reconstruct the needed shards for
  every stripe at once from whatever shard slices survive.  This
  batched many-stripes decode IS the recovery shape (SURVEY §2.6
  recovery-concurrency row: ECBackend::recover_object fetching
  minimum_to_decode then decoding stripe runs).
- ``HashInfo``: cumulative per-shard crc32c (ECUtil.h:164-180), crc32c
  (Castagnoli) matching the reference's ceph_crc32c.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Set

import numpy as np

from .interface import ErasureCode, ErasureCodeError


class StripeInfo:
    """stripe_info_t (ECUtil.h:27-80): ``stripe_size`` data chunks per
    stripe (k), ``stripe_width`` logical bytes per stripe."""

    def __init__(self, stripe_size: int, stripe_width: int):
        if stripe_width % stripe_size:
            raise ValueError("stripe_width must be a multiple of "
                             "stripe_size")
        self.stripe_width = stripe_width
        self.chunk_size = stripe_width // stripe_size

    def logical_offset_is_stripe_aligned(self, logical: int) -> bool:
        return logical % self.stripe_width == 0

    def logical_to_prev_chunk_offset(self, offset: int) -> int:
        return (offset // self.stripe_width) * self.chunk_size

    def logical_to_next_chunk_offset(self, offset: int) -> int:
        return ((offset + self.stripe_width - 1)
                // self.stripe_width) * self.chunk_size

    def logical_to_prev_stripe_offset(self, offset: int) -> int:
        return offset - (offset % self.stripe_width)

    def logical_to_next_stripe_offset(self, offset: int) -> int:
        rem = offset % self.stripe_width
        return offset + (self.stripe_width - rem) if rem else offset

    def aligned_logical_offset_to_chunk_offset(self, offset: int) -> int:
        assert offset % self.stripe_width == 0
        return (offset // self.stripe_width) * self.chunk_size

    def aligned_chunk_offset_to_logical_offset(self, offset: int) -> int:
        assert offset % self.chunk_size == 0
        return (offset // self.chunk_size) * self.stripe_width

    def offset_len_to_stripe_bounds(self, offset: int,
                                    length: int) -> tuple:
        off = self.logical_to_prev_stripe_offset(offset)
        ln = self.logical_to_next_stripe_offset((offset - off) + length)
        return off, ln


def sinfo_for(code: ErasureCode, stripe_unit: int = 4096) -> StripeInfo:
    """The OSD's stripe geometry for a code: chunk = stripe_unit bytes,
    width = k * stripe_unit (PGBackend::get_ec_stripe semantics)."""
    k = code.get_data_chunk_count()
    return StripeInfo(k, k * stripe_unit)


def encode(sinfo: StripeInfo, code: ErasureCode,
           data: bytes | np.ndarray,
           want: Iterable[int] | None = None
           ) -> Dict[int, np.ndarray]:
    """ECUtil::encode: logical buffer (multiple of stripe_width) ->
    per-shard concatenated chunk buffers — all stripes in ONE engine
    call."""
    buf = np.frombuffer(data, np.uint8) if isinstance(
        data, (bytes, bytearray)) else np.asarray(data, np.uint8).ravel()
    if len(buf) % sinfo.stripe_width:
        raise ValueError("input must be stripe-aligned "
                         "(ECUtil.cc:133 assert)")
    k = code.get_data_chunk_count()
    n = code.get_chunk_count()
    cs = sinfo.chunk_size
    nstripes = len(buf) // sinfo.stripe_width
    if want is None:
        want = range(n)
    if nstripes == 0:
        return {i: np.zeros(0, np.uint8) for i in want}

    # [stripe, chunk_j, byte] -> per-shard concatenation [chunk_j,
    # stripe*cs]: equivalent to the reference's per-stripe loop with
    # claim_append, because byte lanes are independent in the engine
    stripes = buf.reshape(nstripes, k, cs).transpose(1, 0, 2)
    shard_data = stripes.reshape(k, nstripes * cs)

    chunks: Dict[int, np.ndarray] = {
        code.chunk_index(i): shard_data[i] for i in range(k)}
    for i in range(k, n):
        chunks[code.chunk_index(i)] = np.zeros(nstripes * cs, np.uint8)
    code.encode_chunks(set(want), chunks)
    return {i: chunks[i] for i in want}


def decode(sinfo: StripeInfo, code: ErasureCode,
           to_decode: Dict[int, np.ndarray],
           need: Iterable[int]) -> Dict[int, np.ndarray]:
    """ECUtil::decode: per-shard concatenated slices in, reconstructed
    shard buffers out — every stripe decoded in one engine call."""
    need = set(need)
    avail = set(to_decode)
    lengths = {len(np.asarray(v).ravel()) for v in to_decode.values()}
    if len(lengths) != 1:
        raise ValueError("all shard buffers must be equal length")
    (length,) = lengths
    if length % sinfo.chunk_size:
        raise ValueError("shard buffers must be chunk-aligned")
    # feasibility via the code's own minimum_to_decode
    code.minimum_to_decode(need, avail)
    chunks = {i: np.asarray(v, np.uint8).ravel()
              for i, v in to_decode.items()}
    out = code.decode(need, chunks)
    return {i: np.asarray(out[i], np.uint8) for i in need}


def recover_stripes(sinfo: StripeInfo, code: ErasureCode,
                    surviving: Dict[int, np.ndarray],
                    lost: Iterable[int]) -> Dict[int, np.ndarray]:
    """The batched recovery path (ECBackend::recover_object shape,
    ECBackend.cc:757/589): reconstruct the lost shards for a run of
    stripes from the survivors, one launch."""
    return decode(sinfo, code, surviving, set(lost))


# -- crc32c (Castagnoli) — HashInfo (ECUtil.h:164-180) ----------------------

_native_crc = None


def _native_crc32c():
    """The slicing-by-8 C engine (native/crush_host.cpp crc32c_sb8) —
    the src/common/crc32c.h hot-path role.  A failed native build
    raises (``native.ensure_built``)."""
    global _native_crc
    if _native_crc is None:
        import ctypes

        from ..crush.native import ensure_built

        lib = ensure_built()
        lib.crc32c_sb8.restype = ctypes.c_uint32
        lib.crc32c_sb8.argtypes = [
            ctypes.c_uint32,
            np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
            ctypes.c_int64]
        _native_crc = lib.crc32c_sb8
    return _native_crc


def crc32c(data: bytes | np.ndarray, crc: int = 0xFFFFFFFF) -> int:
    """ceph_crc32c semantics (seed as passed, no final xor; the OSD
    uses -1)."""
    buf = np.frombuffer(data, np.uint8) if isinstance(
        data, (bytes, bytearray, memoryview)) \
        else np.ascontiguousarray(np.asarray(data, np.uint8).ravel())
    return int(_native_crc32c()(crc & 0xFFFFFFFF, buf, len(buf)))


class HashInfo:
    """Cumulative per-shard crc32c of everything appended
    (ECUtil.h:164-180)."""

    def __init__(self, n_shards: int):
        self.total_chunk_size = 0
        self.cumulative_shard_hashes = [0xFFFFFFFF] * n_shards

    def append(self, old_size: int,
               to_append: Dict[int, np.ndarray]) -> None:
        assert old_size == self.total_chunk_size
        sizes = {len(np.asarray(v).ravel())
                 for v in to_append.values()}
        assert len(sizes) == 1
        for shard, buf in to_append.items():
            self.cumulative_shard_hashes[shard] = crc32c(
                buf, self.cumulative_shard_hashes[shard])
        self.total_chunk_size += sizes.pop()

    def get_chunk_hash(self, shard: int) -> int:
        return self.cumulative_shard_hashes[shard]

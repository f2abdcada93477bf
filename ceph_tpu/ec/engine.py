"""The TPU EC execution engine: every code family as one mod-2 matmul.

The reference executes EC three different ways — isa-l's table-driven
SSE/AVX GF multiplies (ErasureCodeIsa.cc:129 ec_encode_data), jerasure's
matrix loops, and jerasure's bitmatrix XOR schedules
(jerasure_schedule_encode, ErasureCodeJerasure.cc:264).  None of those
map to a TPU.  What does: every one of these codes is GF(2)-linear, so
encode/decode is a single 0/1 matrix applied over bit rows — an int8
matmul on the MXU with a mod-2 epilogue.  Three data layouts cover the
whole zoo:

- ``w8``  — GF(2^8) matrix codes: chunk bytes → 8 bit planes.
- ``w16/w32`` — GF(2^16/2^32) RS: chunk viewed as little-endian words →
  w bit planes (matches jerasure's word-in-memory convention).
- ``packet(w, psize)`` — bitmatrix/schedule codes (cauchy, liberation,
  blaum_roth, liber8tion): chunk = blocks of w packets of psize bytes;
  packet-rows are the GF(2) vector elements; bytes XOR bitwise, so the
  byte axis is unpacked to bits for the matmul and repacked after.

Encode: parity_rows = CB @ data_rows (CB = coding bitmatrix, w*m x w*k).
Decode: pick k surviving chunks, stack their row-blocks of the full
[I; CB] matrix, invert over GF(2) on host (cached per erasure
signature — the ErasureCodeIsaTableCache flow, ErasureCodeIsa.cc:227),
one matmul recovers all data rows; missing parity is re-encoded.
"""

from __future__ import annotations

import functools
import time
from typing import Dict, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..common import device_metrics
from ..common.perf_counters import collection
from .gfw import gf2_mat_inv

_BITS8 = np.arange(8, dtype=np.uint8)

# -- instrumentation (process-global: the MXU kernels are shared by
# every in-process daemon; served via each daemon's `perf dump`, which
# merges the global collection).  First-call JIT compile cost books
# under jit_compiles/jit_compile_time — keyed by kernel signature, the
# same shape key XLA's own jit cache uses — so steady-state dispatch
# histograms are not polluted by tracing+compilation.  The times are
# host wall time of each dispatch, not device time: kernels run
# asynchronously and nothing here waits for them.
_pc = collection().create("ec.engine")
for _k in ("encode_ops", "decode_ops", "encode_bytes",
           "decode_bytes", "jit_compiles", "device_launches",
           "interpret_launches"):
    _pc.add_u64_counter(_k)
for _k in ("encode_time", "decode_time", "jit_compile_time"):
    _pc.add_time(_k)
_pc.add_histogram("encode_lat")
_pc.add_histogram("decode_lat")
# stripes per batched-encode dispatch (value 1 = the per-stripe path):
# the depth-1-regression canary the aio smoke test gates on
_pc.add_histogram("ec_batch_size", min_value=1)
# signatures already traced+compiled; set membership races only
# double-count a compile, they never corrupt (CPython set ops are
# atomic)
_seen_sigs: set = set()


def book_batch(n_stripes: int) -> None:
    """Record one batched-encode dispatch of ``n_stripes`` stripes
    (the EncodeBatcher and the engine-level batched path both book
    here; per-stripe fallbacks book 1)."""
    _pc.hist_add("ec_batch_size", n_stripes)


def _data_plane_mesh():
    """The process-default data-plane mesh, when one is installed
    (parallel.placement.set_data_plane_mesh).  Reads the
    dependency-free holder, NOT parallel.placement — that module
    pulls the CRUSH mapper (and its x64 config flip), which
    plugin-only processes must never pay for on the encode path."""
    from ..parallel.meshctx import get_mesh

    return get_mesh()


def encode_batched_sharded(code: "BitCode", stripes, mesh,
                           axis_name: str = None):
    """Module-level handle for ``BitCode.encode_batched_sharded`` —
    the name the jaxcheck contract registry and the multichip bench
    lane address the sharded kernel by."""
    return code.encode_batched_sharded(stripes, mesh,
                                       axis_name=axis_name)


def _account(kind: str, sig: tuple, dt: float, nbytes: int,
             jitted: bool = True, nbytes_out: int = 0,
             device_ids=None, interpret: bool = False) -> None:
    """Shared by every EC execution engine (the jitted bit-plane path
    here and native_gf's table engine, which passes jitted=False —
    it has no compile step to separate out).  ``dt`` is the host wall
    time of the call: for a jitted engine, the dispatch, since the
    kernel runs asynchronously and is not waited for.  Jitted launches also
    book into the device plane: the input bytes cross host->device,
    the materialized output crosses back (common/device_metrics.py,
    per-shape-signature).  Mesh launches pass ``device_ids`` so every
    participating chip books a per-device row too.  A Pallas kernel
    run in interpret mode (``interpret``) books ``interpret_launches``
    instead of ``device_launches``: only a compiled kernel counts as a
    device launch."""
    _pc.inc(f"{kind}_ops")
    _pc.inc(f"{kind}_bytes", nbytes)
    if jitted and sig not in _seen_sigs:
        _seen_sigs.add(sig)
        _pc.inc("jit_compiles")
        _pc.tinc("jit_compile_time", dt)
    else:
        _pc.tinc(f"{kind}_time", dt)
        _pc.hist_add(f"{kind}_lat", dt)
    if jitted:
        if interpret:
            _pc.inc("interpret_launches")
        else:
            _pc.inc("device_launches")
        if device_ids:
            device_metrics.record_mesh_launch(
                "ec.engine", f"{kind}:{sig}", dt, device_ids,
                h2d_bytes=nbytes, d2h_bytes=nbytes_out)
        else:
            device_metrics.record_launch(
                "ec.engine", f"{kind}:{sig}", dt,
                h2d_bytes=nbytes, d2h_bytes=nbytes_out)


@jax.jit
def _mod2_matmul(bm, planes):
    """(R, C) 0/1 int8 @ (C, N) 0/1 int8 -> (R, N) 0/1 uint8.
    Products are 0/1 and C <= a few thousand << 2^31, so the i32
    accumulator is exact; the &1 is the mod-2 epilogue XLA fuses."""
    acc = jax.lax.dot_general(
        bm.astype(jnp.int8), planes.astype(jnp.int8),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    return (acc & 1).astype(jnp.uint8)


def _unpack_bytes(data):
    """u8[r, L] -> 0/1 u8[8r, L], row-major (row, bit), LSB first."""
    r, L = data.shape
    planes = (data[:, None, :] >> _BITS8[None, :, None]) & jnp.uint8(1)
    return planes.reshape(8 * r, L)


def _pack_bytes(planes):
    """0/1 u8[8r, L] -> u8[r, L]."""
    r8, L = planes.shape
    p = planes.reshape(r8 // 8, 8, L)
    return jnp.sum(p << _BITS8[None, :, None], axis=1, dtype=jnp.uint8)


class Layout:
    """Chunk bytes <-> GF(2) row-block transform for one code family."""

    def __init__(self, w: int, packetsize: int = 0):
        self.w = w
        self.packetsize = packetsize
        self.is_packet = packetsize > 0

    def check(self, L: int):
        if self.is_packet:
            blk = self.w * self.packetsize
            if L % blk:
                raise ValueError(
                    f"chunk size {L} not a multiple of w*packetsize={blk}")
        else:
            if L % (self.w // 8):
                raise ValueError(
                    f"chunk size {L} not a multiple of word size "
                    f"{self.w // 8}")

    def to_rows(self, chunks):
        """u8[n, L] -> 0/1 u8[n*w, N]: each chunk becomes w GF(2) rows."""
        n, L = chunks.shape
        w = self.w
        if self.is_packet:
            # packet-rows of bytes; the byte's bit axis folds into N so
            # the matmul XORs whole packets bitwise
            ps = self.packetsize
            nb = L // (w * ps)
            r = chunks.reshape(n, nb, w, ps).transpose(0, 2, 1, 3)
            r = r.reshape(n * w, nb * ps)
            bits = (r[:, None, :] >> _BITS8[None, :, None]) & jnp.uint8(1)
            return bits.reshape(n * w, 8 * nb * ps)
        if w == 8:
            return _unpack_bytes(chunks)
        # little-endian words: byte b of a word carries bits 8b..8b+7
        wb = w // 8
        nw = L // wb
        words = chunks.reshape(n, nw, wb)
        planes = (words[:, :, :, None] >> _BITS8[None, None, None, :]) \
            & jnp.uint8(1)
        # [n, nw, wb, 8] -> [n, w, nw] rows (bit index = 8*byte + bit)
        return planes.transpose(0, 2, 3, 1).reshape(n * w, nw)

    def from_rows(self, rows, n: int, L: int):
        """Inverse of to_rows for n chunks of L bytes."""
        w = self.w
        if self.is_packet:
            ps = self.packetsize
            nb = L // (w * ps)
            bits = rows.reshape(n * w, 8, nb * ps)
            by = jnp.sum(bits << _BITS8[None, :, None], axis=1,
                         dtype=jnp.uint8)
            by = by.reshape(n, w, nb, ps).transpose(0, 2, 1, 3)
            return by.reshape(n, L)
        if w == 8:
            return _pack_bytes(rows)
        wb = w // 8
        nw = L // wb
        planes = rows.reshape(n, wb, 8, nw).transpose(0, 3, 1, 2)
        by = jnp.sum(planes << _BITS8[None, None, None, :], axis=3,
                     dtype=jnp.uint8)
        return by.reshape(n, L)


class BitCode:
    """A systematic GF(2)-linear code executed as MXU matmuls.

    ``coding_bm``: (w*m, w*k) 0/1 coding bitmatrix (rows produce the m
    parity chunks' row-blocks from the k data chunks' row-blocks).

    ``force_fused``: route w=8 byte layouts through the Pallas fused
    unpack→MXU→pack kernel unconditionally — compiled on TPU,
    interpret mode elsewhere (the registry's 'pallas-fused' engine).
    Without it the fused kernel still applies opportunistically on a
    TPU backend.
    """

    def __init__(self, k: int, m: int, coding_bm: np.ndarray,
                 layout: Layout, force_fused: bool = False):
        self.k, self.m = k, m
        self.layout = layout
        self.force_fused = force_fused
        if force_fused and (layout.is_packet or layout.w != 8):
            raise ValueError(
                "pallas-fused engine requires a plain byte (w=8) "
                "layout")
        w = layout.w
        assert coding_bm.shape == (w * m, w * k), coding_bm.shape
        self.coding_bm = np.asarray(coding_bm, np.uint8) & 1
        full = np.concatenate(
            [np.eye(w * k, dtype=np.uint8), self.coding_bm], axis=0)
        self.full_bm = full                      # ((k+m)w, kw)
        self._enc_dev = jnp.asarray(self.coding_bm)
        self._dec_cache: Dict[Tuple[int, ...], tuple] = {}
        self._mesh_cache: Dict[tuple, object] = {}

    # -- encode -------------------------------------------------------
    def _fused_w8(self):
        """The Pallas fused path applies on TPU for plain byte (w=8)
        layouts — the bandwidth-bound RS/isa shape — or anywhere when
        ``force_fused`` selected it (interpret mode off-TPU); None
        otherwise."""
        if self.layout.is_packet or self.layout.w != 8:
            return None
        from . import pallas_kernels as PK

        return PK if (self.force_fused or PK.on_tpu()) else None

    def encode(self, data):
        """u8[k, L] -> parity u8[m, L]."""
        data = jnp.asarray(data)
        assert data.shape[0] == self.k
        self.layout.check(data.shape[1])
        t0 = time.monotonic()
        pk = self._fused_w8()
        interp = pk is not None and not pk.on_tpu()
        if pk is not None:
            out = pk.fused_gf2_matmul_w8(self._enc_dev, data,
                                         interpret=interp)
        else:
            rows = self.layout.to_rows(data)
            out = self.layout.from_rows(
                _mod2_matmul(self._enc_dev, rows), self.m,
                data.shape[1])
        _account("encode",
                 ("enc", self.coding_bm.shape, tuple(data.shape),
                  self.layout.w, self.layout.packetsize,
                  pk is not None),
                 time.monotonic() - t0, int(data.size),
                 nbytes_out=self.m * int(data.shape[1]),
                 interpret=interp)
        return out

    def encode_batched(self, stripes, mesh=None):
        """u8[B, k, L] -> parity u8[B, m, L]: ONE kernel dispatch for
        B same-shape stripes.

        Every layout's GF(2) rows treat byte (or word, or packet)
        columns independently, so the B stripes concatenate along the
        byte axis — chunk row i becomes the concat of every stripe's
        chunk i — run through the SAME jitted kernel as ``encode``
        (one dispatch; the compile signature is keyed by (k, B*L), so
        callers batching at fixed sizes stay inside the recompile
        budget), and the parities split back.  Byte-identical to B
        per-stripe ``encode`` calls: the matmul is exact integer
        arithmetic over disjoint columns.

        ``mesh``: an explicit ``jax.sharding.Mesh`` — or, when None,
        the process-default ``parallel.placement.data_plane_mesh()``
        — with more than one device routes through
        ``encode_batched_sharded``: the stripe batch axis sharded
        across the chips, still one launch, still byte-identical."""
        if mesh is None:
            mesh = _data_plane_mesh()
        if mesh is not None and \
                int(np.asarray(mesh.devices).size) > 1:  # jax-ok: mesh.devices is a host-side numpy array of Device handles
            return self.encode_batched_sharded(stripes, mesh)
        stripes = jnp.asarray(stripes)
        B, k, L = stripes.shape
        assert k == self.k, (k, self.k)
        self.layout.check(L)
        t0 = time.monotonic()
        flat = stripes.transpose(1, 0, 2).reshape(self.k, B * L)
        pk = self._fused_w8()
        interp = pk is not None and not pk.on_tpu()
        if pk is not None:
            out = pk.fused_gf2_matmul_w8(self._enc_dev, flat,
                                         interpret=interp)
        else:
            rows = self.layout.to_rows(flat)
            out = self.layout.from_rows(
                _mod2_matmul(self._enc_dev, rows), self.m, B * L)
        out = out.reshape(self.m, B, L).transpose(1, 0, 2)
        _account("encode",
                 ("encb", self.coding_bm.shape, (B, k, L),
                  self.layout.w, self.layout.packetsize,
                  pk is not None),
                 time.monotonic() - t0, int(stripes.size),
                 nbytes_out=B * self.m * L, interpret=interp)
        book_batch(B)
        return out

    def _mesh_fn(self, mesh, axis_name: str):
        """The jitted stripe-batch-sharded encode for one mesh: the
        batch axis carries ``NamedSharding(mesh, P(axis))``, every
        chip encodes its stripe shard against the replicated coding
        bitmatrix, and no collective ever runs — the DrJAX
        data-parallel leaf computation with an empty reduce."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        key = (mesh, axis_name)
        fn = self._mesh_cache.get(key)
        if fn is None:
            shard = NamedSharding(mesh, P(axis_name, None, None))
            layout, enc, m = self.layout, self._enc_dev, self.m

            def one(data):
                L = data.shape[1]
                rows = layout.to_rows(data)
                return layout.from_rows(_mod2_matmul(enc, rows), m, L)

            fn = jax.jit(jax.vmap(one), in_shardings=(shard,),
                         out_shardings=shard)
            self._mesh_cache[key] = fn
        return fn

    def encode_batched_sharded(self, stripes, mesh,
                               axis_name: str = None):
        """The mesh path of ``encode_batched``: u8[B, k, L] with the
        stripe batch axis sharded across ``mesh``'s devices — one pjit
        launch, parity u8[B, m, L] sharded the same way.

        B is pow2-padded with zero stripes up to a multiple of the
        mesh size (a zero stripe's parity is zero for every linear
        code; pad outputs are sliced off), so batch-shape signatures
        stay inside the recompile budget and non-divisible batches
        never fork.  Byte-identical to B per-stripe ``encode`` calls:
        each stripe is encoded by exactly the per-stripe kernel
        composition, vmapped."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.meshctx import pad_batch

        stripes = jnp.asarray(stripes)
        B, k, L = stripes.shape
        assert k == self.k, (k, self.k)
        self.layout.check(L)
        axis_name = axis_name or mesh.axis_names[0]
        n_dev = int(np.asarray(mesh.devices).size)  # jax-ok: mesh.devices is a host-side numpy array of Device handles
        Bp = pad_batch(B, n_dev)
        t0 = time.monotonic()
        if Bp != B:
            stripes = jnp.concatenate(
                [stripes, jnp.zeros((Bp - B, k, L), jnp.uint8)],
                axis=0)
        pk = self._fused_w8()
        interp = pk is not None and not pk.on_tpu()
        if pk is not None:
            # fused mesh path: split the padded batch evenly, flatten
            # each shard along the byte axis ((b, k, L) -> (k, b*L) —
            # GF(2) matmul columns are independent), and run the SAME
            # fused kernel committed to each chip.  Byte-identical to
            # the vmapped path: identical arithmetic over disjoint
            # columns.
            devs = list(np.asarray(mesh.devices).ravel())  # jax-ok: mesh.devices is a host-side numpy array of Device handles
            per = Bp // n_dev
            parts = []
            for d, grp in zip(devs, jnp.split(stripes, n_dev)):
                flat = jax.device_put(
                    grp.transpose(1, 0, 2).reshape(k, per * L), d)
                par = pk.fused_gf2_matmul_w8(self._enc_dev, flat,
                                             interpret=interp)
                parts.append(np.asarray(par).reshape(  # jax-ok: per-device gather — parts are committed to distinct chips and must meet on host
                    self.m, per, L).transpose(1, 0, 2))
            # per-device results are committed to distinct chips;
            # gather on host (the callers materialize anyway)
            out = np.concatenate(parts, axis=0)
        else:
            sharded = jax.device_put(
                stripes, NamedSharding(mesh, P(axis_name, None, None)))
            out = self._mesh_fn(mesh, axis_name)(sharded)
        if Bp != B:
            out = out[:B]
        _account("encode",
                 ("encb_mesh", self.coding_bm.shape, (Bp, k, L),
                  self.layout.w, self.layout.packetsize, n_dev,
                  pk is not None),
                 time.monotonic() - t0, B * k * L,
                 nbytes_out=B * self.m * L,
                 device_ids=[int(d.id) for d in
                             np.asarray(mesh.devices).ravel()],  # jax-ok: mesh.devices is a host-side numpy array of Device handles
                 interpret=interp)
        book_batch(B)
        return out

    def all_chunks(self, data):
        data = jnp.asarray(data)
        return jnp.concatenate([data, self.encode(data)], axis=0)

    # -- decode -------------------------------------------------------
    def _decode_mats(self, present: Tuple[int, ...]):
        """Host-inverted GF(2) decode matrix for k survivors, cached by
        erasure signature (the IsaTableCache flow)."""
        mats = self._dec_cache.get(present)
        if mats is None:
            w = self.layout.w
            rows = np.concatenate(
                [self.full_bm[c * w:(c + 1) * w] for c in present], axis=0)
            inv = gf2_mat_inv(rows)
            mats = (jnp.asarray(inv),)
            if len(self._dec_cache) >= 512:   # LRU-ish bound
                self._dec_cache.pop(next(iter(self._dec_cache)))
            self._dec_cache[present] = mats
        return mats

    def decode_data(self, chunks: Dict[int, "jnp.ndarray"]):
        """Recover all k data chunks from any k available chunks.
        ``chunks``: {chunk_id: u8[L]}."""
        avail = sorted(chunks)
        if len(avail) < self.k:
            raise ValueError("need at least k chunks")
        present = tuple(avail[:self.k])
        (inv,) = self._decode_mats(present)
        stack = jnp.stack([jnp.asarray(chunks[i]) for i in present])
        L = stack.shape[1]
        self.layout.check(L)
        t0 = time.monotonic()
        pk = self._fused_w8()
        interp = pk is not None and not pk.on_tpu()
        if pk is not None:
            out = pk.fused_gf2_matmul_w8(inv, stack, interpret=interp)
        else:
            rows = self.layout.to_rows(stack)
            out = self.layout.from_rows(_mod2_matmul(inv, rows),
                                        self.k, L)
        _account("decode",
                 ("dec", inv.shape, tuple(stack.shape),
                  self.layout.w, self.layout.packetsize,
                  pk is not None),
                 time.monotonic() - t0, int(stack.size),
                 nbytes_out=self.k * int(L), interpret=interp)
        return out

    def decode(self, want: Sequence[int], chunks: Dict[int, "jnp.ndarray"]):
        """Reconstruct the wanted chunk ids (data and/or parity).
        Returns {chunk_id: u8[L]}."""
        have = dict(chunks)
        missing = [i for i in want if i not in have]
        if missing:
            data = self.decode_data(have)
            for i in range(self.k):
                if i not in have:
                    have[i] = data[i]
            par_missing = [i for i in missing if i >= self.k]
            if par_missing:
                parity = self.encode(data)
                for i in par_missing:
                    have[i] = parity[i - self.k]
        return {i: have[i] for i in want}

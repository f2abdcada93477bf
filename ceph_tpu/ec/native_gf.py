"""Native GF(2^8) matmul — the EC engine's CPU twin.

The isa-l role on the host: RS encode/decode as table-driven GF(2^8)
matrix application (native/crush_host.cpp gf8_matmul, OpenMP over
rows).  Two consumers:

- the bench's CPU EC figure and host tools;
- the plugin registry's w=8 matrix techniques (jerasure RS, isa),
  via :class:`NativeMatrixCode` — the OSD/client data path operates
  on per-op chunks far below the size where accelerator dispatch
  pays for itself, so the host engine is the default there EVEN on
  a TPU host (CEPH_TPU_EC_ENGINE=bitplane opts back into the
  array/Pallas engine, which remains the large-batch bench path).

Parity is identical to the array engines by construction: both apply
the SAME generator matrices (gf.py) over the same field (poly 0x11D),
pinned by tests (tests/test_native_gf.py cross-engine byte equality).
"""

from __future__ import annotations

import ctypes
from typing import Dict, Sequence

import numpy as np

from . import gf
from ..crush.native import ensure_built

_u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
_wired = False


def _lib():
    global _wired
    lib = ensure_built()
    if not _wired:
        lib.gf8_matmul.restype = ctypes.c_int
        lib.gf8_matmul.argtypes = [
            ctypes.c_int, ctypes.c_int, _u8p, _u8p, _u8p,
            ctypes.c_int64,
        ]
        _wired = True
    return lib


def gf8_matmul(mat: np.ndarray, data: np.ndarray) -> np.ndarray:
    """(rows, k) GF(2^8) matrix @ u8[k, L] -> u8[rows, L]."""
    lib = _lib()
    mat = np.ascontiguousarray(mat, np.uint8)
    data = np.ascontiguousarray(data, np.uint8)
    rows, k = mat.shape
    assert data.shape[0] == k
    out = np.zeros((rows, data.shape[1]), np.uint8)
    lib.gf8_matmul(rows, k, mat, data, out,
                   np.int64(data.shape[1]))
    return out


ENGINES = ("native", "bitplane", "pallas-fused")


def engine_choice(profile_engine: str = "") -> str:
    """Which engine the plugin registry should put behind w=8 MATRIX
    techniques: 'native' (the GF(2^8) table engine — the isa-l role,
    7-40x the portable bit-plane engine on CPU) unless overridden.
    Mirrors the reference's plugin-selection rationale
    (src/erasure-code/isa/ErasureCodeIsa.cc:333-336: pick the fastest
    verified engine for the shape).

    ``profile_engine`` is the pool profile's ``engine=`` key and wins
    over the process-wide CEPH_TPU_EC_ENGINE env override.  Choices:
    'native', 'bitplane' (the array/XLA engine), and 'pallas-fused'
    (the fused unpack→MXU→pack kernel — compiled on TPU, interpret
    mode on CPU; byte-identical to bitplane by the corpus tests)."""
    import os

    forced = profile_engine or os.environ.get("CEPH_TPU_EC_ENGINE", "")
    if forced and forced not in ENGINES:
        raise RuntimeError(
            f"unknown EC engine {forced!r}; have {list(ENGINES)}")
    return forced or "native"


class NativeMatrixCode:
    """BitCode-compatible facade over the native GF(2^8) engine for
    w=8 matrix techniques (jerasure reed_sol_van/reed_sol_r6_op w=8,
    every isa technique).

    Same generator matrices as the bit-plane engine — parity bytes are
    identical by construction (pinned by the EC corpus tests); only
    the execution engine differs.  Interface mirrors engine.BitCode:
    encode / all_chunks / decode_data / decode."""

    def __init__(self, k: int, m: int, coding_rows: np.ndarray):
        self.k, self.m = k, m
        rows = np.asarray(coding_rows, np.uint8)
        assert rows.shape == (m, k), rows.shape
        self.G = np.concatenate(
            [np.eye(k, dtype=np.uint8), rows], axis=0)
        self._dec_cache: Dict[tuple, np.ndarray] = {}

    def encode(self, data) -> np.ndarray:
        import time

        from .engine import _account

        data = np.asarray(data, np.uint8)
        assert data.shape[0] == self.k
        t0 = time.monotonic()
        out = gf8_matmul(self.G[self.k:], data)
        _account("encode", (), time.monotonic() - t0,
                 int(data.size), jitted=False)
        return out

    def all_chunks(self, data) -> np.ndarray:
        data = np.asarray(data, np.uint8)
        return np.concatenate([data, self.encode(data)], axis=0)

    def decode_data(self, chunks: Dict[int, np.ndarray]) -> np.ndarray:
        avail = sorted(chunks)
        if len(avail) < self.k:
            raise ValueError("need at least k chunks")
        present = tuple(avail[:self.k])
        dm = self._dec_cache.get(present)
        if dm is None:
            dm = np.asarray(gf.decode_matrix(self.G, list(present),
                                             self.k), np.uint8)
            if len(self._dec_cache) >= 512:  # IsaTableCache-style bound
                self._dec_cache.pop(next(iter(self._dec_cache)))
            self._dec_cache[present] = dm
        import time

        from .engine import _account

        stack = np.stack([np.asarray(chunks[i], np.uint8)
                          for i in present])
        t0 = time.monotonic()
        out = gf8_matmul(dm, stack)
        _account("decode", (), time.monotonic() - t0,
                 int(stack.size), jitted=False)
        return out

    def decode(self, want: Sequence[int],
               chunks: Dict[int, np.ndarray]) -> Dict[int, np.ndarray]:
        have = {i: np.asarray(c, np.uint8) for i, c in chunks.items()}
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


class NativeRS(NativeMatrixCode):
    """RS(k, m) on the native engine — mirrors rs_jax.RSCode's array
    API for host-side callers (a thin facade over NativeMatrixCode:
    one decode-cache implementation to keep in sync, not two)."""

    def __init__(self, k: int, m: int, technique: str = "reed_sol_van"):
        if technique in ("reed_sol_van", "vandermonde"):
            G = gf.rs_vandermonde_matrix(k, m)
        else:
            G = gf.rs_cauchy_matrix(k, m)
        super().__init__(k, m, np.asarray(G[k:], np.uint8))

    # rs_jax.RSCode decode signature: (chunks, erasures) -> data rows
    def decode(self, chunks: Dict[int, np.ndarray],  # type: ignore[override]
               erasures: Sequence[int]) -> np.ndarray:
        avail = {i: c for i, c in chunks.items()
                 if i not in set(erasures)}
        return self.decode_data(avail)

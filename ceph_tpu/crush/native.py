"""ctypes bridge to the native host mapper (native/crush_host.cpp).

The host-side hot loops (tools' scalar sweeps, the bench's CPU
fallback) run the batched C++ mapper over the SAME SoA arrays the TPU
mapper consumes; Python remains the source of truth (mapper_ref).

``ensure_built()`` runs the Makefile once per process (the toolchain
is part of the image); a failed build raises.
"""

from __future__ import annotations

import ctypes
import fcntl
import pathlib
import subprocess
from typing import List, Optional, Tuple

from ..analysis.lockdep import make_lock

import numpy as np

from .map import ChooseArgMap, CrushMap
from .map_arrays import encode_map

REPO = pathlib.Path(__file__).resolve().parents[2]
NATIVE_DIR = REPO / "native"
LIB_PATH = NATIVE_DIR / "libcrush_host.so"

_lock = make_lock("crush::native_build")
_lib: Optional[ctypes.CDLL] = None
_build_error: Optional[str] = None

_i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
_u32p = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")
_u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")


def ensure_built() -> ctypes.CDLL:
    """Load the native library, building it from the committed sources
    first.  A failed build raises: a library left over from an earlier
    build is never loaded in its place."""
    global _lib, _build_error
    with _lock:
        if _lib is not None:
            return _lib
        if _build_error is not None:
            raise RuntimeError(_build_error)
        # always run make: a no-op when fresh, and source edits never
        # load a stale library (the Makefile carries the deps).  The
        # flock serializes builds across processes (test workers).
        with open(NATIVE_DIR / ".build.lock", "w") as lk:
            fcntl.flock(lk, fcntl.LOCK_EX)
            proc = subprocess.run(["make", "-s"], cwd=str(NATIVE_DIR),
                                  capture_output=True, text=True,
                                  timeout=120)
        if proc.returncode != 0:
            _build_error = (f"native build failed (make exit "
                            f"{proc.returncode}): {proc.stderr[-2000:]}")
            raise RuntimeError(_build_error)
        lib = ctypes.CDLL(str(LIB_PATH))
        lib.crush_do_rule_batched.restype = ctypes.c_int
        lib.crush_do_rule_batched.argtypes = [
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int,
            _i32p, _i32p, _i32p, _i32p, _i32p, _i32p,
            _u32p, _u32p, _u32p, _u32p,
            _i32p, _u32p, _u8p,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int,
            ctypes.c_int, _i32p,
            _u32p, ctypes.c_int,
            ctypes.c_int, _u32p, ctypes.c_int,
            _i32p, _i32p,
        ]
        _lib = lib
        return _lib


class NativeMapper:
    """Batched do_rule on the C++ engine for one (map, choose_args)."""

    def __init__(self, cmap: CrushMap,
                 choose_args: Optional[ChooseArgMap] = None):
        self._lib = ensure_built()
        self.cmap = cmap
        self.static, arr = encode_map(cmap, choose_args)
        self._a = {
            "alg": np.ascontiguousarray(arr.alg, np.int32),
            "btype": np.ascontiguousarray(arr.btype, np.int32),
            "bhash": np.ascontiguousarray(arr.bhash, np.int32),
            "size": np.ascontiguousarray(arr.size, np.int32),
            "nnodes": np.ascontiguousarray(arr.nnodes, np.int32),
            "items": np.ascontiguousarray(arr.items, np.int32),
            "weights": np.ascontiguousarray(arr.weights, np.uint32),
            "sum_weights": np.ascontiguousarray(arr.sum_weights,
                                                np.uint32),
            "straws": np.ascontiguousarray(arr.straws, np.uint32),
            "node_weights": np.ascontiguousarray(arr.node_weights,
                                                 np.uint32),
            "arg_ids": np.ascontiguousarray(arr.arg_ids, np.int32),
            "arg_weights": np.ascontiguousarray(arr.arg_weights,
                                                np.uint32),
            "has_arg": np.ascontiguousarray(
                arr.has_arg.astype(np.uint8)),
        }

    def _steps(self, ruleno: int) -> np.ndarray:
        rule = self.cmap.rules[ruleno]
        return np.ascontiguousarray(
            [[s.op, s.arg1, s.arg2] for s in rule.steps], np.int32)

    def map_batch(self, ruleno: int, xs, result_max: int,
                  weight) -> Tuple[np.ndarray, np.ndarray]:
        """Same shape contract as BatchedMapper.map_batch."""
        xs = np.ascontiguousarray(xs, np.uint32)
        weight = np.ascontiguousarray(weight, np.uint32)
        steps = self._steps(ruleno)
        nx = len(xs)
        results = np.zeros((nx, result_max), np.int32)
        lens = np.zeros(nx, np.int32)
        st = self.static
        a = self._a
        t = st.tunables
        self._lib.crush_do_rule_batched(
            st.max_buckets, st.max_size, st.max_nodes,
            st.max_positions, st.max_devices,
            a["alg"], a["btype"], a["bhash"], a["size"], a["nnodes"],
            a["items"], a["weights"], a["sum_weights"], a["straws"],
            a["node_weights"], a["arg_ids"], a["arg_weights"],
            a["has_arg"],
            t[0], t[1], t[2], t[3], t[4], t[5],
            len(steps), steps,
            weight, len(weight),
            nx, xs, result_max,
            results, lens)
        return results, lens

    def do_rule(self, ruleno: int, x: int, result_max: int,
                weight) -> List[int]:
        res, lens = self.map_batch(
            ruleno, np.asarray([x], np.uint32), result_max, weight)
        return list(res[0, :lens[0]])

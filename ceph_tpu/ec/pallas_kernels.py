"""Pallas TPU kernel for the GF(2) bit-matmul — the EC hot op.

The engine executes every code as ``parity_planes = (BM @ planes) & 1``
(engine.py).  Under plain XLA that is three HLOs with the bit planes
MATERIALIZED in HBM: u8[k, L] unpacks to u8[8k, L] (an 8x byte blowup),
the MXU matmul reads it back, and the pack writes u8[m, L].  EC encode
is bandwidth-bound (SURVEY §7 hard part 4: the win must come from
table-gather/bandwidth + batching), so the 8x round-trip is the cost
that matters.

This kernel fuses unpack → MXU matmul → mod-2 → pack per L-tile inside
VMEM: HBM traffic is k bytes in + m bytes out per lane — the minimum.
The bit matrix (8m x 8k int8, a few KB) stays resident in VMEM across
the grid.

Used by ``engine.BitCode`` for w=8 byte layouts (the RS/isa bench
path) when running on a TPU backend; every other layout/platform rides
the XLA path.  ``interpret=True`` runs the same kernel on CPU for the
correctness tests.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

_LANE_TILE = 512  # lanes per grid step (multiple of 128)


def _kernel(bm_ref, data_ref, out_ref, *, k: int, m: int):
    """One L-tile: u8[k, T] -> u8[m, T] through the resident bit
    matrix int8[8m, 8k].

    All intermediate arithmetic stays int32: the real-TPU Mosaic
    lowering has no unsigned reductions ("Reductions over unsigned
    integers not implemented"), so the plane unpack/repack must not
    touch u8/u32 until the final store."""
    bits = jnp.arange(8, dtype=jnp.int32)
    d = data_ref[:].astype(jnp.int32)                 # i32[k, T]
    planes = (d[:, None, :] >> bits[None, :, None]) & 1
    planes = planes.reshape(8 * k, d.shape[-1])       # i32[8k, T]
    acc = jax.lax.dot_general(
        bm_ref[:], planes.astype(jnp.int8),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)             # i32[8m, T]
    par = (acc & 1).reshape(m, 8, d.shape[-1])        # i32
    out_ref[:] = jnp.sum(par << bits[None, :, None], axis=1,
                         dtype=jnp.int32).astype(jnp.uint8)


@functools.partial(jax.jit,
                   static_argnames=("k", "m", "interpret", "tile"))
def _call(bm, data, k: int, m: int, interpret: bool, tile: int):
    from jax.experimental import pallas as pl

    L = data.shape[1]
    grid = (L // tile,)
    # block indices stay int32: the CRUSH mapper turns x64 on for the
    # whole process, and Mosaic cannot lower an i64 index map
    return pl.pallas_call(
        functools.partial(_kernel, k=k, m=m),
        out_shape=jax.ShapeDtypeStruct((m, L), jnp.uint8),
        grid=grid,
        in_specs=[
            pl.BlockSpec((8 * m, 8 * k), lambda i: (i * 0, i * 0)),
            pl.BlockSpec((k, tile), lambda i: (i * 0, i)),
        ],
        out_specs=pl.BlockSpec((m, tile), lambda i: (i * 0, i)),
        interpret=interpret,
    )(bm, data)


def fused_gf2_matmul_w8(bm_bits, data, interpret: bool = False):
    """(8m, 8k) 0/1 matrix applied to u8[k, L] byte chunks -> u8[m, L],
    one fused kernel.  Pads L up to the lane tile and slices back."""
    bm = jnp.asarray(bm_bits, jnp.int8)
    data = jnp.asarray(data, jnp.uint8)
    rout8, rin8 = bm.shape
    assert rout8 % 8 == 0 and rin8 % 8 == 0
    k, m = rin8 // 8, rout8 // 8
    assert data.shape[0] == k
    L = data.shape[1]
    tile = _LANE_TILE  # fixed lane-aligned tile; short inputs pad up
    pad = (-L) % tile
    if pad:
        data = jnp.pad(data, ((0, 0), (0, pad)))
    out = _call(bm, data, k, m, interpret, tile)
    return out[:, :L] if pad else out


def on_tpu() -> bool:
    """The kernel compiles for the chip on the TPU backend and runs in
    interpret mode on every other backend.  A backend that fails to
    initialize raises here rather than passing for one without a
    chip."""
    return jax.default_backend() == "tpu"

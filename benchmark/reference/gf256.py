"""Plain Reed-Solomon over GF(2^8): the shards an isa ``reed_sol_van``
pool must store.

ISA-L's ``gf_gen_rs_matrix`` (the Vandermonde construction Ceph's isa
plugin uses for ``technique=reed_sol_van``): an identity on the k data
rows, and parity row i holds gen_i^j for j < k with gen_0 = 1 and
gen_{i+1} = 2 * gen_i, over the field polynomial x^8+x^4+x^3+x^2+1
(0x11d).  An object is padded with zeros to k * chunk_size and cut into
k contiguous data chunks (``ErasureCode::encode_prepare``); the chunk
size is the object over k, rounded up to the isa alignment of 32 bytes.
Nothing here imports the system under test.
"""

from __future__ import annotations

from typing import List

import numpy as np

POLY = 0x11D
ISA_ALIGNMENT = 32


def _tables():
    exp = [0] * 512
    log = [0] * 256
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    for i in range(255, 512):
        exp[i] = exp[i - 255]
    return exp, log


EXP, LOG = _tables()


def mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return EXP[LOG[a] + LOG[b]]


# MUL[c] maps every byte v to c * v: one table lookup per byte
MUL = np.array([[mul(c, v) for v in range(256)] for c in range(256)],
               dtype=np.uint8)


def rs_matrix(k: int, m: int) -> List[List[int]]:
    """The m parity rows of gf_gen_rs_matrix(k + m, k)."""
    rows = []
    gen = 1
    for _ in range(m):
        p, row = 1, []
        for _j in range(k):
            row.append(p)
            p = mul(p, gen)
        rows.append(row)
        gen = mul(gen, 2)
    return rows


def chunk_size(object_size: int, k: int) -> int:
    c = (object_size + k - 1) // k
    return c + (-c) % ISA_ALIGNMENT


def encode(data: bytes, k: int, m: int) -> List[bytes]:
    """The k data chunks and m parity chunks of one object."""
    L = chunk_size(len(data), k)
    buf = np.zeros(k * L, np.uint8)
    buf[:len(data)] = np.frombuffer(data, np.uint8)
    chunks = buf.reshape(k, L)
    out = [chunks[i].tobytes() for i in range(k)]
    for row in rs_matrix(k, m):
        acc = np.zeros(L, np.uint8)
        for j, c in enumerate(row):
            if c:
                acc ^= MUL[c][chunks[j]]
        out.append(acc.tobytes())
    return out

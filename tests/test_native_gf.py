"""Native GF(2^8) engine — parity-identical to the array engine."""

import numpy as np
import pytest

from ceph_tpu.ec.native_gf import NativeRS, gf8_matmul
from ceph_tpu.ec import gf
from ceph_tpu.ec.rs_jax import RSCode


def test_gf8_matmul_matches_reference():
    rng = np.random.default_rng(1)
    mat = rng.integers(0, 256, (3, 5), dtype=np.uint8)
    data = rng.integers(0, 256, (5, 700), dtype=np.uint8)
    got = gf8_matmul(mat, data)
    want = np.zeros((3, 700), np.uint8)
    for r in range(3):
        for j in range(5):
            want[r] ^= gf.gf_mul(
                np.full(700, mat[r, j], np.uint8), data[j])
    assert np.array_equal(got, want)


@pytest.mark.parametrize("k,m", [(4, 2), (8, 3)])
def test_native_rs_equals_engine(k, m):
    rng = np.random.default_rng(k)
    data = rng.integers(0, 256, (k, 4096), dtype=np.uint8)
    nat, eng = NativeRS(k, m), RSCode(k, m)
    assert np.array_equal(nat.encode(data),
                          np.asarray(eng.encode(data)))
    full = nat.all_chunks(data)
    chunks = {i: full[i] for i in range(k + m)}
    for erasures in ([0], [k - 1, k], list(range(m))):
        got = nat.decode(chunks, erasures)
        assert np.array_equal(got, data), erasures
    with pytest.raises(ValueError):
        nat.decode({0: full[0]}, [])


@pytest.mark.parametrize("plugin,profile", [
    ("jerasure", {"technique": "reed_sol_van", "k": "4", "m": "2",
                  "w": "8"}),
    ("jerasure", {"technique": "reed_sol_r6_op", "k": "4", "m": "2",
                  "w": "8"}),
    ("isa", {"technique": "reed_sol_van", "k": "4", "m": "2"}),
    ("isa", {"technique": "cauchy", "k": "6", "m": "3"}),
])
def test_plugin_engines_byte_identical(monkeypatch, plugin, profile):
    """The registry's engine dispatch must be invisible: the native
    GF(2^8) engine and the portable bit-plane engine produce the SAME
    chunk bytes for every w=8 matrix technique (whichever one a given
    machine defaults to, the other is covered here)."""
    from ceph_tpu.ec.registry import factory

    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, 1 << 14, dtype=np.uint8).tobytes()

    out = {}
    for engine in ("native", "bitplane"):
        monkeypatch.setenv("CEPH_TPU_EC_ENGINE", engine)
        code = factory(plugin, dict(profile))
        n = code.get_chunk_count()
        chunks = code.encode(range(n), data)
        out[engine] = [np.asarray(chunks[i]) for i in range(n)]
        # decode parity too: drop the first data + last parity chunk
        k = code.get_data_chunk_count()
        avail = {i: np.asarray(chunks[i]) for i in range(n)
                 if i not in (0, n - 1)}
        dec = code.decode({0, n - 1}, avail)
        assert np.array_equal(np.asarray(dec[0]),
                              np.asarray(chunks[0]))
        assert np.array_equal(np.asarray(dec[n - 1]),
                              np.asarray(chunks[n - 1]))
    for a, b in zip(out["native"], out["bitplane"]):
        assert np.array_equal(a, b)

"""OSDMap pipeline: batched JAX vs the scalar spec, across every stage.

Covers the scenario matrix of the reference's TestOSDMap.cc: down/out
OSDs, pg_upmap / pg_upmap_items rejection rules, pg_temp / primary_temp
overlays, primary affinity, replicated (shifting) vs erasure
(positional) pools, and non-power-of-two pg_num (stable_mod).
"""

import numpy as np
import pytest

import conftest  # noqa: F401

from ceph_tpu.crush.builder import sample_cluster_map
from ceph_tpu.crush.constants import CRUSH_ITEM_NONE as NONE
from ceph_tpu.osdmap.osdmap import (OSDMap, PgPool, POOL_TYPE_ERASURE,
                                    POOL_TYPE_REPLICATED)
from ceph_tpu.osdmap.pipeline_jax import PoolMapper


def make_map(n_osd=48, pg_num=128):
    cmap = sample_cluster_map(3, 4, 4)
    m = OSDMap(cmap)
    for o in range(n_osd):
        m.add_osd(o)
    m.pools[1] = PgPool(pool_type=POOL_TYPE_REPLICATED, size=3,
                        pg_num=pg_num, crush_rule=0)
    m.pools[2] = PgPool(pool_type=POOL_TYPE_ERASURE, size=6,
                        pg_num=pg_num, crush_rule=1)
    return m


def assert_match(m, pool_id, note=""):
    pm = PoolMapper(m, pool_id)
    out = pm.map_all()
    up = np.asarray(out["up"])
    ulen = np.asarray(out["up_len"])
    uprim = np.asarray(out["up_primary"])
    act = np.asarray(out["acting"])
    alen = np.asarray(out["acting_len"])
    aprim = np.asarray(out["acting_primary"])
    pool = m.pools[pool_id]
    for ps in range(pool.pg_num):
        w_up, w_up_p, w_act, w_act_p = m.pg_to_up_acting_osds(pool_id, ps)
        g_up = list(up[ps, :ulen[ps]])
        g_act = list(act[ps, :alen[ps]])
        assert g_up == w_up, (note, pool_id, ps, "up", g_up, w_up)
        assert uprim[ps] == w_up_p, (note, pool_id, ps, "up_primary")
        assert g_act == w_act, (note, pool_id, ps, "acting", g_act, w_act)
        assert aprim[ps] == w_act_p, (note, pool_id, ps, "act_primary")


def test_pipeline_program_is_named_jit_single_pg():
    """The device trace names each launch after the jitted function;
    the benchmark's ``placement.device_ns_per_pg`` finds the pipeline's
    launches by this name, so a rename would silence it.  Both the
    plain loops (16 PGs) and the straggler pass (4,096) are one
    program of that name."""
    import jax.numpy as jnp

    for pg_num in (16, 4096):
        m = make_map(pg_num=pg_num)
        pm = PoolMapper(m, 1)
        weight, state, paff = pm.runtime_args()
        ps = jnp.arange(pg_num, dtype=jnp.uint32)
        lowered = pm.fn.lower(pm.arrays, weight, state, paff, pm._trow,
                              ps)
        assert "module @jit_single_pg " in lowered.as_text()


def test_clean_cluster():
    m = make_map()
    assert_match(m, 1, "clean-rep")
    assert_match(m, 2, "clean-ec")


def test_down_and_out_osds():
    m = make_map()
    for o in (3, 17, 40):
        m.osd_state[o] &= ~2  # down
    m.osd_weight[8] = 0       # out
    m.osd_weight[22] = 0x8000  # half in
    assert_match(m, 1, "down-rep")
    assert_match(m, 2, "down-ec")


def test_nonexistent_osd():
    m = make_map()
    m.osd_state[30] = 0  # does not exist
    assert_match(m, 1, "dne-rep")
    assert_match(m, 2, "dne-ec")


def test_pg_upmap_full():
    m = make_map()
    m.pg_upmap[(1, 5)] = [1, 2, 3]
    m.pg_upmap[(1, 9)] = [4, 5, 44]
    m.pg_upmap[(2, 7)] = [0, 1, 2, 3, 4, 5]
    # rejected: target marked out
    m.osd_weight[10] = 0
    m.pg_upmap[(1, 11)] = [10, 11, 12]
    assert_match(m, 1, "upmap-rep")
    assert_match(m, 2, "upmap-ec")


def test_pg_upmap_items():
    m = make_map()
    pm0 = PoolMapper(m, 1)
    up0 = np.asarray(pm0.map_all()["up"])
    # remap first osd of pg 3 to osd 47, and a no-op pair
    src = int(up0[3, 0])
    m.pg_upmap_items[(1, 3)] = [(src, 47), (200, 5)]
    # pair whose target already appears in the set (must be skipped)
    src2 = int(up0[4, 0])
    tgt2 = int(up0[4, 1])
    m.pg_upmap_items[(1, 4)] = [(src2, tgt2)]
    # pair whose target is marked out (must be skipped)
    m.osd_weight[46] = 0
    src3 = int(up0[6, 1])
    m.pg_upmap_items[(1, 6)] = [(src3, 46)]
    assert_match(m, 1, "upmap-items")


def test_pg_temp_and_primary_temp():
    m = make_map()
    m.pg_temp[(1, 2)] = [9, 10, 11]
    m.pg_temp[(2, 2)] = [0, 1, 2, 3, 4, 5]
    m.primary_temp[(1, 8)] = 33
    m.pg_temp[(1, 12)] = [20, 21]
    m.primary_temp[(1, 12)] = 21
    # temp containing a down osd
    m.osd_state[10] &= ~2
    # temp that filters to empty (all down) -> falls back to up
    m.osd_state[44] &= ~2
    m.osd_state[45] &= ~2
    m.pg_temp[(1, 14)] = [44, 45]
    assert_match(m, 1, "temp-rep")
    assert_match(m, 2, "temp-ec")


def test_primary_affinity():
    m = make_map()
    m.set_primary_affinity(0, 0)        # never primary
    m.set_primary_affinity(7, 0x8000)   # half
    m.set_primary_affinity(13, 0x4000)  # quarter
    assert_match(m, 1, "paff-rep")
    assert_match(m, 2, "paff-ec")
    # osd.0 must never be primary where alternatives exist
    pm = PoolMapper(m, 1)
    out = pm.map_all()
    uprim = np.asarray(out["up_primary"])
    ulen = np.asarray(out["up_len"])
    assert not ((uprim == 0) & (ulen > 1)).any()


def test_non_pow2_pg_num():
    m = make_map(pg_num=100)  # stable_mod split domain
    assert_match(m, 1, "pg100-rep")
    m2 = make_map(pg_num=96)
    m2.pools[2].pgp_num = 48  # pgp < pg
    assert_match(m2, 2, "pgp48-ec")


def test_everything_at_once():
    m = make_map()
    for o in (3, 17):
        m.osd_state[o] &= ~2
    m.osd_weight[8] = 0
    m.set_primary_affinity(7, 0x8000)
    m.pg_upmap[(1, 5)] = [1, 2, 3]
    m.pg_upmap_items[(1, 7)] = [(0, 47), (1, 46)]
    m.pg_temp[(1, 2)] = [9, 10, 11]
    m.primary_temp[(1, 2)] = 10
    assert_match(m, 1, "combo")


def test_refresh_tables():
    m = make_map()
    pm = PoolMapper(m, 1)
    up0 = np.asarray(pm.map_all()["up"])

    def check(note):
        out = pm.map_all()
        up = np.asarray(out["up"])
        ulen = np.asarray(out["up_len"])
        for ps in range(m.pools[1].pg_num):
            w_up, _, _, _ = m.pg_to_up_acting_osds(1, ps)
            assert list(up[ps, :ulen[ps]]) == w_up, (note, ps)

    # stage appears: upmap_items added after build -> rebuild path
    m.pg_upmap_items[(1, 3)] = [(int(up0[3, 0]), 47)]
    pm.refresh_tables()
    check("refresh-new-stage")
    # same stage, more pairs per pg -> relower + retrace path
    m.pg_upmap_items[(1, 5)] = [(int(up0[5, 0]), 46),
                                (int(up0[5, 1]), 45)]
    pm.refresh_tables()
    check("refresh-more-pairs")


def test_oversized_upmap_rejected():
    m = make_map()
    m.pg_upmap[(1, 5)] = [1, 2, 3, 4]  # longer than pool size 3
    with pytest.raises(ValueError):
        PoolMapper(m, 1)


def test_stale_out_of_range_entries_ignored():
    m = make_map(pg_num=16)
    m.pg_temp[(1, 20)] = [1, 2, 3]  # ps >= pg_num: unreachable
    assert_match(m, 1, "stale-temp")


def test_osdmap_json_roundtrip():
    m = make_map()
    m.pg_upmap[(1, 5)] = [1, 2, 3]
    m.pg_temp[(1, 2)] = [9, 10, 11]
    m.primary_temp[(1, 8)] = 33
    m2 = OSDMap.from_json(m.to_json())
    for ps in (0, 2, 5, 8, 31):
        assert m.pg_to_up_acting_osds(1, ps) == \
            m2.pg_to_up_acting_osds(1, ps)

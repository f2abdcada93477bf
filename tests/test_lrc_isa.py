"""LRC + ISA plugin + registry tests.

Mirrors src/test/erasure-code/TestErasureCodeLrc.cc (generated k/m/l
profiles, explicit layers, minimum_to_decode locality) and
TestErasureCodeIsa.cc (both techniques, round-trips, chunk size), plus
plugin-registry dispatch (TestErasureCodePlugin.cc's factory flow).
"""

import itertools
import json

import numpy as np
import pytest

from ceph_tpu.ec import registry
from ceph_tpu.ec.interface import ErasureCodeError
from ceph_tpu.ec.isa import make_isa
from ceph_tpu.ec.lrc import make_lrc


def _obj(n=3000, seed=7):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


# -- registry ---------------------------------------------------------------

def test_registry_dispatch():
    assert set(registry.plugins()) >= {"jerasure", "isa", "lrc"}
    code = registry.factory("jerasure", {"technique": "reed_sol_van",
                                         "k": "2", "m": "1"})
    assert code.get_chunk_count() == 3
    code = registry.profile_factory({"plugin": "isa", "k": "4",
                                     "m": "2"})
    assert code.get_chunk_count() == 6
    with pytest.raises(ErasureCodeError):
        registry.factory("nope", {})


# -- isa --------------------------------------------------------------------

@pytest.mark.parametrize("technique", ["reed_sol_van", "cauchy"])
def test_isa_roundtrip(technique):
    code = make_isa({"technique": technique, "k": "7", "m": "3"})
    raw = _obj(5000)
    chunks = code.encode(range(10), raw)
    assert chunks[0].shape[0] % 32 == 0  # EC_ISA_ADDRESS_ALIGNMENT
    for erased in itertools.combinations(range(10), 3):
        avail = {i: c for i, c in chunks.items() if i not in erased}
        assert code.decode_concat(avail)[:len(raw)] == raw


def test_isa_m1_xor_path():
    """m=1 degenerates to XOR parity (the region_xor fast path): the
    parity chunk must equal the XOR of the data chunks."""
    code = make_isa({"k": "4", "m": "1"})
    raw = _obj(1000)
    chunks = code.encode(range(5), raw)
    want = np.zeros_like(np.asarray(chunks[0]))
    for i in range(4):
        want ^= np.asarray(chunks[i])
    assert np.array_equal(np.asarray(chunks[4]), want)


def test_isa_vandermonde_clamps():
    with pytest.raises(ErasureCodeError):
        make_isa({"k": "33", "m": "3"})
    with pytest.raises(ErasureCodeError):
        make_isa({"k": "7", "m": "5"})
    with pytest.raises(ErasureCodeError):
        make_isa({"k": "22", "m": "4"})
    make_isa({"technique": "cauchy", "k": "33", "m": "5"})  # no clamp


# -- lrc --------------------------------------------------------------------

def test_lrc_kml_profile_generation():
    code = make_lrc({"k": "4", "m": "2", "l": "3"})
    prof = code.get_profile()
    assert prof["mapping"] == "DD__DD__"
    layers = json.loads(prof["layers"])
    assert layers[0][0] == "DDc_DDc_"
    assert layers[1][0] == "DDDc____"
    assert layers[2][0] == "____DDDc"
    assert code.get_chunk_count() == 8
    assert code.get_data_chunk_count() == 4


def test_lrc_kml_validation():
    with pytest.raises(ErasureCodeError):
        make_lrc({"k": "4", "m": "2"})  # l missing
    with pytest.raises(ErasureCodeError):
        make_lrc({"k": "4", "m": "2", "l": "5"})  # (k+m) % l != 0
    with pytest.raises(ErasureCodeError):
        make_lrc({"k": "4", "m": "2", "l": "3",
                  "mapping": "DD__DD__"})  # generated key set
    with pytest.raises(ErasureCodeError):
        make_lrc({})  # no mapping at all


def test_lrc_roundtrip_all_single_and_double_losses():
    code = make_lrc({"k": "4", "m": "2", "l": "3"})
    raw = _obj(4000)
    n = code.get_chunk_count()
    chunks = code.encode(range(n), raw)
    for r in (1, 2):
        for erased in itertools.combinations(range(n), r):
            avail = {i: c for i, c in chunks.items()
                     if i not in erased}
            try:
                got = code.decode_concat(avail)
            except ErasureCodeError:
                continue  # some double losses exceed LRC's capability
            assert got[:len(raw)] == raw, f"erased={erased}"


def test_lrc_local_repair_reads_fewer_than_k():
    """BASELINE config 4: a single lost chunk repairs from its LOCAL
    layer — strictly fewer chunks than the global k would need."""
    code = make_lrc({"k": "4", "m": "2", "l": "3"})
    n = code.get_chunk_count()
    # lose data chunk 0 (in local group 0 = positions {0,1,2,3})
    want = set(range(n))
    minimum = code.minimum_to_decode({0}, want - {0})
    assert set(minimum) <= {1, 2, 3}  # local group only
    assert len(minimum) == 3  # l chunks, < global k=4 never mind equal
    # and the repair actually works from exactly those chunks
    raw = _obj(2000)
    chunks = code.encode(range(n), raw)
    avail = {i: chunks[i] for i in minimum}
    out = code.decode({0}, avail)
    assert np.array_equal(np.asarray(out[0]), np.asarray(chunks[0]))


def test_lrc_explicit_layers():
    code = make_lrc({
        "mapping": "__DD__DD",
        "layers": json.dumps([
            ["_cDD_cDD", ""],
            ["cDDD____", ""],
            ["____cDDD", ""],
        ]),
    })
    assert code.get_chunk_count() == 8
    assert code.get_data_chunk_count() == 4
    raw = _obj(1000)
    chunks = code.encode(range(8), raw)
    for erased in itertools.combinations(range(8), 1):
        avail = {i: c for i, c in chunks.items() if i not in erased}
        assert code.decode_concat(avail)[:len(raw)] == raw


def test_lrc_minimum_no_erasure_is_want():
    code = make_lrc({"k": "4", "m": "2", "l": "3"})
    n = code.get_chunk_count()
    got = code.minimum_to_decode({1, 2}, set(range(n)))
    assert set(got) == {1, 2}


def test_lrc_unrecoverable_raises():
    code = make_lrc({"k": "4", "m": "2", "l": "3"})
    with pytest.raises(ErasureCodeError):
        # lose an entire local group plus its global parity
        code.minimum_to_decode({0}, {4, 5, 6, 7})


def test_lrc_create_rule_and_placement():
    from ceph_tpu.crush.wrapper import CrushWrapper

    w = CrushWrapper()
    dev = 0
    for h in range(8):
        for _ in range(2):
            w.insert_item(dev, 0x10000, f"osd.{dev}",
                          {"host": f"host{h}", "root": "default"})
            dev += 1
    code = make_lrc({"k": "4", "m": "2", "l": "3",
                     "crush-root": "default",
                     "crush-failure-domain": "host"})
    rid = code.create_rule("lrcpool", w)
    n = code.get_chunk_count()
    for x in range(16):
        res = w.do_rule(rid, x, n, [0x10000] * 16)
        assert len(res) == n
        hosts = {o // 2 for o in res}
        assert len(hosts) == n  # failure-domain separation


def _rack_cluster(racks=4, hosts=4, per_host=2):
    from ceph_tpu.crush.wrapper import CrushWrapper

    w = CrushWrapper()
    dev = 0
    for r in range(racks):
        for h in range(hosts):
            for _ in range(per_host):
                w.insert_item(dev, 0x10000, f"osd.{dev}",
                              {"host": f"host{r}.{h}", "rack": f"rack{r}",
                               "root": "default"})
                dev += 1
    return w, dev


LRC_RACK = {"plugin": "lrc", "k": "4", "m": "2", "l": "3",
            "crush-locality": "rack", "crush-failure-domain": "host"}


def test_lrc_rack_locality_rule_is_ceph_s():
    """ErasureCodeLrc::create_rule (ErasureCodeLrc.cc:44-110) for
    k=4 m=2 l=3 crush-locality=rack crush-failure-domain=host."""
    from ceph_tpu.crush import constants as C

    w, _ = _rack_cluster()
    code = registry.profile_factory(dict(LRC_RACK))
    rid = code.create_rule("lrc8", w)
    steps = [(s.op, s.arg1, s.arg2) for s in w.crush.rules[rid].steps]
    assert steps == [
        (C.CRUSH_RULE_SET_CHOOSELEAF_TRIES, 5, 0),
        (C.CRUSH_RULE_SET_CHOOSE_TRIES, 100, 0),
        (C.CRUSH_RULE_TAKE, w.get_item_id("default"), 0),
        (C.CRUSH_RULE_CHOOSE_INDEP, 2, w.get_type_id("rack")),
        (C.CRUSH_RULE_CHOOSELEAF_INDEP, 4, w.get_type_id("host")),
        (C.CRUSH_RULE_EMIT, 0, 0)]
    assert code.get_chunk_count() == 8


def _mapper_counters():
    from ceph_tpu.common.perf_counters import collection

    return collection().dump("crush.mapper")["crush.mapper"]


def test_lrc_rack_locality_pool_takes_the_speculative_lowering():
    """A PoolMapper on the LRC pool books ``lowered_spec`` and maps
    every PG as the host's scalar OSDMap does, with an OSD down."""
    from ceph_tpu.osdmap.osdmap import (OSD_EXISTS, OSDMap, PgPool,
                                        POOL_TYPE_ERASURE)
    from ceph_tpu.osdmap.pipeline_jax import PoolMapper

    w, n = _rack_cluster()
    code = registry.profile_factory(dict(LRC_RACK))
    m = OSDMap(w.crush)
    for osd in range(n):
        m.add_osd(osd)
    m.pools[3] = PgPool(pool_type=POOL_TYPE_ERASURE,
                        size=code.get_chunk_count(), min_size=5,
                        pg_num=32, crush_rule=code.create_rule("lrc8", w))
    m.osd_weight[5] = 0
    m.osd_state[5] = OSD_EXISTS         # down
    before = _mapper_counters()
    pm = PoolMapper(m, 3)
    after = _mapper_counters()
    assert after["lowered_spec"] - before["lowered_spec"] == 1
    assert after["lowered_general"] == before["lowered_general"]
    out = {k: np.asarray(v) for k, v in pm.map_all().items()}
    for ps in range(32):
        up, upp, acting, actp = m.pg_to_up_acting_osds(3, ps)
        assert out["up"][ps, :out["up_len"][ps]].tolist() == up
        assert out["acting"][ps, :out["acting_len"][ps]].tolist() == acting
        assert (out["up_primary"][ps], out["acting_primary"][ps]) == \
            (upp, actp)


def test_refused_rule_books_general_and_logs_its_reason_once():
    """A rule the speculative lowering refuses (stretch mode's firstn
    two-step) books ``lowered_general`` at each PoolMapper, and its
    reason reaches the log once."""
    import io

    from ceph_tpu.common.log import core
    from ceph_tpu.crush import constants as C
    from ceph_tpu.crush import lowering
    from ceph_tpu.crush.map import Rule, RuleStep
    from ceph_tpu.osdmap.osdmap import OSDMap, PgPool
    from ceph_tpu.osdmap.pipeline_jax import PoolMapper

    w, n = _rack_cluster()
    rid = w.crush.add_rule(Rule(steps=[
        RuleStep(C.CRUSH_RULE_TAKE, w.get_item_id("default"), 0),
        RuleStep(C.CRUSH_RULE_CHOOSE_FIRSTN, 0, w.get_type_id("rack")),
        RuleStep(C.CRUSH_RULE_CHOOSELEAF_FIRSTN, 2, w.get_type_id("host")),
        RuleStep(C.CRUSH_RULE_EMIT, 0, 0)]))
    m = OSDMap(w.crush)
    for osd in range(n):
        m.add_osd(osd)
    m.pools[1] = PgPool(pool_type=1, size=4, min_size=2, pg_num=16,
                        crush_rule=rid)

    def logged():
        buf = io.StringIO()
        core().dump_recent(buf)
        return buf.getvalue().count(f"rule {rid} takes the general rule "
                                    f"VM: two chooses other than")

    lowering._refusals_logged.clear()
    before, seen = _mapper_counters(), logged()
    PoolMapper(m, 1)
    PoolMapper(m, 1)
    after = _mapper_counters()
    assert after["lowered_general"] - before["lowered_general"] == 2
    assert after["lowered_spec"] == before["lowered_spec"]
    assert logged() - seen == 1

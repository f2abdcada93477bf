"""The plain references against published answers: the reference C
build's CRUSH mappings, hashes and log table for the 10,000-OSD map
(the repository's golden fixtures), ISA-L's parity construction, and a
second witness for placement under failures."""

import json
import pathlib

import numpy as np
import pytest

from benchmark.lib.crushmap import build_map
from benchmark.lib.spec import BENCH, ROOT
from benchmark.reference import crush as C
from benchmark.reference import gf256
from benchmark.reference import placement as P

GOLDEN = ROOT / "tests" / "golden"


@pytest.fixture(scope="module")
def big():
    return json.loads((GOLDEN / "map_big10k.json").read_text())


def test_crush10k_config_builds_the_golden_map(big):
    cfg = json.loads((BENCH / "configs" / "crush10k.json").read_text())
    assert build_map(cfg["crush"]) == big["map"]


def test_crush_ln_matches_the_reference_build():
    want = json.loads((GOLDEN / "crush_ln.json").read_text())["ln"]
    assert [C.crush_ln(u) for u in range(0, 65536, 7)] == want[::7]


def test_rjenkins_hashes_match_the_reference_build():
    cases = json.loads((GOLDEN / "hash.json").read_text())["cases"]
    for a, b, _h1, h2, *_rest in cases:
        assert C.hash32_2(a, b) == h2


@pytest.mark.parametrize("ruleno", [0, 1])
def test_do_rule_matches_the_golden_mappings(big, ruleno):
    m = C.Map(big["map"])
    case = next(c for c in big["cases"] if c["ruleno"] == ruleno)
    for i, want in enumerate(case["results"][:96]):
        got = m.do_rule(ruleno, case["x0"] + i, case["numrep"],
                        case["weight"])
        assert got == want, (ruleno, i)


def test_isa_rs_first_parity_row_is_xor():
    assert gf256.rs_matrix(8, 3)[0] == [1] * 8
    data = np.random.default_rng(3).bytes(8 * 4096)
    chunks = gf256.encode(data, 8, 3)
    x = np.zeros(4096, np.uint8)
    for c in chunks[:8]:
        x ^= np.frombuffer(c, np.uint8)
    assert x.tobytes() == chunks[8]


def test_isa_encode_matches_the_corpus():
    d = ROOT / "tests" / "corpus" / "isa-k=8-m=3"
    prof = json.loads((d / "profile.json").read_text())
    raw = (d / "data.bin").read_bytes()[:prof["payload_size"]]
    for i, chunk in enumerate(gf256.encode(raw, 8, 3)):
        assert chunk == (d / f"chunk.{i}").read_bytes()


@pytest.mark.parametrize("pool_type,rule", [("replicated", 0),
                                            ("erasure", 1)])
def test_placement_agrees_with_the_program_under_failures(pool_type,
                                                          rule):
    """Second witness: the program's scalar OSDMap pipeline."""
    from ceph_tpu.crush.map import CrushMap
    from ceph_tpu.osdmap.osdmap import OSDMap, PgPool

    cfg = json.loads((BENCH / "tests" / "data" /
                      "tiny_crush.json").read_text())
    d = build_map(cfg["crush"])
    m = OSDMap(CrushMap.from_dict(d))
    for osd in range(d["max_devices"]):
        m.add_osd(osd)
    for osd in (1, 6, 17):
        m.osd_weight[osd] = 0
        m.osd_state[osd] = P.EXISTS
    size = 3 if pool_type == "replicated" else 11
    m.pools[1] = PgPool(pool_type=1 if pool_type == "replicated" else 3,
                        size=size, min_size=2, pg_num=48, crush_rule=rule)
    pool = {"id": 1, "type": pool_type, "size": size, "pg_num": 48,
            "crush_rule": rule}
    cmap = C.Map(d)
    for ps in range(48):
        assert P.up_acting(cmap, pool, ps, m.osd_weight, m.osd_state) == \
            tuple(m.pg_to_up_acting_osds(1, ps))

"""Native C++ host mapper tests — bit-exact equivalence against the
scalar executable spec and the reference C golden vectors, across
bucket algorithms, tunables, choose_args, and rule shapes."""

import json
import pathlib

import numpy as np

from ceph_tpu.crush import constants as C
from ceph_tpu.crush.builder import (add_simple_rule, build_hierarchy,
                                    make_list_bucket,
                                    make_straw2_bucket,
                                    make_tree_bucket,
                                    make_uniform_bucket,
                                    sample_cluster_map, calc_straw)
from ceph_tpu.crush.map import (Bucket, ChooseArg, ChooseArgMap,
                                CrushMap, Rule, RuleStep, Tunables)
from ceph_tpu.crush.mapper_ref import crush_do_rule
from ceph_tpu.crush.native import NativeMapper

GOLDEN = pathlib.Path(__file__).parent / "golden"


def assert_equivalent(cmap, ruleno, numrep, weight, xs,
                      choose_args=None):
    nm = NativeMapper(cmap, choose_args)
    res, lens = nm.map_batch(ruleno, np.asarray(xs, np.uint32),
                             numrep, np.asarray(weight, np.uint32))
    for i, x in enumerate(xs):
        want = crush_do_rule(cmap, ruleno, int(x), numrep,
                             list(weight), choose_args=choose_args)
        got = list(res[i, :lens[i]])
        assert got == want, f"x={x}: native {got} != spec {want}"


def test_sample_map_both_rules():
    cmap = sample_cluster_map()
    w = [0x10000] * cmap.max_devices
    assert_equivalent(cmap, 0, 3, w, range(256))
    assert_equivalent(cmap, 1, 6, w, range(256))


def test_weight_rejection_and_zero_weights():
    cmap = sample_cluster_map()
    w = [0x10000] * cmap.max_devices
    w[0] = 0
    w[5] = 0x4000  # 25% acceptance
    assert_equivalent(cmap, 0, 3, w, range(512))


def test_golden_10k_map():
    d = json.load(open(GOLDEN / "map_big10k.json"))
    cmap = CrushMap.from_dict(d["map"])
    case = d["cases"][0]
    nm = NativeMapper(cmap)
    res, lens = nm.map_batch(
        case["ruleno"],
        np.arange(case["x0"], case["x1"], dtype=np.uint32),
        case["numrep"], np.asarray(case["weight"], np.uint32))
    for i in range(case["x1"] - case["x0"]):
        assert list(res[i, :lens[i]]) == case["results"][i], f"i={i}"


def test_all_bucket_algorithms():
    """uniform/list/tree/straw/straw2 buckets each as the leaf layer."""
    for maker in ("uniform", "list", "tree", "straw", "straw2"):
        cmap = CrushMap()
        items = list(range(8))
        weights = [0x10000 * (1 + i % 3) for i in items]
        if maker == "uniform":
            b = make_uniform_bucket(items, 0x10000, 1)
        elif maker == "list":
            b = make_list_bucket(items, weights, 1)
        elif maker == "tree":
            b = make_tree_bucket(items, weights, 1)
        elif maker == "straw":
            b = Bucket(id=0, alg=C.CRUSH_BUCKET_STRAW, type=1,
                       items=items, item_weights=weights,
                       straws=calc_straw(weights),
                       weight=sum(weights))
        else:
            b = make_straw2_bucket(items, weights, 1)
        root = cmap.add_bucket(b)
        cmap.max_devices = 8
        add_simple_rule(cmap, root, leaf_type=0, firstn=True, ruleno=0)
        w = [0x10000] * 8
        assert_equivalent(cmap, 0, 3, w, range(200))


def test_legacy_tunables():
    cmap = sample_cluster_map()
    cmap.tunables = Tunables.legacy()
    w = [0x10000] * cmap.max_devices
    assert_equivalent(cmap, 0, 3, w, range(256))


def test_choose_args_weight_sets():
    cmap = sample_cluster_map()
    cargs = ChooseArgMap()
    for idx, b in cmap.buckets.items():
        ws = [[max(0, int(wt) - (i * 0x1000) % 0x8000)
               for i, wt in enumerate(b.item_weights)],
              list(b.item_weights)]
        cargs[idx] = ChooseArg(ids=None, weight_set=ws)
    w = [0x10000] * cmap.max_devices
    assert_equivalent(cmap, 0, 3, w, range(200), choose_args=cargs)


def test_multi_step_rule_with_set_ops():
    """The LRC-style rule shape: set_* steps + choose + chooseleaf."""
    cmap = CrushMap()
    root = build_hierarchy(cmap, [(1, 2), (2, 2), (3, 4)])
    steps = [
        RuleStep(C.CRUSH_RULE_SET_CHOOSELEAF_TRIES, 5, 0),
        RuleStep(C.CRUSH_RULE_SET_CHOOSE_TRIES, 100, 0),
        RuleStep(C.CRUSH_RULE_TAKE, root, 0),
        RuleStep(C.CRUSH_RULE_CHOOSE_INDEP, 2, 2),
        RuleStep(C.CRUSH_RULE_CHOOSELEAF_INDEP, 2, 1),
        RuleStep(C.CRUSH_RULE_EMIT, 0, 0),
    ]
    cmap.add_rule(Rule(steps=steps, type=3), 0)
    w = [0x10000] * cmap.max_devices
    assert_equivalent(cmap, 0, 4, w, range(200))


def test_u32_x_wraparound():
    cmap = sample_cluster_map()
    w = [0x10000] * cmap.max_devices
    assert_equivalent(cmap, 0, 3, w,
                      [0xFFFFFFFF, 0x7FFFFFFF, 0x80000000, 12345])


def test_tester_native_path_matches_scalar():
    from ceph_tpu.crush.wrapper import CrushWrapper
    from ceph_tpu.tools.tester import CrushTester

    w = CrushWrapper(sample_cluster_map())
    t = CrushTester(w)
    a = t.test_rule(0, 3, 0, 127, scalar=True, collect_mappings=True)
    b = t.test_rule(0, 3, 0, 127, native=True, collect_mappings=True)
    assert a.mappings == b.mappings
    assert np.array_equal(a.device_stored, b.device_stored)

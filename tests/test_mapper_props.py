"""Property tests for map shapes the golden corpus doesn't cover.

The golden maps are each single-algorithm; these tests build tree-bucket
and mixed-algorithm hierarchies with the builder and check the batched
JAX mapper against the scalar executable spec (itself golden-tested), so
the lax.switch multi-branch dispatch path is exercised.  Also covers the
statistical tests the reference runs in src/test/crush/crush.cc
(straw2_stddev:514, indep stability under failures: indep_out_*:151).
"""

import numpy as np
import pytest

import conftest  # noqa: F401  (forces CPU platform)

from ceph_tpu.crush import constants as C
from ceph_tpu.crush.builder import (add_simple_rule, make_list_bucket,
                                    make_straw2_bucket, make_tree_bucket,
                                    make_uniform_bucket,
                                    sample_cluster_map)
from ceph_tpu.crush.map import CrushMap, Rule, RuleStep
from ceph_tpu.crush.lowering import BatchedMapper
from ceph_tpu.crush.mapper_ref import crush_do_rule


def _check_vs_ref(cmap, ruleno, numrep, weight, n=256):
    m = BatchedMapper(cmap)
    xs = np.arange(n, dtype=np.uint32)
    res, lens = m.map_batch(ruleno, xs, numrep, weight)
    res = np.asarray(res)
    lens = np.asarray(lens)
    for i, x in enumerate(xs):
        want = crush_do_rule(cmap, ruleno, int(x), numrep, list(weight))
        got = list(res[i, :lens[i]])
        assert got == want, (int(x), got, want)


def test_tree_bucket_map():
    cmap = CrushMap()
    ids = []
    for h in range(3):
        b = make_tree_bucket(list(range(4 * h, 4 * h + 4)),
                             [0x10000, 0x20000, 0x10000, 0x8000], 1)
        ids.append(cmap.add_bucket(b))
    root = make_tree_bucket(ids, [b and 0x40000 or 0x40000 for b in ids],
                            2)
    root_id = cmap.add_bucket(root)
    cmap.max_devices = 12
    add_simple_rule(cmap, root_id, leaf_type=1, firstn=True, ruleno=0)
    _check_vs_ref(cmap, 0, 3, np.full(12, 0x10000, np.uint32))


def test_mixed_alg_map():
    """One host of each algorithm under a straw2 root — every lax.switch
    branch executes for every lane."""
    cmap = CrushMap()
    hosts = [
        make_straw2_bucket([0, 1, 2], [0x10000] * 3, 1),
        make_list_bucket([3, 4, 5], [0x10000, 0x18000, 0x8000], 1),
        make_tree_bucket([6, 7, 8], [0x10000, 0x10000, 0x20000], 1),
        make_uniform_bucket([9, 10, 11], 0x10000, 1),
    ]
    ids = [cmap.add_bucket(b) for b in hosts]
    root = make_straw2_bucket(ids, [b.weight for b in hosts], 2)
    root_id = cmap.add_bucket(root)
    cmap.max_devices = 12
    add_simple_rule(cmap, root_id, leaf_type=1, firstn=True, ruleno=0)
    add_simple_rule(cmap, root_id, leaf_type=1, firstn=False, ruleno=1)
    w = np.full(12, 0x10000, np.uint32)
    _check_vs_ref(cmap, 0, 3, w, n=128)
    _check_vs_ref(cmap, 1, 3, w, n=128)


def test_straw2_weight_proportionality():
    """straw2_stddev analogue (src/test/crush/crush.cc:514): selection
    frequency tracks weight within a few percent."""
    cmap = CrushMap()
    weights = [0x10000, 0x20000, 0x30000, 0x40000]
    b = make_straw2_bucket([0, 1, 2, 3], weights, 1)
    root_id = cmap.add_bucket(b)
    cmap.max_devices = 4
    cmap.add_rule(Rule([RuleStep(C.CRUSH_RULE_TAKE, root_id, 0),
                        RuleStep(C.CRUSH_RULE_CHOOSE_FIRSTN, 1, 0),
                        RuleStep(C.CRUSH_RULE_EMIT, 0, 0)]), 0)
    m = BatchedMapper(cmap)
    n = 40000
    res, lens = m.map_batch(0, np.arange(n, dtype=np.uint32), 1,
                            np.full(4, 0x10000, np.uint32))
    counts = np.bincount(np.asarray(res)[:, 0], minlength=4)
    total_w = sum(weights)
    for i, w in enumerate(weights):
        expect = n * w / total_w
        assert abs(counts[i] - expect) / expect < 0.05, (i, counts)


def test_indep_positional_stability():
    """indep_out_* analogue (src/test/crush/crush.cc:151-233): marking a
    device out must not disturb other positions of EC mappings."""
    cmap = sample_cluster_map(3, 3, 3)
    m = BatchedMapper(cmap)
    D = cmap.max_devices
    xs = np.arange(2048, dtype=np.uint32)
    w = np.full(D, 0x10000, np.uint32)
    res, _ = m.map_batch(1, xs, 6, w)
    res = np.asarray(res)
    w2 = w.copy()
    w2[5] = 0
    res2 = np.asarray(m.map_batch(1, xs, 6, w2)[0])
    # positions that didn't hold osd.5 keep their device (or NONE)
    unchanged = res != 5
    assert (res2[unchanged] == res[unchanged]).mean() > 0.98


def test_u32_x_wraparound():
    """x is u32: -1 and 2**32-1 must map identically (goldengen gotcha)."""
    cmap = sample_cluster_map()
    m = BatchedMapper(cmap)
    w = np.full(cmap.max_devices, 0x10000, np.uint32)
    a = np.asarray(m.map_batch(0, np.array([2**32 - 1], np.uint32), 3,
                               w)[0])
    ref = crush_do_rule(cmap, 0, 2**32 - 1, 3, list(w))
    assert list(a[0][:len(ref)]) == ref

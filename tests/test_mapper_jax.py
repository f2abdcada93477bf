"""The vmapped JAX mapper vs every golden do_rule vector.

Same corpus as test_mapper_ref.py, but the whole x-range of each case is
mapped in ONE batched call — exercising exactly the program that runs on
TPU (vmap over x, lax.while_loop retry descents, masked bucket chooses).
"""

import json

import numpy as np
import pytest

from conftest import GOLDEN_DIR

from ceph_tpu.crush.map import CrushMap
from ceph_tpu.crush.lowering import BatchedMapper
from ceph_tpu.crush.mapper_jax import build_rule_fn

MAP_FILES = [
    "map_flat12", "map_tree3", "map_tree3_chooseargs", "map_tree3_legacy",
    "map_uniform", "map_list", "map_straw", "map_weird", "map_big10k",
]


def load(name):
    d = json.load(open(GOLDEN_DIR / f"{name}.json"))
    cmap = CrushMap.from_dict(d["map"])
    return cmap, d


@pytest.mark.parametrize("spec", [True, False], ids=["spec", "general"])
@pytest.mark.parametrize("name", MAP_FILES)
def test_golden_map_batched(name, spec):
    """Both lowerings: ``BatchedMapper`` takes the speculative one for
    every rule it accepts; ``build_rule_fn`` is the general rule VM
    for every rule (on CPU with the computed-ln straw2 draw)."""
    cmap, d = load(name)
    cargs = cmap.choose_args.get("golden")
    mapper = BatchedMapper(cmap, choose_args=cargs) if spec else None
    general = {}
    for case in d["cases"]:
        ruleno = case["ruleno"]
        numrep = case["numrep"]
        weight = np.asarray(case["weight"], np.uint32)
        x0, x1 = case["x0"], case["x1"]
        n = x1 - x0 if name != "map_big10k" else 256
        xs = np.arange(x0, x0 + n, dtype=np.uint32)
        if spec:
            res, lens = mapper.map_batch(ruleno, xs, numrep, weight)
        else:
            if (ruleno, numrep) not in general:
                general[ruleno, numrep] = build_rule_fn(
                    cmap, ruleno, numrep, cargs)
            fn, _, arrays = general[ruleno, numrep]
            res, lens = fn(arrays, weight, xs)
        res = np.asarray(res)
        lens = np.asarray(lens)
        for i in range(n):
            want = case["results"][i]
            got = list(res[i, :lens[i]])
            assert got == want, (name, ruleno, numrep, int(xs[i]),
                                 got, want)


def test_launches_bounded_by_memory_budget(monkeypatch):
    """The launch size comes from a small probe's footprint: a budget
    that holds the whole batch maps it in one launch; a budget below
    it splits the range into power-of-two launches (the tail padded)
    with the same results."""
    from ceph_tpu.crush import lowering

    monkeypatch.setattr(lowering, "PROBE_LANES", 128)
    cmap, d = load("map_tree3")
    weight = np.asarray(d["cases"][0]["weight"], np.uint32)
    xs = np.arange(1000, dtype=np.uint32)
    whole = BatchedMapper(cmap)
    want = [np.asarray(a) for a in whole.map_batch(0, xs, 3, weight)]
    assert whole._lanes[(0, 3, 1000)] == 1000
    per_lane = lowering.footprint_bytes(whole._exe[(0, 3, 128)]) / 128
    monkeypatch.setattr(lowering, "launch_budget_bytes",
                        lambda: int(per_lane * 300))
    split = BatchedMapper(cmap)
    got = [np.asarray(a) for a in split.map_batch(0, xs, 3, weight)]
    assert split._lanes[(0, 3, 1000)] == 256
    assert sorted(split._exe) == [(0, 3, 128), (0, 3, 256)]
    for g, w in zip(got, want):
        assert np.array_equal(g, w)

"""The speculative firstn mapper vs the golden do_rule vectors.

Same corpus as test_mapper_jax.py restricted to eligible cases (straw2-only
maps, take/chooseleaf-firstn/emit rules, modern tunables) — the speculative
program must be bit-exact there, and `analyze` must correctly refuse
everything else (legacy tunables, other bucket algs, multi-step rules).
Both straw2 lowerings (LN16-table key and computed-ln draw) are covered.
"""

import json

import numpy as np
import pytest

from conftest import GOLDEN_DIR

from ceph_tpu.crush.map import CrushMap
from ceph_tpu.crush.mapper_spec import (Ineligible, SpeculativeMapper,
                                        analyze)

MAP_FILES = [
    "map_flat12", "map_tree3", "map_tree3_chooseargs", "map_tree3_legacy",
    "map_uniform", "map_list", "map_straw", "map_weird", "map_big10k",
]

# cases analyze() must accept: (map, ruleno) pairs known eligible — the
# default replicated-rule shape on every straw2 map in the corpus
ELIGIBLE = {("map_flat12", 0), ("map_tree3", 0),
            ("map_tree3_chooseargs", 0), ("map_weird", 0),
            ("map_big10k", 0)}
# and ones it must refuse, with the reason class
INELIGIBLE = {("map_tree3_legacy", 0): "legacy",
              ("map_uniform", 0): "alg",
              ("map_tree3", 2): "non-device"}


def load(name):
    d = json.load(open(GOLDEN_DIR / f"{name}.json"))
    return CrushMap.from_dict(d["map"]), d


@pytest.mark.parametrize("k_tries", [1, 8])
@pytest.mark.parametrize("name", MAP_FILES)
def test_golden_eligible_cases(name, k_tries):
    cmap, d = load(name)
    cargs = cmap.choose_args.get("golden")
    mapper = None
    covered = 0
    for case in d["cases"]:
        ruleno, numrep = case["ruleno"], case["numrep"]
        try:
            analyze(cmap, ruleno, numrep)
        except Ineligible:
            continue
        if mapper is None:
            mapper = SpeculativeMapper(cmap, choose_args=cargs,
                                       k_tries=k_tries)
        weight = np.asarray(case["weight"], np.uint32)
        x0, x1 = case["x0"], case["x1"]
        n = min(x1 - x0, 48 if name == "map_big10k" else x1 - x0)
        xs = np.arange(x0, x0 + n, dtype=np.uint32)
        res, lens = mapper.map_batch(ruleno, xs, numrep, weight)
        res = np.asarray(res)
        lens = np.asarray(lens)
        for i in range(n):
            want = case["results"][i]
            got = list(res[i, :lens[i]])
            assert got == want, (name, ruleno, numrep, int(xs[i]),
                                 got, want)
        covered += 1
    if any(nm == name for nm, _ in ELIGIBLE):
        assert covered > 0, f"{name}: expected at least one eligible case"


def test_eligibility_judgments():
    for name, ruleno in ELIGIBLE:
        cmap, d = load(name)
        numrep = next(c["numrep"] for c in d["cases"]
                      if c["ruleno"] == ruleno)
        analyze(cmap, ruleno, numrep)  # must not raise
    for (name, ruleno), _why in INELIGIBLE.items():
        cmap, d = load(name)
        numrep = next((c["numrep"] for c in d["cases"]
                       if c["ruleno"] == ruleno), 3)
        with pytest.raises(Ineligible):
            analyze(cmap, ruleno, numrep)


def test_compute_mode_matches_table_mode(monkeypatch):
    """Both straw2 lowerings agree with the golden vectors (the table
    mode is exercised by the parametrized test above; this pins the
    computed-ln mode)."""
    import importlib

    import ceph_tpu.crush.mapper_spec as MS
    monkeypatch.setenv("CEPH_TPU_STRAW2", "compute")
    importlib.reload(MS)
    try:
        cmap, d = load("map_tree3")
        case = d["cases"][0]
        m = MS.SpeculativeMapper(cmap)
        weight = np.asarray(case["weight"], np.uint32)
        xs = np.arange(case["x0"], case["x1"], dtype=np.uint32)
        res, lens = m.map_batch(case["ruleno"], xs, case["numrep"], weight)
        res, lens = np.asarray(res), np.asarray(lens)
        for i, want in enumerate(case["results"]):
            assert list(res[i, :lens[i]]) == want
    finally:
        monkeypatch.delenv("CEPH_TPU_STRAW2")
        importlib.reload(MS)


def test_indep_cases_covered_and_leaf_type0_rejected():
    """The indep lowering: eligible golden indep cases are bit-exact
    (covered by the parametrized sweep), chooseleaf-indep-of-type-0 is
    REFUSED (the reference leaks the last is_out-rejected device
    through out2 there — a quirk the spec path does not reproduce),
    and a randomized zero-weight differential pins the accepted shapes
    against the scalar spec."""
    import random

    cmap, d = load("map_big10k")
    # the golden corpus includes at least one eligible indep case
    indep_cases = [c for c in d["cases"] if c["ruleno"] == 1]
    assert indep_cases, "corpus lost its indep case"
    analyze(cmap, 1, indep_cases[0]["numrep"])  # eligible

    # randomized differential with rejections in play (zeroed weights)
    from ceph_tpu.crush.mapper_ref import crush_do_rule

    case = indep_cases[0]
    rng = random.Random(99)
    weights = list(case["weight"])
    for _ in range(40):
        weights[rng.randrange(len(weights))] = 0
    m = SpeculativeMapper(cmap, k_tries=1)
    import numpy as np

    xs = np.arange(500, 564, dtype=np.uint32)
    res, lens = m.map_batch(1, xs, case["numrep"],
                            np.asarray(weights, np.uint32))
    res, lens = np.asarray(res), np.asarray(lens)
    for i, x in enumerate(xs):
        want = crush_do_rule(cmap, 1, int(x), case["numrep"],
                             list(weights))
        assert list(res[i, :lens[i]]) == want, int(x)

    # chooseleaf indep of type 0: must fall back to the general VM
    from ceph_tpu.crush.map import Rule, RuleStep
    from ceph_tpu.crush import constants as CC

    cmap2, _ = load("map_flat12")
    root_id = next(b.id for b in cmap2.buckets.values()
                   if all(i >= 0 for i in b.items))
    cmap2.rules[9] = Rule(steps=[
        RuleStep(CC.CRUSH_RULE_TAKE, root_id, 0),
        RuleStep(CC.CRUSH_RULE_CHOOSELEAF_INDEP, 4, 0),
        RuleStep(CC.CRUSH_RULE_EMIT, 0, 0)])
    # match on the ValueError base: the reload test earlier in this
    # module swaps the Ineligible class identity in analyze's globals
    with pytest.raises(ValueError, match="type 0"):
        analyze(cmap2, 9, 4)


# -- the straggler pass (map_stragglers) --------------------------------

N_STRAGGLE = 1 << 12   # PGs per launch; chunks of N // 64 = 64 lanes


@pytest.fixture(scope="module")
def straggle_progs():
    """Per rule of map_big10k (0: chooseleaf firstn host, size 3; 1:
    chooseleaf indep host, size 11): the one-round flags, the plain
    vmapped loops, and the straggler pass with its stats."""
    import jax

    from ceph_tpu.crush.mapper_spec import make_single_spec, map_stragglers

    cmap, _ = load("map_big10k")
    progs = {}
    for rule, size in ((0, 3), (1, 11)):
        single, one_round, _, arrays = make_single_spec(cmap, rule, size,
                                                        k_tries=1)
        progs[rule] = dict(
            size=size,
            arrays=jax.tree_util.tree_map(jax.numpy.asarray, arrays),
            flags=jax.jit(lambda A, w, xs, f=one_round: jax.vmap(
                f, in_axes=(None, None, 0))(A, w, xs)[2]),
            plain=jax.jit(jax.vmap(single, in_axes=(None, None, 0))),
            straggle=jax.jit(lambda A, w, xs, s=single, f=one_round:
                             map_stragglers(s, f, A, w, xs)))
    return cmap, progs


def _straggle_batch(cmap, prog, case):
    """xs and weights whose first round leaves the case's count of
    stragglers: picked from a probe of the first round's flags, or, for
    "most", 60% of the OSDs at weight 0 (nearly every PG re-runs)."""
    rng = np.random.default_rng(25)
    weight = np.full(cmap.max_devices, 0x10000, np.uint32)
    weight[rng.choice(cmap.max_devices, 100, replace=False)] = 0
    if case == "most":
        weight[rng.choice(cmap.max_devices, 6000, replace=False)] = 0
        return np.arange(N_STRAGGLE, dtype=np.uint32), weight, None
    probe = np.arange(2 * N_STRAGGLE, dtype=np.uint32)
    flags = np.concatenate([
        np.asarray(prog["flags"](prog["arrays"], weight, half))
        for half in np.split(probe, 2)])
    k = {"none": 0, "one_chunk": 40, "many_chunks": 2 * 64 + 3}[case]
    assert flags.sum() >= k
    xs = np.concatenate([probe[flags][:k],
                         probe[~flags][:N_STRAGGLE - k]])
    return rng.permutation(xs), weight, k


@pytest.mark.parametrize("case",
                         ["none", "one_chunk", "many_chunks", "most"])
@pytest.mark.parametrize("rule", [0, 1], ids=["firstn3", "indep11"])
def test_straggler_pass_matches_plain_loops(straggle_progs, rule, case):
    """The one-round pass plus chunked re-runs gives, bit for bit, what
    the plain vmapped retry loops give, and on a sample what the
    reference mapper gives, whatever the count of stragglers; its
    stats count the stragglers and ceil(stragglers / 64) chunks."""
    from ceph_tpu.crush.mapper_ref import crush_do_rule

    cmap, progs = straggle_progs
    prog = progs[rule]
    xs, weight, k = _straggle_batch(cmap, prog, case)
    A = prog["arrays"]
    res, lens, stats = (np.asarray(v)
                        for v in prog["straggle"](A, weight, xs))
    want_res, want_lens = (np.asarray(v)
                           for v in prog["plain"](A, weight, xs))
    assert np.array_equal(res, want_res)
    assert np.array_equal(lens, want_lens)

    flags = np.asarray(prog["flags"](A, weight, xs))
    flagged, chunks = (int(v) for v in stats)
    assert flagged == int(flags.sum())
    if k is not None:
        assert flagged == k
    else:
        assert flagged > 0.9 * N_STRAGGLE
    assert chunks == -(-flagged // (N_STRAGGLE // 64))

    # the reference on a few stragglers and a few others
    lanes = np.concatenate([np.nonzero(flags)[0][:6],
                            np.nonzero(~flags)[0][:2]])
    for i in lanes:
        want = crush_do_rule(cmap, rule, int(xs[i]), prog["size"],
                             weight.tolist())
        assert list(res[i, :lens[i]]) == want, (case, int(xs[i]))


def test_straggler_stats_booked_when_read(straggle_progs):
    """A launch's straggler stats are kept as a device array and booked
    in the ``crush.mapper`` counters by the next ``perf dump``."""
    from ceph_tpu.common.perf_counters import collection
    from ceph_tpu.crush.mapper_jax import defer_rerun_stats

    cmap, progs = straggle_progs
    prog = progs[0]
    xs, weight, k = _straggle_batch(cmap, prog, "many_chunks")

    def dump():
        return collection().dump("crush.mapper")["crush.mapper"]

    before = dump()
    defer_rerun_stats(prog["straggle"](prog["arrays"], weight, xs)[2])
    after = dump()
    assert after["spec_rerun_pgs"] - before["spec_rerun_pgs"] == k
    assert after["spec_rerun_chunks"] - before["spec_rerun_chunks"] == 3

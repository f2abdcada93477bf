"""The speculative firstn mapper vs the golden do_rule vectors.

Same corpus as test_mapper_jax.py restricted to eligible cases (straw2-only
maps, take/chooseleaf-firstn/emit rules, modern tunables) — the speculative
program must be bit-exact there, and `analyze` must correctly refuse
everything else (legacy tunables, other bucket algs, multi-step rules).
"""

import json

import numpy as np
import pytest

from conftest import GOLDEN_DIR

from ceph_tpu.crush.map import CrushMap
from ceph_tpu.crush.mapper_spec import (Ineligible, analyze,
                                        build_spec_rule_fn)

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
    fns = {}
    covered = 0
    for case in d["cases"]:
        ruleno, numrep = case["ruleno"], case["numrep"]
        try:
            analyze(cmap, ruleno, numrep)
        except Ineligible:
            continue
        if (ruleno, numrep) not in fns:
            fns[ruleno, numrep] = build_spec_rule_fn(
                cmap, ruleno, numrep, cargs, k_tries=k_tries)
        fn, _, arrays = fns[ruleno, numrep]
        weight = np.asarray(case["weight"], np.uint32)
        x0, x1 = case["x0"], case["x1"]
        n = min(x1 - x0, 48 if name == "map_big10k" else x1 - x0)
        xs = np.arange(x0, x0 + n, dtype=np.uint32)
        res, lens = fn(arrays, weight, xs)
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
    fn, _, arrays = build_spec_rule_fn(cmap, 1, case["numrep"], k_tries=1)
    xs = np.arange(500, 564, dtype=np.uint32)
    res, lens = fn(arrays, np.asarray(weights, np.uint32), xs)
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
    with pytest.raises(Ineligible, match="type 0"):
        analyze(cmap2, 9, 4)


# -- the straggler pass (map_stragglers) --------------------------------

N_STRAGGLE = 1 << 12   # PGs per launch; chunks of N // 64 = 64 lanes


def add_lrc_rule(cmap):
    """The rule the LRC plugin writes for k=4 m=2 l=3 with rack
    locality (``choose indep 2 rack / chooseleaf indep 4 host``), on
    ``cmap`` under its root named ``default``; returns its number."""
    from ceph_tpu.crush.wrapper import CrushWrapper
    from ceph_tpu.ec.registry import profile_factory

    w = CrushWrapper(cmap)
    root = next(b.id for b in cmap.buckets.values() if b.type == 3)
    w.set_item_name(root, "default")
    return profile_factory({
        "plugin": "lrc", "k": "4", "m": "2", "l": "3",
        "crush-locality": "rack",
        "crush-failure-domain": "host"}).create_rule("lrc8", w)


@pytest.fixture(scope="module")
def straggle_progs():
    """Per rule of map_big10k (0: chooseleaf firstn host, size 3; 1:
    chooseleaf indep host, size 11) and the LRC rule (2, size 8): the
    one-round flags, the plain vmapped loops, and the straggler pass
    with its stats; for the LRC rule also the general rule VM."""
    import jax

    from ceph_tpu.crush.mapper_jax import make_single_fn
    from ceph_tpu.crush.mapper_spec import make_single_spec, map_stragglers

    cmap, _ = load("map_big10k")
    assert add_lrc_rule(cmap) == 2
    progs = {}
    for rule, size in ((0, 3), (1, 11), (2, 8)):
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
    # the rule the parent left to the general rule VM: also against it
    general, _, garrays = make_single_fn(cmap, 2, 8)
    progs[2].update(
        general=jax.jit(jax.vmap(general, in_axes=(None, None, 0))),
        garrays=jax.tree_util.tree_map(jax.numpy.asarray, garrays))
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
@pytest.mark.parametrize("rule", [0, 1, 2],
                         ids=["firstn3", "indep11", "lrc8"])
def test_straggler_pass_matches_plain_loops(straggle_progs, rule, case):
    """The one-round pass plus chunked re-runs gives, bit for bit, what
    the plain vmapped retry loops give (for the LRC rule, the general
    rule VM too), and on a sample what the reference mapper gives,
    whatever the count of stragglers; its stats count the stragglers
    and ceil(stragglers / 64) chunks."""
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
    if "general" in prog:
        g_res, g_lens = (np.asarray(v) for v in
                         prog["general"](prog["garrays"], weight, xs))
        assert np.array_equal(res, g_res)
        assert np.array_equal(lens, g_lens)

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
    from ceph_tpu.crush.lowering import defer_rerun_stats

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


# -- two-step rules: choose indep of buckets, chooseleaf indep below ----

def _first_step_holes(d):
    """Make map_big10k's dict ``d`` hold, at its root, racks 0 and 2
    and one host of rack 1 at equal weights: a first-step descent into
    the host finds no rack and leaves its slot empty (either slot, both
    in some inputs)."""
    racks = [b for b in d["buckets"] if b["type"] == 2]
    root = next(b for b in d["buckets"] if b["type"] == 3)
    w = racks[0]["weight"]
    root.update(items=[racks[0]["id"], racks[2]["id"],
                       racks[1]["items"][0]],
                item_weights=[w] * 3, size=3, weight=3 * w)


@pytest.mark.parametrize("case", ["all_in", "rack_out",
                                  "first_step_holes"])
def test_two_step_rule_matches_reference_and_general_vm(case):
    """The LRC rule over 4,096 PGs through the straggler pass equals the
    general rule VM on every PG and the scalar reference on a sample
    (the straggler lanes first): every OSD in; every OSD of rack 0 out
    (its segments run their full 100 rounds and keep holes); and a
    first step that leaves slots empty, whose later segments close up
    (crush_do_rule does not advance osize past a hole)."""
    import jax

    from ceph_tpu.crush.mapper_jax import make_single_fn
    from ceph_tpu.crush.mapper_ref import crush_do_rule
    from ceph_tpu.crush.mapper_spec import make_single_spec, map_stragglers

    _, d = load("map_big10k")
    if case == "first_step_holes":
        _first_step_holes(d["map"])
    cmap = CrushMap.from_dict(d["map"])
    rule = add_lrc_rule(cmap)
    weight = np.full(cmap.max_devices, 0x10000, np.uint32)
    if case == "rack_out":
        weight[:500] = 0
    xs = np.arange(N_STRAGGLE, dtype=np.uint32) * np.uint32(2654435761)
    single, one_round, _, arrays = make_single_spec(cmap, rule, 8,
                                                    k_tries=1)
    A = jax.tree_util.tree_map(jax.numpy.asarray, arrays)
    res, lens, stats = (np.asarray(v) for v in jax.jit(
        lambda A, w, xs: map_stragglers(single, one_round, A, w, xs))(
            A, weight, xs))
    general, _, garrays = make_single_fn(cmap, rule, 8)
    want_res, want_lens = (np.asarray(v) for v in jax.jit(jax.vmap(
        general, in_axes=(None, None, 0)))(
            jax.tree_util.tree_map(jax.numpy.asarray, garrays), weight,
            xs))
    assert np.array_equal(lens, want_lens)
    assert np.array_equal(res, want_res)
    flags = np.asarray(jax.jit(jax.vmap(one_round, in_axes=(
        None, None, 0)))(A, weight, xs)[2])
    assert int(stats[0]) == int(flags.sum()) > 0
    # a lane on the emptied rack runs the reference's 100 rounds
    nflag = 8 if case == "rack_out" else 24
    lanes = np.concatenate([np.nonzero(flags)[0][:nflag],
                            np.nonzero(~flags)[0][:8]])
    if case == "first_step_holes":
        assert {0, 4, 8} <= set(lens.tolist())
        lanes = np.concatenate([lanes, np.nonzero(lens < 8)[0][:16]])
    if case == "rack_out":
        assert (res == 0x7FFFFFFF).any()
    for i in lanes:
        want = crush_do_rule(cmap, rule, int(xs[i]), 8, weight.tolist())
        assert list(res[i, :lens[i]]) == want, (case, int(xs[i]))


def test_two_step_plan():
    cmap, _ = load("map_big10k")
    plan = analyze(cmap, add_lrc_rule(cmap), 8)
    assert (plan.pre_numrep, plan.pre_type, plan.pre_tries) == (2, 2, 100)
    assert (plan.numrep, plan.type_, plan.recurse_tries) == (4, 1, 5)
    # root -> rack, rack -> host, host -> OSD: one level each
    assert (plan.pre_depth, plan.depth_outer, plan.depth_inner) == \
        (1, 1, 1)


# -- descents bounded by the levels to their wanted type ----------------

@pytest.mark.parametrize("name,rule,depths", [
    ("map_big10k", 0, (2, 1)), ("map_big10k", 1, (2, 1)),
    ("map_tree3", 0, (1, 2))])
def test_one_step_plan_descends_to_the_target_type(name, rule, depths):
    """A one-step rule's outer descent stops at the choose's type: on
    map_big10k root -> rack -> host, not a third level below the hosts;
    on map_tree3 one level to the racks, then two to the devices."""
    cmap, d = load(name)
    numrep = next(c["numrep"] for c in d["cases"] if c["ruleno"] == rule)
    plan = analyze(cmap, rule, numrep)
    assert (plan.depth_outer, plan.depth_inner) == depths


HOST, ROW, RACK, ROOT = 1, 2, 3, 4


def uneven_map():
    """Hosts of three OSDs at three depths below one root: two directly
    under it, two under a rack, two under a row under a rack (item
    weights uneven); rule 0 is ``chooseleaf firstn 3 type host``, rule
    1 ``chooseleaf indep 4 type host``."""
    from ceph_tpu.crush.builder import add_simple_rule, make_straw2_bucket

    cmap = CrushMap()
    osds = iter(range(18))

    def bucket(type_, children):
        b = make_straw2_bucket([c.id for c in children],
                               [c.weight for c in children], type_)
        cmap.add_bucket(b)
        return b

    hosts = []
    for i in range(6):
        ids = [next(osds) for _ in range(3)]
        b = make_straw2_bucket(ids, [0x10000 * (1 + (i + j) % 3)
                                     for j in range(3)], HOST)
        cmap.add_bucket(b)
        hosts.append(b)
    row = bucket(ROW, hosts[4:])
    root = bucket(ROOT, hosts[:2] + [bucket(RACK, hosts[2:4]),
                                     bucket(RACK, [row])])
    add_simple_rule(cmap, root.id, HOST, firstn=True, ruleno=0)
    add_simple_rule(cmap, root.id, HOST, firstn=False, ruleno=1)
    return cmap


@pytest.mark.parametrize("k_tries", [1, 8])
@pytest.mark.parametrize("rule,size", [(0, 3), (1, 4)],
                         ids=["firstn3", "indep4"])
def test_hosts_at_uneven_depths_match_reference(rule, size, k_tries):
    """Where the wanted type sits at several depths the bound is the
    deepest (root -> rack -> row -> host), and a lane that finds its
    host early waits out the levels below it: the speculative program
    through the straggler pass (4,096 lanes), and the plain loops,
    give what the reference gives, with two OSDs out."""
    import jax

    from ceph_tpu.crush.mapper_ref import crush_do_rule
    from ceph_tpu.crush.mapper_spec import make_single_spec

    cmap = uneven_map()
    plan = analyze(cmap, rule, size)
    assert (plan.depth_outer, plan.depth_inner, plan.levels_cut) == \
        (3, 1, 1)
    weight = np.full(cmap.max_devices, 0x10000, np.uint32)
    weight[[4, 13]] = 0
    xs = np.arange(N_STRAGGLE, dtype=np.uint32) * np.uint32(2654435761)
    fn, _, arrays = build_spec_rule_fn(cmap, rule, size,
                                       k_tries=k_tries)
    res, lens = (np.asarray(v) for v in fn(arrays, weight, xs))
    single, _, _, _ = make_single_spec(cmap, rule, size, k_tries=k_tries)
    plain = jax.jit(jax.vmap(single, in_axes=(None, None, 0)))
    p_res, p_lens = (np.asarray(v) for v in plain(arrays, weight,
                                                  xs[:300]))
    for i in range(300):
        want = crush_do_rule(cmap, rule, int(xs[i]), size, weight.tolist())
        assert list(res[i, :lens[i]]) == want, int(xs[i])
        assert list(p_res[i, :p_lens[i]]) == want, int(xs[i])


@pytest.mark.parametrize("rule,cut", [(0, 1), ("lrc8", 0)],
                         ids=["firstn3", "lrc8"])
def test_spec_levels_cut_booked_per_lowering(rule, cut):
    """Each speculative lowering books the outer levels per slot that
    the bound left out: one below crush10k's hosts for its replicated
    rule, none for the LRC rule, whose descents were already bounded
    by their types."""
    from ceph_tpu.common.perf_counters import collection
    from ceph_tpu.crush.lowering import BatchedMapper

    cmap, _ = load("map_big10k")
    size = 3
    if rule == "lrc8":
        rule, size = add_lrc_rule(cmap), 8

    def dump():
        return collection().dump("crush.mapper")["crush.mapper"]

    before = dump()
    BatchedMapper(cmap).rule_fn(rule, size)
    after = dump()
    assert after["lowered_spec"] - before["lowered_spec"] == 1
    assert after["spec_levels_cut"] - before["spec_levels_cut"] == cut


# every other shape with two chooses goes to the general rule VM
OTHER_TWO_CHOOSE = {
    "stretch_firstn": [(2, 0, 2), (6, 2, 1)],
    "indep_then_leaf_firstn": [(3, 2, 2), (6, 4, 1)],
    "firstn_then_leaf_indep": [(2, 2, 2), (7, 4, 1)],
    "second_not_leaf": [(3, 2, 2), (3, 4, 1)],
    "first_leaf": [(7, 2, 2), (7, 4, 1)],
    "first_of_devices": [(3, 2, 0), (7, 4, 1)],
    "leaf_of_devices": [(3, 2, 2), (7, 4, 0)],
    "three_chooses": [(3, 2, 2), (3, 2, 1), (7, 1, 1)],
    "over_result_max": [(3, 3, 2), (7, 4, 1)],
}


@pytest.mark.parametrize("form", sorted(OTHER_TWO_CHOOSE))
def test_other_two_choose_forms_refused(form):
    from ceph_tpu.crush import constants as CC
    from ceph_tpu.crush.map import Rule, RuleStep

    cmap, d = load("map_big10k")
    root = d["map"]["rules"][0]["steps"][0][1]
    cmap.rules[9] = Rule(steps=[
        RuleStep(CC.CRUSH_RULE_SET_CHOOSELEAF_TRIES, 5, 0),
        RuleStep(CC.CRUSH_RULE_SET_CHOOSE_TRIES, 100, 0),
        RuleStep(CC.CRUSH_RULE_TAKE, root, 0),
        *(RuleStep(*s) for s in OTHER_TWO_CHOOSE[form]),
        RuleStep(CC.CRUSH_RULE_EMIT, 0, 0)])
    with pytest.raises(Ineligible):
        analyze(cmap, 9, 8)

"""CrushWrapper facade tests — mirrors src/test/crush/CrushWrapper.cc
scenarios: topology edits (insert/move/adjust, :87-964), device classes
(device_class_clone :1148, populate_classes :1227), simple-rule
generation, and the upmap engine (try_remap_rule :1261)."""

import pytest

from ceph_tpu.crush import constants as C
from ceph_tpu.crush.wrapper import CrushWrapper


def build_cluster(hosts=4, osds_per_host=2, weight=0x10000):
    """root -> host{i} -> osd, all straw2, via insert_item only (the
    facade path, like the reference tests)."""
    w = CrushWrapper()
    dev = 0
    for h in range(hosts):
        for _ in range(osds_per_host):
            w.insert_item(dev, weight, f"osd.{dev}",
                          {"host": f"host{h}", "root": "default"})
            dev += 1
    return w


def test_insert_item_builds_hierarchy():
    w = build_cluster()
    root = w.get_item_id("default")
    assert root < 0
    hosts = w.get_children(root)
    assert len(hosts) == 4
    assert {w.get_item_name(h) for h in hosts} == \
        {f"host{i}" for i in range(4)}
    for h in hosts:
        assert w.get_bucket_type(h) == w.get_type_id("host")
        assert len(w.get_children(h)) == 2
    # weights accumulated up the chain
    assert w.get_bucket(root).weight == 8 * 0x10000
    assert w.get_bucket(hosts[0]).weight == 2 * 0x10000


def test_adjust_item_weight_propagates():
    w = build_cluster()
    root = w.get_item_id("default")
    h0 = w.get_item_id("host0")
    w.adjust_item_weight(0, 0x30000)
    assert w.get_item_weight(0) == 0x30000
    assert w.get_bucket(h0).weight == 0x40000
    assert w.get_bucket(root).weight == 10 * 0x10000


def test_remove_item_propagates():
    w = build_cluster()
    root = w.get_item_id("default")
    w.remove_item(7)
    assert w.get_bucket(root).weight == 7 * 0x10000
    with pytest.raises(KeyError):
        w.get_item_weight(7)


def test_move_bucket():
    w = build_cluster(hosts=2)
    w.insert_item(99, 0x10000, "osd.99",
                  {"host": "hostx", "root": "other"})
    root = w.get_item_id("default")
    hx = w.get_item_id("hostx")
    w.move_bucket(hx, {"root": "default"})
    assert hx in w.get_children(root)
    assert w.get_bucket(root).weight == 5 * 0x10000
    other = w.get_item_id("other")
    assert w.get_bucket(other).weight == 0


def test_move_bucket_under_itself_rejected():
    w = build_cluster(hosts=2)
    with pytest.raises(ValueError):
        w.move_bucket(w.get_item_id("default"),
                      {"host": "host0", "root": "default"})


def test_swap_bucket():
    w = build_cluster(hosts=2)
    h0, h1 = w.get_item_id("host0"), w.get_item_id("host1")
    w.adjust_item_weight(0, 0x20000)
    a_items = list(w.get_bucket(h0).items)
    b_items = list(w.get_bucket(h1).items)
    w.swap_bucket(h0, h1)
    assert w.get_bucket(h0).items == b_items
    assert w.get_bucket(h1).items == a_items
    root = w.get_item_id("default")
    assert w.get_bucket(root).weight == 5 * 0x10000


def test_name_maps():
    w = build_cluster(hosts=1)
    assert w.get_item_id("osd.0") == 0
    assert w.name_exists("host0")
    w.rename_item("host0", "hostA")
    assert w.name_exists("hostA") and not w.name_exists("host0")
    with pytest.raises(ValueError):
        w.set_item_name(0, "hostA")  # duplicate
    with pytest.raises(KeyError):
        w.get_item_id("nope")


def test_do_rule_on_facade_map():
    w = build_cluster(hosts=4)
    rid = w.add_simple_rule("replicated", "default", "host", "",
                            "firstn")
    weight = [0x10000] * 8
    for x in range(32):
        res = w.do_rule(rid, x, 3, weight)
        assert len(res) == 3
        hosts = {w.get_parent_of_type(o, w.get_type_id("host"))
                 for o in res}
        assert len(hosts) == 3  # failure-domain separation


def test_device_classes_shadow_tree():
    w = build_cluster(hosts=4)
    for d in range(8):
        w.set_item_class(d, "ssd" if d % 2 == 0 else "hdd")
    w.populate_classes()
    root = w.get_item_id("default")
    cid = w.get_or_create_class_id("ssd")
    shadow = w.class_bucket[(root, cid)]
    assert w.get_item_name(shadow) == "default~ssd"
    leaves = w.get_leaves(shadow)
    assert sorted(leaves) == [0, 2, 4, 6]
    assert w.get_bucket(shadow).weight == 4 * 0x10000

    # a class rule maps only to devices of that class
    rid = w.add_simple_rule("ssd_rule", "default", "host", "ssd",
                            "firstn")
    weight = [0x10000] * 8
    for x in range(32):
        res = w.do_rule(rid, x, 3, weight)
        assert len(res) == 3
        assert all(o % 2 == 0 for o in res), res


def test_device_class_missing_raises():
    w = build_cluster(hosts=2)
    with pytest.raises(KeyError):
        w.add_simple_rule("r", "default", "host", "nvme", "firstn")


def test_create_rule_signature_from_ec_interface():
    """interface.create_rule must be resolvable against the facade
    (in round 2 no object satisfied that signature)."""
    from ceph_tpu.ec.jerasure import make_jerasure

    w = build_cluster(hosts=4)
    code = make_jerasure({"technique": "reed_sol_van", "k": "2",
                          "m": "1", "w": "8"})
    rid = code.create_rule("ecpool", w)
    rule = w.crush.rules[rid]
    assert rule.type == 3
    assert rule.steps[1].op == C.CRUSH_RULE_CHOOSELEAF_INDEP


def test_shadow_tree_tracks_topology_edits():
    """Edits after populate_classes must not leave stale shadow trees
    (weights and membership refresh before the next map consumption),
    and shadow ids stay stable so existing class rules remain valid."""
    w = build_cluster(hosts=4)
    for d in range(8):
        w.set_item_class(d, "ssd" if d % 2 == 0 else "hdd")
    rid = w.add_simple_rule("ssdr", "default", "host", "ssd", "firstn")
    root = w.get_item_id("default")
    cid = w.get_or_create_class_id("ssd")
    shadow_before = w.class_bucket[(root, cid)]

    w.adjust_item_weight(0, 0x80000)
    w.remove_item(2)
    weight = [0x10000] * 8
    res = [w.do_rule(rid, x, 3, weight) for x in range(32)]
    # shadow refreshed: id stable, weight current, osd 2 gone
    assert w.class_bucket[(root, cid)] == shadow_before
    assert not any(2 in m for m in res)
    assert all(o % 2 == 0 for m in res for o in m)
    assert w.get_bucket(shadow_before).weight == \
        0x80000 + 2 * 0x10000  # osds 0,4,6


def test_failed_move_does_not_corrupt_map():
    w = build_cluster(hosts=2)
    root = w.get_item_id("default")
    before = w.get_bucket(root).weight
    with pytest.raises(ValueError):
        w.move_bucket(root, {"host": "host0", "root": "default"})
    # root still intact and attached as before
    assert w.get_bucket(root).weight == before
    assert len(w.get_children(root)) >= 2
    assert w.do_rule(0, 1, 3, [0x10000] * 4) if 0 in w.crush.rules \
        else True


def test_reweight_recomputes_bottom_up():
    w = build_cluster(hosts=2)
    root = w.get_item_id("default")
    h0 = w.get_item_id("host0")
    # corrupt weights deliberately, then reweight restores consistency
    w.get_bucket(h0).item_weights[0] = 0x50000
    w.reweight()
    assert w.get_bucket(h0).weight == 0x50000 + 0x10000
    assert w.get_bucket(root).weight == 0x50000 + 3 * 0x10000


def test_calc_straw_v1_values():
    """Pin straw_calc_version=1 semantics: NO equal-weight skip (that
    branch is v0-only); at equal weights wnext=0 so the straw carries
    unchanged.  Hand-derived trace for [1, 1, 2] (16.16):
    items 0,1 -> straw 1.0; item 2 -> 1.0 * (1/(3/4))^(1/1) = 4/3."""
    from ceph_tpu.crush.builder import calc_straw

    got = calc_straw([0x10000, 0x10000, 0x20000])
    assert got[0] == got[1] == 0x10000
    assert got[2] == int((4 / 3) * 0x10000)
    # zero-weight items get zero straws (v1 branch)
    assert calc_straw([0, 0x10000])[0] == 0


def test_wrapper_serialization_roundtrip():
    w = build_cluster(hosts=2)
    for d in range(4):
        w.set_item_class(d, "ssd" if d % 2 else "hdd")
    w.add_simple_rule("r", "default", "host", "ssd", "firstn")
    from ceph_tpu.crush.map import ChooseArg, ChooseArgMap
    cam = ChooseArgMap()
    cam[0] = ChooseArg(ids=None, weight_set=[[0x8000, 0x10000]])
    w.crush.choose_args["p1"] = cam

    w2 = CrushWrapper.from_dict(w.to_dict())
    assert w2.get_item_id("default") == w.get_item_id("default")
    assert w2.get_item_class(1) == "ssd"
    assert w2.class_bucket == w.class_bucket
    # choose_args survive (CrushWrapper::encode parity)
    assert "p1" in w2.crush.choose_args
    assert w2.crush.choose_args["p1"][0].weight_set == \
        [[0x8000, 0x10000]]
    weight = [0x10000] * 4
    for x in range(32):
        assert w.do_rule(0, x, 2, weight) == w2.do_rule(0, x, 2, weight)


# -- try_remap_rule (the upmap engine) --------------------------------------

def test_try_remap_rule_swaps_overfull():
    w = build_cluster(hosts=4)
    rid = w.add_simple_rule("r", "default", "host", "", "firstn")
    orig = [0, 2, 4]
    out = w.try_remap_rule(rid, 3, overfull={0}, underfull=[6],
                           more_underfull=[], orig=orig)
    assert out == [6, 2, 4]


def test_try_remap_rule_prefers_same_failure_domain():
    w = build_cluster(hosts=4)
    rid = w.add_simple_rule("r", "default", "host", "", "firstn")
    # osd 1 shares host0 with the overfull osd 0: valid swap in place
    out = w.try_remap_rule(rid, 3, overfull={0}, underfull=[1],
                           more_underfull=[], orig=[0, 2, 4])
    assert out == [1, 2, 4]


def test_try_remap_rule_skips_used_and_orig():
    w = build_cluster(hosts=4)
    rid = w.add_simple_rule("r", "default", "host", "", "firstn")
    # candidate 2 is already in orig -> must not be chosen twice
    out = w.try_remap_rule(rid, 3, overfull={0}, underfull=[2, 6],
                           more_underfull=[], orig=[0, 2, 4])
    assert out == [6, 2, 4]


def test_try_remap_rule_no_candidates_keeps_orig():
    w = build_cluster(hosts=4)
    rid = w.add_simple_rule("r", "default", "host", "", "firstn")
    out = w.try_remap_rule(rid, 3, overfull={0}, underfull=[],
                           more_underfull=[], orig=[0, 2, 4])
    assert out == [0, 2, 4]


def test_try_remap_rule_more_underfull_fallback():
    """more_underfull doesn't steer bucket selection (only `underfull`
    feeds underfull_buckets, CrushWrapper.cc:3884), so a fallback
    candidate must sit under an already-chosen bucket to be used."""
    w = build_cluster(hosts=4)
    rid = w.add_simple_rule("r", "default", "host", "", "firstn")
    out = w.try_remap_rule(rid, 3, overfull={0}, underfull=[],
                           more_underfull=[1], orig=[0, 2, 4])
    assert out == [1, 2, 4]
    # a cross-host fallback alone cannot be reached
    out = w.try_remap_rule(rid, 3, overfull={0}, underfull=[],
                           more_underfull=[6], orig=[0, 2, 4])
    assert out == [0, 2, 4]

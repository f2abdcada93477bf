"""The LRC cell's pieces on the CPU: the reference that reads the
rule's retry-budget steps against the native C mapper (built from the
reference's ``mapper.c``), and the cell's check, sound and with the
timed path broken underneath."""

import json
import time

import numpy as np
import pytest

from benchmark.lib import faults, harness
from benchmark.lib.crushmap_steps import build_map_with_steps
from benchmark.lib.spec import BENCH, Cell
from benchmark.reference import crush_rules

CONFIG = json.loads((BENCH / "configs" / "crush10k_lrc.json").read_text())
TINY = json.loads((BENCH / "tests" / "data" / "tiny_lrc.json").read_text())
TRAFFIC = json.loads((BENCH / "traffic" / "remap_lrc8.json").read_text())


def lrc_map(first_step_holes: bool):
    """The configuration's map; with ``first_step_holes`` the root
    holds racks 0 and 2 and one host of rack 1 at equal weights, so the
    first step leaves a slot empty (a descent into the host finds no
    rack) in many inputs, either slot, and both in some."""
    d = build_map_with_steps(CONFIG["crush"])
    if first_step_holes:
        racks = [b for b in d["buckets"] if b["type"] == 2]
        host = racks[1]["items"][0]
        w = racks[0]["weight"]
        d["buckets"][-1].update(
            items=[racks[0]["id"], racks[2]["id"], host],
            item_weights=[w] * 3, size=3, weight=3 * w)
    return d


# rack_out: inputs that land on the emptied rack run all 100 rounds of
# the reference's Python loops, so fewer of them
@pytest.mark.parametrize("case,n", [("osds_out", 2048), ("rack_out", 256),
                                    ("first_step_holes", 2048)])
def test_reference_matches_native_mapper(case, n):
    from ceph_tpu.crush.map import CrushMap
    from ceph_tpu.crush.native import NativeMapper

    d = lrc_map(case == "first_step_holes")
    rng = np.random.default_rng(26)
    weight = np.full(d["max_devices"], 0x10000, np.uint32)
    weight[rng.choice(d["max_devices"], 100, replace=False)] = 0
    if case == "rack_out":
        weight[:500] = 0                 # every OSD of rack 0
    xs = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    res, lens = NativeMapper(CrushMap.from_dict(d)).map_batch(
        0, xs, 8, weight)
    ref = crush_rules.Map(d)
    w = weight.tolist()
    for i, x in enumerate(xs.tolist()):
        assert ref.do_rule(0, x, 8, w) == res[i, :lens[i]].tolist(), x
    if case == "first_step_holes":
        assert {0, 4, 8} <= set(lens.tolist())
    if case == "rack_out":
        assert (res == 0x7FFFFFFF).any()


def tiny_cell() -> Cell:
    return Cell(name="tiny.remap_lrc8", chips=1, config=TINY,
                traffic=dict(TRAFFIC, check_uniform=16, max_failed=3),
                end_to_end=[{"name": "placements_per_s", "unit": "x"},
                            {"name": "setup_s", "unit": "x"}],
                per_layer=[])


def run(seed: int, patch=None):
    return harness.run_cell(tiny_cell(), seed, 0.5, False,
                            time.monotonic(), require_tpu=False,
                            window_patch=patch)


def test_sound_run_is_correct():
    line = run(2 ** 31 + 26)
    assert line["correct"], line["check"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"placements_per_s", "setup_s"}


@pytest.mark.parametrize("patch", [faults.placement_stale_epoch,
                                   faults.placement_unchanged_state,
                                   faults.placement_altered_answer],
                         ids=lambda f: f.__name__)
def test_broken_timed_path_is_not_correct(patch):
    line = run(23, patch)
    assert not line["correct"], (patch.__name__, line["check"])

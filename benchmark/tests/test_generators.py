"""Each traffic generator's window and check at tiny sizes on the CPU:
sound runs come out correct, and with the timed path broken underneath (the
control, and each fault of ``lib/faults.py``) the same run comes out
not correct.  The harness's look for a chip is skipped."""

import json
import time

import pytest

from benchmark.lib import faults, harness
from benchmark.lib.spec import BENCH, Cell

DATA = BENCH / "tests" / "data"


def tiny_cell(kind: str) -> Cell:
    if kind.startswith("remap"):
        config = json.loads((DATA / "tiny_crush.json").read_text())
        traffic = dict(json.loads(
            (BENCH / "traffic" / f"{kind}.json").read_text()),
            check_uniform=16, max_failed=3)
        e2e = ["placements_per_s", "setup_s"]
    else:
        config = json.loads((DATA / "tiny_rados.json").read_text())
        traffic = json.loads((BENCH / "traffic" / f"{kind}.json")
                             .read_text())
        traffic.update(object_bytes=1 << 16, in_flight=4, readers=4,
                       objects=24, warm_reads=2, keep_share=0.5,
                       check_objects=8, warm_batches=[1, 2, 4])
        e2e = (["write_MBps", "setup_s"]
               if kind.startswith("write") else ["read_MBps", "setup_s"])
    return Cell(name=f"tiny.{kind}", chips=1, config=config,
                traffic=traffic,
                end_to_end=[{"name": n, "unit": "x"} for n in e2e],
                per_layer=[])


KINDS = ["remap_rep3", "remap_ec11", "write_4m", "degraded_read_4m"]
SECONDS = {"remap_rep3": 0.5, "remap_ec11": 0.5, "write_4m": 1.5,
           "degraded_read_4m": 1.5}


def run(kind: str, seed: int, patch=None):
    return harness.run_cell(tiny_cell(kind), seed, SECONDS[kind], False,
                            time.monotonic(), require_tpu=False,
                            window_patch=patch)


@pytest.mark.parametrize("kind", KINDS)
def test_sound_run_is_correct(kind):
    line = run(kind, 2 ** 31 + 17)
    assert line["correct"], line["check"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {m["name"] for m in
                                    tiny_cell(kind).end_to_end}


BROKEN = [(k, "control") for k in KINDS] + [
    (k, f) for k in KINDS
    for f in ("unchanged_state", "half_batch", "altered_answer")]


@pytest.mark.parametrize("kind,mode", BROKEN)
def test_broken_timed_path_is_not_correct(kind, mode):
    gen = tiny_cell(kind).traffic["generator"]
    patch = (faults.CONTROLS[gen] if mode == "control"
             else faults.FAULTS[gen][mode])
    line = run(kind, 23, patch)
    assert not line["correct"], (mode, line["check"])


def test_write_acknowledged_short_of_a_shard_is_not_correct():
    """Recovery may restore the shard before the check reads the stores;
    the acknowledgement itself still breaks the guarantee."""
    line = run("write_4m", 29, faults.write_lost_push)
    assert line["check"]["degraded_acks"]["value"] > 0
    assert not line["correct"]

"""The trace reduction, on a small trace recorded on a TPU v5e by
``record_trace_fixture.py``: one 1,024-PG pool mapped, 50 ms asleep,
one 2-object encode batch and one decode, inside ``bench.window``."""

import gzip
import shutil

import pytest

from benchmark.lib import trace as T
from benchmark.lib.spec import BENCH

FIXTURE = BENCH / "tests" / "data" / "trace_fixture.xplane.pb.gz"


@pytest.fixture(scope="module")
def tr(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "fixture.xplane.pb"
    with gzip.open(FIXTURE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return T.load(str(path))


def test_window_and_spans_come_from_the_benchmark_annotations(tr):
    names = {s[2] for s in tr.spans}
    assert {"bench.window", "bench.map_all", "bench.sleep",
            "bench.encode", "bench.decode"} <= names
    lo, hi = tr.window
    assert 0.06e9 < hi - lo < 0.2e9


def test_busy_time_is_the_union_of_device_ops_inside_the_window(tr):
    lo, hi = tr.window
    busy = T.busy_ns(tr, 0)
    gaps = T.idle_gaps(tr, 0)
    assert busy + sum(b - a for a, b in gaps) == pytest.approx(hi - lo)
    assert 0 < busy < (hi - lo) - 0.05e9   # the 50 ms sleep is idle


def test_the_longest_idle_gap_is_labelled_with_the_open_span(tr):
    bd = T.breakdown(tr, 0)
    label, seconds = bd["idle_gaps"][0]
    assert label == "bench.sleep" and 0.045 < seconds < 0.06
    assert len(bd["device_ops"]) == 10
    assert all(s > 0 for _name, s in bd["device_ops"])


def test_gf_kernel_calls_are_found_with_their_shapes(tr):
    calls = T.gf_kernel_calls(tr, 0)
    shapes = sorted((r_in, r_out, lanes) for _ns, r_in, r_out, lanes
                    in calls)
    # 2 x 64 KiB objects: 8 rows of 16 KiB in, 3 out; one decode of
    # 8 rows of 8 KiB
    assert shapes == [(8, 3, 16384), (8, 8, 8192)]
    assert all(ns > 0 for ns, *_ in calls)


def test_module_time_of_the_placement_program(tr):
    ns = T.module_ns(tr, 0, "jit_single_pg")
    assert 0 < ns < T.busy_ns(tr, 0)
    assert T.module_ns(tr, 0, "jit_no_such_program") == 0


def test_union_and_gaps_on_synthetic_events():
    t = T.Trace(ops={0: [(10, 20, "a"), (15, 30, "b"), (40, 50, "c")]},
                window=(0, 60))
    assert T.union([(10, 20), (15, 30), (40, 50)]) == [(10, 30), (40, 50)]
    assert T.busy_ns(t, 0) == 30
    assert T.idle_gaps(t, 0) == [(0, 10), (30, 40), (50, 60)]
    t.window = (12, 45)
    assert T.busy_ns(t, 0) == 18 + 5

"""Rates, percentiles, peaks and kernel costs."""

import math

import pytest

from benchmark.lib import kernel_cost, peaks
from benchmark.lib.stats import Op, Window, latency_percentile, rate


def window(lat_ok):
    w = Window(t0=0.0)
    for i, (lat, ok) in enumerate(lat_ok):
        w.ops.append(Op(key=i, units=10, t_submit=float(i),
                        t_done=float(i) + lat, ok=ok))
    return w


def test_rate_counts_completed_work_to_the_last_completion():
    w = window([(1.0, True), (1.0, True), (1.0, False)])
    assert rate(w) == pytest.approx(20 / 2.0)
    assert rate(Window(t0=0.0)) is None


def test_p95_is_nearest_rank_and_a_failure_is_infinitely_late():
    w = window([(0.01 * (i + 1), True) for i in range(100)])
    assert latency_percentile(w, 95) == pytest.approx(0.95)
    w.ops[-1].ok = False
    w.ops[-2].ok = False
    w.ops[-3].ok = False
    w.ops[-4].ok = False
    w.ops[-5].ok = False
    assert latency_percentile(w, 95) == pytest.approx(0.95)
    w.ops[-6].ok = False
    assert math.isinf(latency_percentile(w, 95))


def test_peaks_are_published_figures_and_unknown_kinds_fail():
    p = peaks.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")


def test_gf_kernel_cost_and_roofline():
    ops, nbytes = kernel_cost.gf_matmul_cost(8, 3, 1 << 19)
    assert ops == 2 * 24 * 64 * (1 << 19)
    assert nbytes == 11 * (1 << 19)
    p = peaks.peaks("TPU v5 lite")
    least = nbytes / p["hbm_bytes_per_s"]
    share, bound = kernel_cost.roofline_share(ops, nbytes, 2 * least, p)
    assert bound == "bytes" and share == pytest.approx(50.0)
    ops, nbytes = kernel_cost.gf_matmul_cost(8, 8, 1 << 19)
    assert kernel_cost.roofline_share(ops, nbytes, 1.0, p)[1] == "ops"


def test_ack_tap_counts_only_degraded_ec_write_acks():
    from types import SimpleNamespace

    from benchmark.lib.rados import AckTap

    replies = iter([{"ok": True, "degraded": True},
                    {"ok": True, "degraded": False},
                    {"ok": False, "degraded": True},
                    {"ok": True, "degraded": True}])
    client = SimpleNamespace(msgr=SimpleNamespace(
        call=lambda addr, msg, timeout=None: next(replies)))
    tap = AckTap(client)
    got = [client.msgr.call("osd", {"type": t}, timeout=1)
           for t in ("ec_write", "ec_write", "ec_write", "rep_write")]
    assert got[0] == {"ok": True, "degraded": True}
    assert tap.degraded == 1

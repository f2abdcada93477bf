"""The continuous stats plane, end to end.

Acceptance drill: a MiniCluster write burst followed by killing one
OSD must yield (1) a `pool-stats` series showing nonzero client write
B/s and then recovery B/s, (2) a `progress` event that starts on the
failure and completes with fraction 1.0, (3) health transitioning
HEALTH_WARN(PG_DEGRADED) -> HEALTH_OK, and (4) a
`dump_metrics_history` ring on every daemon with >= 3 samples whose
derived rates are consistent with the counter deltas.  Plus the
satellites: pg_stats staleness (STALE_PG_STATS + aging), bench stage
SLO blocks, and the perf_history trajectory."""

import glob
import json
import os
import time

import pytest

from ceph_tpu.common.admin_socket import AdminSocket
from ceph_tpu.common.config import Config
from ceph_tpu.services.cluster import MiniCluster


def _fast_conf(**extra):
    conf = Config()
    conf.set("osd_heartbeat_interval", 0.2)
    conf.set("osd_heartbeat_grace", 1.0)
    conf.set("mon_osd_down_out_interval", 1.0)
    conf.set("osd_pg_stat_report_interval", 0.2)
    conf.set("metrics_history_interval", 0.2)
    conf.set("osd_scrub_interval", 0.0)
    for k, v in extra.items():
        conf.set(k, v)
    return conf


# -- the acceptance drill ---------------------------------------------------

def test_write_burst_failure_recovery_stats_plane():
    cl = MiniCluster(n_osds=4, config=_fast_conf()).start()
    try:
        cl.create_replicated_pool(1, pg_num=8, size=2)
        c = cl.client("burst")
        for i in range(24):
            c.put(1, f"obj-{i}", b"x" * 65536)
        time.sleep(0.5)

        # (1a) the pool-stats series saw the client write burst
        series = cl.pool_stats(1)["pools"]["1"]["series"]
        assert len(series) >= 2
        assert max(r["wr_bps"] for r in series) > 0
        assert max(r["wr_ops_s"] for r in series) > 0

        # failure: kill one OSD, then watch the plane tell the story
        victim = cl.status()["up_osds"][-1]
        t_kill = time.time()
        cl.kill_osd(victim)

        # (3a) HEALTH_WARN with the PG_DEGRADED check
        deadline = time.monotonic() + 30
        saw_degraded = False
        while time.monotonic() < deadline and not saw_degraded:
            h = cl.health()
            saw_degraded = (h["status"] == "HEALTH_WARN"
                            and "PG_DEGRADED" in h["check_codes"])
            time.sleep(0.05)
        assert saw_degraded, "no HEALTH_WARN(PG_DEGRADED) after kill"

        # (3b) ... transitioning back to HEALTH_OK once recovered
        cl.wait_for_health_ok(timeout=60)

        # (2) a progress event that started on the failure and
        # completed with fraction 1.0
        events = cl.progress()["events"]
        assert events, "no recovery progress event"
        ev = events[-1]
        assert ev["started_at"] >= t_kill - 1.0
        assert ev["done"] and ev["fraction"] == 1.0
        assert ev.get("ended_at", 0) >= ev["started_at"]

        # (1b) the series saw recovery traffic
        series = cl.pool_stats(1)["pools"]["1"]["series"]
        assert max(r["recovery_bps"] for r in series) > 0, \
            "recovery B/s never surfaced in pool-stats"

        # (4) every daemon's metrics-history ring: >= 3 samples, and
        # the derived rates are exactly consistent with the counter
        # deltas in the samples they were derived from
        socks = sorted(glob.glob(os.path.join(cl.asok_dir,
                                              "*.asok")))
        assert len(socks) >= 5  # mon + 3 live osds + client
        for path in socks:
            hist = AdminSocket.request(path, "dump_metrics_history")
            assert hist["n"] >= 3, \
                f"{os.path.basename(path)}: ring has {hist['n']} " \
                f"samples"
            assert hist["rates"], "no counter ever moved?"
            _check_rates_consistent(hist)
    finally:
        cl.shutdown()


def test_cli_pool_stats_progress_top(capsys):
    """The operator surface: `ceph_cli pool-stats` / `progress`
    against the monitor, `top` / `history` against the asok dir."""
    from ceph_tpu.tools.ceph_cli import main as ceph_main

    cl = MiniCluster(n_osds=2, config=_fast_conf()).start()
    try:
        cl.create_replicated_pool(1, pg_num=4, size=2)
        c = cl.client("cli")
        for i in range(4):
            c.put(1, f"cli-{i}", b"z" * 4096)
        time.sleep(0.6)
        mon = f"{cl.mon.addr[0]}:{cl.mon.addr[1]}"
        assert ceph_main(["--mon", mon, "pool-stats", "1"]) == 0
        out = capsys.readouterr().out
        assert "pool 1:" in out and "wr " in out
        assert ceph_main(["--mon", mon, "progress"]) == 0
        out = capsys.readouterr().out
        assert "progress" in out or "recovery" in out
        assert ceph_main(["--asok-dir", cl.asok_dir, "top",
                          "--interval", "0.2", "--count", "1"]) == 0
        out = capsys.readouterr().out
        assert "ceph-tpu top" in out and "daemon" in out
        assert ceph_main(["--asok-dir", cl.asok_dir,
                          "history"]) == 0
        out = capsys.readouterr().out
        assert "time" in out.splitlines()[0]
    finally:
        cl.shutdown()


def _flatten(perf):
    out = {}
    for logger, counters in perf.items():
        for key, val in counters.items():
            if isinstance(val, (int, float)):
                out[f"{logger}.{key}"] = float(val)
    return out


def _check_rates_consistent(hist):
    """Each reported rate must equal the clamped counter delta over
    the monotonic interval of its sample pair."""
    samples = hist["samples"]
    flats = [_flatten(s["perf"]) for s in samples]
    checked = 0
    for key, points in hist["rates"].items():
        # points align with consecutive sample pairs where the
        # counter exists on both sides
        idx = 0
        for (a, fa), (b, fb) in zip(zip(samples, flats),
                                    zip(samples[1:], flats[1:])):
            if key not in fa or key not in fb:
                continue
            want = max(0.0, (fb[key] - fa[key])
                       / max(1e-9, b["mono"] - a["mono"]))
            got = points[idx]["rate"]
            assert got == pytest.approx(want, rel=1e-6, abs=1e-9), \
                f"{key}: rate {got} != delta/dt {want}"
            idx += 1
            checked += 1
        assert idx == len(points)
    assert checked > 0


# -- satellite: pg_stats staleness ------------------------------------------

def test_pg_stats_go_stale_and_age_out():
    """Down an OSD whose PGs have no surviving holder: its PGs'
    stats must go STALE (health check) and then age out entirely
    instead of poisoning the PGMap forever."""
    conf = _fast_conf(mon_pg_stats_stale_grace=1.5,
                      # keep the dead osd "in": a remap would elect a
                      # new (empty) primary whose fresh reports would
                      # mask the staleness under test
                      mon_osd_down_out_interval=3600.0)
    cl = MiniCluster(n_osds=2, config=conf).start()
    try:
        cl.create_replicated_pool(1, pg_num=4, size=1)
        c = cl.client("w")
        for i in range(4):
            c.put(1, f"s-{i}", b"y" * 1024)
        # every PG reported by its (single) holder
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            pg = cl.status()["pgmap"]
            if pg["pgs_reported"] == pg["pgs_total"]:
                break
            time.sleep(0.1)
        assert cl.status()["pgmap"]["pgs_reported"] == 4

        victim = cl.status()["up_osds"][0]
        cl.kill_osd(victim)

        # STALE_PG_STATS surfaces after the grace
        deadline = time.monotonic() + 20
        saw_stale = False
        while time.monotonic() < deadline and not saw_stale:
            h = cl.health()
            saw_stale = "STALE_PG_STATS" in h.get("check_codes", [])
            time.sleep(0.1)
        assert saw_stale, "STALE_PG_STATS never fired"

        # ... and the entries age out (4x grace), shrinking
        # pgs_reported instead of keeping dead state forever
        deadline = time.monotonic() + 30
        aged = False
        while time.monotonic() < deadline and not aged:
            pg = cl.status()["pgmap"]
            aged = pg["pgs_reported"] < 4
            time.sleep(0.2)
        assert aged, "stale pg_stats entries never aged out"
    finally:
        cl.shutdown()


# -- satellite: bench SLO blocks --------------------------------------------

def test_bench_stage_emits_slo_and_counter_deltas(capsys):
    """Every bench stage JSON carries an SLO block and the counter
    deltas booked during the stage (the device-plane story)."""
    import bench

    bench._stage_ec_batch("cpu", k=2, m=1, n_stripes=4, chunk=512,
                          iters=2)
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines()
             if ln.startswith(bench.RESULT_TAG)]
    assert lines
    r = json.loads(lines[0][len(bench.RESULT_TAG):])
    slo = r["slo"]
    assert slo["metric"] == "ec_batch_speedup"
    assert "floor" in slo and isinstance(slo["pass"], bool)
    assert any(k.startswith("ec.engine.") for k in r["counters"])
    assert any(k.startswith("device.") for k in r["counters"])


def test_bench_slo_block_semantics():
    import bench

    ok = bench._slo("cluster_write_iops", 500.0, p99_ms=12.5)
    assert ok["pass"] is True and ok["p99_ms"] == 12.5
    bad = bench._slo("cluster_write_iops", 3.0)
    assert bad["pass"] is False
    unfloored = bench._slo("some_unfloored_metric", 1.0)
    assert "pass" not in unfloored


# -- satellite: perf_history trajectory -------------------------------------

def test_perf_history_renders_bench_trajectory(tmp_path):
    """A BENCH_r01..r05 series renders as a trajectory table with
    per-metric deltas."""
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve()
                           .parent.parent))
    from tools import perf_history

    for n in range(1, 6):
        rate = 80_000.0 + 1_000.0 * n
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps({
            "n": str(n), "rc": "0",
            "parsed": {"metric": "crush_mappings_per_sec",
                       "value": rate, "platform": "cpu",
                       "vs_baseline": rate / 85099.6},
            "tail": f"# ec k=8,m=3: encode 0.{n}0 GB/s, decode 0.50 "
                    f"GB/s on cpu (compile 0.1s)\n"}))
    rows = perf_history.load_all(str(tmp_path))
    assert len(rows) == 5
    perf_history.compute_deltas(rows)
    by_run = {r["run"]: r for r in rows}
    # r05 recorded the measured trajectory numbers
    assert by_run["r05"]["metrics"]["crush_mappings_s"] > 0
    assert "crush_mappings_s" in by_run["r05"]["deltas"]
    table = perf_history.render(rows)
    assert "r05" in table and "crush_mappings_s" in table
    for row in rows:
        assert isinstance(row["regressions"], list)


def test_perf_history_regression_check(tmp_path):
    """A throughput drop beyond the threshold in the latest run is a
    red check (exit 1); a healthy series passes."""
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve()
                           .parent.parent))
    from tools import perf_history

    def write_run(n, rate, tail=""):
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps({
            "n": n, "cmd": "bench", "rc": 0, "tail": tail,
            "parsed": {"metric": "crush_mappings_per_sec",
                       "value": rate, "platform": "cpu",
                       "vs_baseline": rate / 85099.6}}))

    write_run(1, 100000.0,
              tail="# cluster 4-osd: write 500.0 IOPS; "
                   "seq 1000.0 IOPS")
    write_run(2, 101000.0,
              tail="# cluster 4-osd: write 520.0 IOPS; "
                   "seq 990.0 IOPS")
    assert perf_history.main([str(tmp_path), "--check"]) == 0
    # now a 60% crush regression in the latest run
    write_run(3, 40000.0)
    assert perf_history.main([str(tmp_path), "--check"]) == 1
    rows = perf_history.load_all(str(tmp_path))
    perf_history.compute_deltas(rows)
    assert rows[-1]["regressions"]
    # a bench-recorded failing SLO block is a regression by itself
    (tmp_path / "BENCH_r04.json").write_text(json.dumps({
        "n": 4, "cmd": "bench", "rc": 0,
        "tail": "# slo cluster_write_iops: value 50 floor 100 -> "
                "FAIL",
        "parsed": {"value": 100000.0, "platform": "cpu",
                   "slo": {"metric": "crush_big10k_mappings_per_sec",
                           "value": 100000.0, "floor": 80000,
                           "pass": True}}}))
    assert perf_history.main([str(tmp_path), "--check"]) == 1


def test_perf_history_zero_copy_goal_gate(tmp_path):
    """copy_bytes_per_op is gated absolutely from r14 on: a run above
    0.6x the r13 baseline (191,330 -> goal 114,798) red-checks even
    when the run-over-run delta stays inside the drift threshold."""
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve()
                           .parent.parent))
    from tools import perf_history

    def write_run(n, bpo):
        cl = json.dumps({"copy": {"bytes_per_op": bpo}})
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps({
            "n": n, "cmd": "bench", "rc": 0,
            "tail": "# cluster json: " + cl,
            "parsed": {"value": 100000.0, "platform": "cpu"}}))

    write_run(13, 191330.0)  # the baseline run itself is not gated
    write_run(14, 110000.0)  # under the goal: ok
    assert perf_history.main([str(tmp_path), "--check"]) == 0
    write_run(14, 120000.0)  # a 37% cut, but above the 114,798 goal
    assert perf_history.main([str(tmp_path), "--check"]) == 1
    rows = perf_history.load_all(str(tmp_path))
    perf_history.compute_deltas(rows)
    assert any("zero-copy goal" in r
               for r in rows[-1]["regressions"])


# -- telemetry history/top views --------------------------------------------

def _hist_sample(ts, mono, bytes_out):
    return {"ts": ts, "mono": mono,
            "perf": {"msgr.osd.0": {"bytes_out": bytes_out,
                                    "bytes_in": 0}},
            "shapes": {}}


def test_history_view_time_aligned_merge():
    from ceph_tpu.tools import telemetry

    histories = {
        "osd.0": {"samples": [_hist_sample(100.0, 10.0, 0),
                              _hist_sample(101.0, 11.0, 1000),
                              _hist_sample(102.0, 12.0, 3000)]},
        "osd.1": {"samples": [_hist_sample(100.1, 20.0, 0),
                              _hist_sample(101.1, 21.0, 500)]},
    }
    view = telemetry.history_view(histories)
    lines = view.splitlines()
    assert "tx_B/s" in lines[0]
    assert len(lines) >= 3  # header + >=2 time buckets
    col = lines[0].split().index("tx_B/s")
    rates = [float(ln.split()[col]) for ln in lines[1:]]
    # bucket at ~101s sums osd.0 (1000/s) + osd.1 (500/s); the 102s
    # bucket is osd.0 alone at 2000/s
    assert 1500.0 in rates and 2000.0 in rates


def test_top_view_frame():
    from ceph_tpu.tools import telemetry

    prev = {"ts": 100.0, "daemons": {
        "osd.0": {"perf": {"msgr.osd.0": {"bytes_out": 0}},
                  "ops_in_flight": {"num_ops": 1}}},
        "unreachable": []}
    cur = {"ts": 101.0, "daemons": {
        "osd.0": {"perf": {"msgr.osd.0": {"bytes_out": 2000}},
                  "ops_in_flight": {"num_ops": 3}}},
        "unreachable": ["osd.9"]}
    frame = telemetry.top_view(prev, cur)
    assert "ops in flight: 3" in frame
    assert "unreachable: 1" in frame
    assert "osd.0" in frame

"""rados_bench harness + dmClock OpScheduler QoS enforcement."""

import threading
import time

from ceph_tpu.common.op_queue import (ClientInfo, MClockQueue,
                                      OpScheduler)
from ceph_tpu.tools.rados_bench import bench_minicluster


def test_rados_bench_minicluster_smoke():
    out = bench_minicluster(op="seq", seconds=1.0, concurrent=4,
                            object_size=4096, n_osds=3, pg_num=8)
    w, s = out["write"], out["seq"]
    assert w["ops"] > 0 and w["errors"] == 0
    assert s["ops"] > 0 and s["errors"] == 0
    assert w["iops"] > 0 and w["lat_p99_ms"] >= w["lat_p50_ms"]


def test_mclock_weight_shares_under_backlog():
    """Two classes, weight 4:1, full backlog: dmClock serves them in
    a 4:1 ratio (deterministic tag-order check, no threads)."""
    q = MClockQueue({
        "hi": ClientInfo(reservation=0.0, weight=4.0, limit=0.0),
        "lo": ClientInfo(reservation=0.0, weight=1.0, limit=0.0),
    })
    for i in range(50):
        q.enqueue("hi", f"h{i}", now=0.0)
        q.enqueue("lo", f"l{i}", now=0.0)
    served = []
    now = 0.0
    while len(served) < 40:
        got = q.dequeue(now)
        if got is None:
            now += 0.01
            continue
        served.append(got[0])
    hi = served.count("hi")
    assert 28 <= hi <= 36, f"expected ~32/40 hi, got {hi}"


def test_opscheduler_limit_ceiling():
    """A limited class cannot exceed its ops/sec ceiling even alone."""
    q = MClockQueue({
        "capped": ClientInfo(reservation=0.0, weight=1.0, limit=50.0),
    })
    sched = OpScheduler(queue=q, n_workers=2)
    try:
        t0 = time.monotonic()
        n = 12
        for _ in range(n):
            sched.submit("capped", lambda: None)
        dt = time.monotonic() - t0
        # 12 ops at 50/s needs >= ~0.2s (first is free)
        assert dt >= (n - 1) / 50.0 * 0.8, dt
    finally:
        sched.shutdown()


def test_rados_bench_qd_sweep_smoke():
    """The pipelined aio write path at a queue-depth sweep: each depth
    reports, the best is promoted, and the sweep rides the summary."""
    out = bench_minicluster(op="seq", seconds=0.8, concurrent=4,
                            object_size=4096, n_osds=3, pg_num=8,
                            qd_sweep=[4, 8])
    assert set(out["qd_sweep"]) == {"4", "8"}
    w = out["write"]
    assert w["qd"] in (4, 8)
    assert w["ops"] > 0 and w["errors"] == 0
    assert out["seq"]["ops"] > 0


def test_bench_init_probe_fail_fast():
    """The staged-lane backend-init probe: a worker that never emits its init
    line must be declared dead at INIT_DEADLINE (60 s default), not
    at the full worker deadline — checked here with a tiny deadline
    against a sleeping child."""
    import subprocess
    import sys

    import bench

    assert bench.INIT_DEADLINE <= 60.0  # the fail-fast contract
    proc = subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(30)"],
        stdout=subprocess.PIPE, text=True)
    try:
        stream = bench.Stream(proc, "probe-test")
        t0 = time.monotonic()
        got = stream.wait(lambda r: r.get("stage") == "init", 0.5)
        dt = time.monotonic() - t0
        assert got is None, "no init line must mean probe failure"
        assert dt < 5.0, f"probe waited {dt:.1f}s past its deadline"
        # kill returns only once the worker is gone: the next worker
        # may open the chip right after it
        stream.kill("test")
        assert proc.poll() is not None
    finally:
        proc.kill()
        proc.wait()


def test_bench_worker_balancer_smoke(tmp_path, monkeypatch, capsys):
    """The balancer bench lane end-to-end on a shrunk synthetic map:
    the record lands with the convergence trajectory perf_history
    ingests (kind/rounds/stddevs/sweep rate), and the offline loop
    actually converged."""
    import json

    import bench

    out = tmp_path / "BALANCE_r99.json"
    monkeypatch.setenv("CEPH_TPU_BALANCE_OSDS", "32")
    monkeypatch.setenv("CEPH_TPU_BALANCE_PGS", "128")
    monkeypatch.setenv("CEPH_TPU_BALANCE_SEED", "1")
    monkeypatch.setenv("CEPH_TPU_BALANCE_ITERS", "30")
    monkeypatch.setenv("CEPH_TPU_BALANCE_ROUNDS", "8")
    monkeypatch.setenv("CEPH_TPU_BALANCE_MAX_DEVIATION", "2")
    monkeypatch.setenv("CEPH_TPU_BALANCE_OUT", str(out))
    bench.worker_balancer()
    lines = [json.loads(ln.split(" ", 1)[1])
             for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("BENCH_RESULT ")]
    assert any(r.get("stage") == "balancer" for r in lines)
    rec = json.loads(out.read_text())
    assert rec["kind"] == "balance"
    assert rec["converged"]
    assert rec["final_stddev"] <= rec["initial_stddev"]
    assert rec["sweep_mappings_per_sec"] > 0
    assert rec["rounds"] >= 1 and rec["upmaps"] > 0

"""Manager daemon + balancer module: the closed upmap loop.

Acceptance drill (ISSUE 10): on a live MiniCluster, enabling the
balancer proposes ``pg_upmap_items`` through a monitor incremental
that every subscribed daemon observes, and the loop provably pauses
under PG_DEGRADED (an OSD is killed mid-loop).  Offline, the same
module converges a synthetic uneven map with batched per-pool sweeps.
Plus the module-plane satellites: ``mgr module ls|enable|disable``,
module-error health folded into the monitor's coded checks, the
``ceph_cli balancer``/``mgr`` verbs, and the stale-map failpoint.
"""

import glob
import os
import time

import pytest

from ceph_tpu.common.admin_socket import AdminSocket
from ceph_tpu.common.config import Config
from ceph_tpu.mgr import (evaluate, make_synthetic_map, run_offline)
from ceph_tpu.mgr.daemon import MgrModule, _ModuleSched
from ceph_tpu.services.cluster import MiniCluster


def _fast_conf(**extra):
    conf = Config()
    conf.set("osd_heartbeat_interval", 0.2)
    conf.set("osd_heartbeat_grace", 1.0)
    conf.set("mon_osd_down_out_interval", 1.0)
    conf.set("osd_pg_stat_report_interval", 0.2)
    conf.set("osd_scrub_interval", 0.0)
    conf.set("mgr_tick_interval", 0.1)
    conf.set("balancer_interval", 0.3)
    conf.set("balancer_max_deviation", 1)
    for k, v in extra.items():
        conf.set(k, v)
    return conf


def _wait(pred, timeout=30.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {msg}")


# -- offline: synthetic maps + convergence ----------------------------------

def test_synthetic_map_uneven_and_classes():
    m, w, rules = make_synthetic_map(
        n_osds=16, osds_per_host=2, hosts_per_rack=4, pg_num=64,
        seed=3, device_classes=["ssd", "hdd"], with_choose_args=True)
    # uneven: more than one distinct CRUSH weight step
    assert len({w.get_item_weight(d) for d in range(16)}) > 1
    assert set(rules) == {"repl", "repl-ssd", "repl-hdd"}
    assert set(m.pools) == {1, 2, 3}
    assert "compat" in m.crush.choose_args
    # the class rules map ONLY devices of their class (ssd = even
    # ids: classes assign round-robin)
    ssd = {d for d in range(16) if d % 2 == 0}
    for pid, want in ((2, ssd), (3, set(range(16)) - ssd)):
        pool = m.pools[pid]
        mapped = set()
        for ps in range(pool.pg_num):
            up, _p, _a, _ap = m.pg_to_up_acting_osds(pid, ps)
            mapped.update(o for o in up if o >= 0)
        assert mapped, f"pool {pid} mapped nothing"
        assert mapped <= want, f"pool {pid} left its device class"


@pytest.mark.slow
def test_offline_loop_converges_on_uneven_map():
    m, w, _rules = make_synthetic_map(
        n_osds=48, osds_per_host=4, hosts_per_rack=4, pg_num=256,
        seed=1)
    rec = run_offline(m, w, max_deviation=1, max_iterations=20,
                      max_rounds=15, seed=1)
    assert rec["converged"], rec
    assert rec["rounds"] >= 1
    assert rec["upmaps"] > 0
    # the ISSUE acceptance bar: deviation stddev reduced >= 5x
    assert rec["final_stddev"] * 5 <= rec["initial_stddev"], rec
    # every evaluation was a batched sweep: one launch per pool per
    # sweep, and the trajectory is monotone non-increasing
    assert rec["sweep_launches"] >= rec["rounds"] + 1
    traj = rec["stddev_trajectory"]
    assert all(b <= a + 1e-9 for a, b in zip(traj, traj[1:]))


def test_evaluate_per_pool_breakdown():
    m, w, _rules = make_synthetic_map(
        n_osds=8, osds_per_host=2, hosts_per_rack=2, pg_num=32,
        seed=2, device_classes=["ssd", "hdd"])
    ev = evaluate(m, w)
    # ONE batched launch per pool, every pool in the breakdown
    assert ev["sweep_launches"] == len(m.pools)
    assert set(ev["pools"]) == set(m.pools)
    for row in ev["pools"].values():
        assert row["stddev"] >= 0.0
        assert 0.0 <= row["score"] < 1.0
    assert ev["mapped_pgs"] == sum(p.pg_num for p in m.pools.values())


# -- live: module framework -------------------------------------------------

class _Boom(MgrModule):
    NAME = "boom"

    def tick(self):
        raise RuntimeError("boom")


def test_mgr_module_framework_and_health_fold():
    cl = MiniCluster(n_osds=3, config=_fast_conf()).start()
    try:
        mgr = cl.start_mgr()
        path = glob.glob(os.path.join(cl.asok_dir, "mgr.*.asok"))[0]

        rep = AdminSocket.request(path, "mgr", argv=["module", "ls"])
        assert "balancer" in rep["modules"]
        assert rep["modules"]["balancer"]["enabled"]

        rep = AdminSocket.request(
            path, "mgr", argv=["module", "disable", "balancer"])
        assert "success" in rep
        rep = AdminSocket.request(path, "balancer", argv=["status"])
        assert "error" in rep  # disabled modules take no commands
        rep = AdminSocket.request(
            path, "mgr", argv=["module", "enable", "balancer"])
        assert "success" in rep
        rep = AdminSocket.request(path, "balancer", argv=["status"])
        assert rep["active"] is False

        # a module that raises: jittered backoff records the error
        # and the monitor's coded health grows MGR_MODULE_ERROR
        mgr.modules["boom"] = _Boom(mgr)
        mgr.enabled["boom"] = True
        with mgr._lock:
            mgr._sched["boom"] = _ModuleSched()
        _wait(lambda: "MGR_MODULE_ERROR" in
              cl.health()["check_codes"], 20,
              "MGR_MODULE_ERROR health check")
        with mgr._lock:
            assert mgr._sched["boom"].error
        # disabling clears the fold on the next report
        mgr.enabled["boom"] = False
        _wait(lambda: "MGR_MODULE_ERROR" not in
              cl.health()["check_codes"], 20,
              "MGR_MODULE_ERROR to clear")
    finally:
        cl.shutdown()


# -- live: the closed loop --------------------------------------------------

def test_balancer_proposes_upmaps_and_pauses_degraded():
    # down-out disabled: the killed OSD stays IN, so PG_DEGRADED
    # holds for as long as it is dead and the pause is observable
    cl = MiniCluster(n_osds=4, config=_fast_conf(
        mon_osd_down_out_interval=600.0)).start()
    try:
        cl.create_replicated_pool(1, pg_num=32, size=2)
        # objects make degradation observable: PG state is computed
        # from shard deficits, so an empty pool never reports it
        c = cl.client("seed")
        for i in range(32):
            c.put(1, f"obj-{i}", b"x" * 4096)
        # manufacture imbalance: a half-weight device keeps its PGs
        # but its weight-proportional target halves
        cl.reweight_osd(0, 0.5)
        cl.wait_for_health_ok(timeout=60)
        epoch0 = cl.status()["epoch"]

        mgr = cl.start_mgr()
        bal = mgr.modules["balancer"]
        path = glob.glob(os.path.join(cl.asok_dir, "mgr.*.asok"))[0]
        rep = AdminSocket.request(path, "balancer", argv=["on"])
        assert "success" in rep

        # the loop proposes pg_upmap_items through a real monitor
        # incremental...
        _wait(lambda: len(cl.mon.map.pg_upmap_items) > 0, 60,
              "balancer upmap proposals at the monitor")
        assert cl.status()["epoch"] > epoch0
        # ...that every subscribed daemon observes
        pgid = next(iter(cl.mon.map.pg_upmap_items))

        def _osds_observed():
            return all(pgid in svc.map.pg_upmap_items
                       for svc in cl.osds.values())
        _wait(_osds_observed, 30, "OSD followers observing the upmap")
        _wait(lambda: pgid in mgr.map.pg_upmap_items, 30,
              "the mgr observing the upmap")  # and the mgr itself
        # the round logs its record after the LAST proposal commits,
        # while the monitor map shows the first one immediately
        _wait(lambda: bal.proposal_log, 30, "proposal round recorded")
        assert all(not p["degraded"] for p in bal.proposal_log)

        # kill an OSD mid-loop: the loop must pause while health
        # shows the cluster degraded, proposing nothing
        victim = cl.status()["up_osds"][-1]
        cl.kill_osd(victim)
        _wait(lambda: "PG_DEGRADED" in cl.health()["check_codes"],
              30, "PG_DEGRADED after kill")
        _wait(lambda: bal.paused, 30, "balancer pause")
        proposals_at_pause = len(bal.proposal_log)
        time.sleep(1.0)  # several ticks under degraded health
        assert bal.paused
        assert len(bal.proposal_log) == proposals_at_pause
        assert all(not p["degraded"] for p in bal.proposal_log)
        assert mgr.pc.dump()["balancer_paused"] >= 1

        # recovery completes -> the loop resumes
        cl.revive_osd(victim)
        cl.wait_for_health_ok(timeout=60)
        _wait(lambda: not bal.paused, 30, "balancer resume")

        # counters booked and live (OBS001's runtime face)
        pc = mgr.pc.dump()
        assert pc["balancer_rounds"] >= 1
        assert pc["balancer_sweep_launches"] >= 1
        assert pc["balancer_upmaps_proposed"] >= 1
    finally:
        cl.shutdown()


def test_balancer_stale_map_failpoint():
    cl = MiniCluster(n_osds=3, config=_fast_conf()).start()
    try:
        cl.create_replicated_pool(1, pg_num=16, size=2)
        cl.reweight_osd(0, 0.5)
        cl.wait_for_health_ok(timeout=60)
        mgr = cl.start_mgr()
        bal = mgr.modules["balancer"]
        cl.set_faults("mgr.balancer.stale_map=count:1")
        bal.active = True
        _wait(lambda: bal.stale_discards >= 1, 30,
              "stale-map discard")
        # the faulted round was discarded whole; the loop recovers
        # and a later clean sweep still lands proposals
        _wait(lambda: len(cl.mon.map.pg_upmap_items) > 0, 60,
              "post-discard proposals")
    finally:
        cl.set_faults("")
        cl.shutdown()


# -- CLI ---------------------------------------------------------------------

def test_ceph_cli_balancer_and_mgr_verbs(capsys):
    from ceph_tpu.tools import ceph_cli

    cl = MiniCluster(n_osds=3, config=_fast_conf()).start()
    try:
        cl.create_replicated_pool(1, pg_num=16, size=2)
        cl.start_mgr()

        rc = ceph_cli.main(["--asok-dir", cl.asok_dir,
                            "mgr", "module", "ls"])
        assert rc == 0
        assert "balancer" in capsys.readouterr().out

        rc = ceph_cli.main(["--asok-dir", cl.asok_dir,
                            "balancer", "status"])
        assert rc == 0
        assert '"active": false' in capsys.readouterr().out

        rc = ceph_cli.main(["--asok-dir", cl.asok_dir,
                            "balancer", "on"])
        assert rc == 0
        capsys.readouterr()

        # eval prints the per-pool score breakdown
        rc = ceph_cli.main(["--asok-dir", cl.asok_dir,
                            "balancer", "eval"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "cluster: stddev" in out
        assert "pool 1:" in out and "score" in out

        # no mgr socket -> clear failure
        rc = ceph_cli.main(["--asok-dir", "/nonexistent-dir",
                            "balancer", "status"])
        assert rc == 2
    finally:
        cl.shutdown()

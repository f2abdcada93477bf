"""Monitor quorum: election, replicated epochs, leader failover.

The round-3 acceptance test: a 3-monitor MiniCluster keeps
accepting writes after the leader is killed mid-workload, a restarted
monitor rejoins and catches up, and committed epochs NEVER fork — every
epoch present on two members is byte-identical.
"""

import itertools
import time

import pytest

from ceph_tpu.common.config import Config
from ceph_tpu.services.cluster import MiniCluster


def fast_conf():
    c = Config()
    c.set("osd_heartbeat_interval", 0.3)
    c.set("osd_heartbeat_grace", 1.5)
    c.set("mon_osd_down_out_interval", 2.0)
    c.set("mon_lease", 0.25)
    c.set("mon_election_timeout", 0.4)
    return c


def assert_no_fork(cluster):
    stores = [(r, dict(m._epochs)) for r, m in cluster.mons.items()]
    for (r1, e1), (r2, e2) in itertools.combinations(stores, 2):
        for v in sorted(set(e1) & set(e2)):
            assert e1[v] == e2[v], \
                f"epoch {v} forked between mon.{r1} and mon.{r2}"


@pytest.fixture
def cluster():
    c = MiniCluster(n_osds=4, hosts=4, config=fast_conf(),
                    n_mons=3).start()
    yield c
    c.shutdown()


def test_quorum_elects_and_replicates(cluster):
    ldr = cluster.wait_for_quorum()
    assert ldr.quorum.is_leader()
    # lowest reachable rank wins the steady-state election
    assert ldr is cluster.mons[0]
    cluster.create_replicated_pool(1, pg_num=8, size=3)
    cli = cluster.client()
    cli.put(1, "obj-a", b"alpha")
    assert cli.get(1, "obj-a") == b"alpha"
    # every member holds the committed history
    lead_lc = ldr.last_committed()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if all(m.last_committed() >= lead_lc
               for m in cluster.mons.values()):
            break
        time.sleep(0.1)
    assert all(m.last_committed() >= lead_lc
               for m in cluster.mons.values())
    assert_no_fork(cluster)


def test_leader_failover_mid_workload(cluster):
    cluster.wait_for_quorum()
    cluster.create_replicated_pool(1, pg_num=8, size=3)
    cli = cluster.client()
    for i in range(5):
        cli.put(1, f"pre-{i}", f"v{i}".encode())

    cluster.kill_mon(0)  # the leader dies mid-workload

    # a new leader (rank 1, the lowest survivor) takes over and WRITES
    # continue: both data-path puts and map-mutating commands
    deadline = time.monotonic() + 15
    new_leader = None
    while time.monotonic() < deadline and new_leader is None:
        for m in cluster.mons.values():
            if m.quorum.is_leader():
                new_leader = m
        time.sleep(0.1)
    assert new_leader is cluster.mons[1]

    cluster.create_replicated_pool(2, pg_num=8, size=2)
    cli.refresh_map()
    for i in range(5):
        cli.put(2, f"post-{i}", f"w{i}".encode())
    for i in range(5):
        assert cli.get(1, f"pre-{i}") == f"v{i}".encode()
        assert cli.get(2, f"post-{i}") == f"w{i}".encode()
    assert_no_fork(cluster)


def test_restarted_mon_rejoins_and_catches_up(cluster):
    cluster.wait_for_quorum()
    cluster.create_replicated_pool(1, pg_num=8, size=3)
    cluster.kill_mon(2)
    cli = cluster.client()
    cli.put(1, "while-down", b"data")
    cluster.create_replicated_pool(3, pg_num=4, size=2)
    lead_lc = cluster.leader().last_committed()

    m2 = cluster.revive_mon(2)
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        if m2.last_committed() >= lead_lc:
            break
        time.sleep(0.1)
    assert m2.last_committed() >= lead_lc
    assert_no_fork(cluster)
    # the rejoined member serves committed reads
    got = m2.msgr.call(m2.addr, {"type": "get_map"}, timeout=5)
    assert got["epoch"] >= lead_lc


def test_minority_partition_commits_nothing(cluster):
    """Kill two of three: the survivor must refuse writes (no quorum)
    rather than fork its own history."""
    cluster.wait_for_quorum()
    base = max(m.last_committed() for m in cluster.mons.values())
    cluster.kill_mon(1)
    cluster.kill_mon(2)
    m0 = cluster.mons[0]
    # wait out the lease so the survivor knows it lost the quorum
    time.sleep(2.0)
    with pytest.raises(Exception):
        rep = m0.msgr.call(m0.addr, {"type": "pool_create",
                                     "pool_id": 9,
                                     "pool": {"pool_type": 1,
                                              "size": 2,
                                              "min_size": 1,
                                              "pg_num": 4,
                                              "crush_rule": 0}},
                           timeout=8)
        if isinstance(rep, dict) and "error" in rep:
            raise RuntimeError(rep["error"])
    assert m0.last_committed() <= base + 1


def test_staged_entry_survives_leader_crash_and_peon_restart(tmp_path):
    """Paxos durability (Paxos.cc:330-560 persistent accepted_pn +
    uncommitted value via MonitorDBStore): stage an entry on one peon
    as if the leader crashed mid-replicate, kill the leader AND restart
    the staged peon, and require the next election to recover and
    commit that exact entry — never a different one at that version."""
    import json

    c = MiniCluster(n_osds=2, hosts=2, config=fast_conf(), n_mons=3,
                    data_dir=str(tmp_path)).start()
    try:
        ldr = c.wait_for_quorum()
        assert ldr is c.mons[0]
        lc = ldr.last_committed()
        m2 = c.mons[2]
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and m2.last_committed() < lc:
            time.sleep(0.05)
        assert m2.last_committed() == lc

        # hand-deliver an accept to mon.2 only — the moment after a
        # real leader got its first (and only) accept ack and died
        p = ldr.get_epoch_payload(lc)
        p["epoch"] = lc + 1
        p["map"]["epoch"] = lc + 1
        entry = {"payload": json.dumps(p), "inc": None}
        e = m2.quorum.election_epoch
        rep = m2.msgr.call(m2.addr, {"type": "mon_accept", "e": e,
                                     "v": lc + 1, "entry": entry},
                           timeout=5)
        assert rep.get("ack")

        c.kill_mon(0)        # leader dies without ever committing
        c.kill_mon(2)        # the one staged holder crashes too...
        c.revive_mon(2)      # ...and restarts from its store
        new = c.wait_for_quorum()
        assert new is c.mons[1]  # the new leader never saw the entry
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and \
                new.last_committed() < lc + 1:
            time.sleep(0.1)
        # the restarted peon's persisted stage rode its propose ack
        # into the new leader's collect majority and was re-proposed
        assert new.last_committed() >= lc + 1
        assert json.loads(new._epochs[lc + 1]) == p
        assert_no_fork(c)
    finally:
        c.shutdown()


def test_quorum_with_auth_keyring(tmp_path):
    """Signed clusters: election, replication, forwarding, and the
    data path all ride HMAC-authenticated frames (mon↔mon quorum
    traffic included)."""
    c = MiniCluster(n_osds=3, hosts=3, config=fast_conf(), n_mons=3,
                    auth=True, data_dir=str(tmp_path)).start()
    try:
        ldr = c.wait_for_quorum()
        assert ldr.quorum.is_leader()
        c.create_replicated_pool(1, pg_num=8, size=2)
        cli = c.client()
        cli.put(1, "signed", b"authenticated-bytes")
        assert cli.get(1, "signed") == b"authenticated-bytes"

        # failover still works with signed election traffic: kill the
        # OBSERVED leader (not a hardcoded rank)
        leader_rank = next(r for r, m in c.mons.items()
                           if m is ldr)
        c.kill_mon(leader_rank)
        new_leader = c.wait_for_quorum()
        assert new_leader is not ldr
        cli.put(1, "signed2", b"post-failover")
        assert cli.get(1, "signed2") == b"post-failover"
        assert_no_fork(c)

        # an unkeyed intruder's frames are dropped silently
        from ceph_tpu.msg.messenger import Messenger

        intruder = Messenger("intruder")
        intruder.start()
        try:
            with pytest.raises(TimeoutError):
                intruder.call(new_leader.addr,
                              {"type": "mark_down", "osd": 1},
                              timeout=2)
            assert 1 in c.status()["up_osds"]
        finally:
            intruder.shutdown()
    finally:
        c.shutdown()


def test_asymmetric_isolation_reelects_without_deposing():
    """One-way isolation (satellite of PR 15): rank 2 can SEND but
    cannot HEAR — its proposes reach the quorum while the leader's
    leases never reach it.  The standing majority must keep serving
    (re-electing through rank 2's blind candidacies), and once the cut
    heals the rejoining rank must settle as a peon WITHOUT deposing
    the leader again: its failed round's nacks carry the standing
    election epoch, so it drops to probing and joins peacefully."""
    from ceph_tpu.analysis import faults

    conf = fast_conf()
    c = MiniCluster(n_osds=2, hosts=2, config=conf, n_mons=3).start()
    try:
        c.create_replicated_pool(1, pg_num=4, size=2)
        ldr = c.wait_for_quorum()
        assert ldr is c.mons[0]
        cli = c.client()
        # inbound-only cut INTO rank 2 (replies carry no sender name,
        # so rank 2's own calls still round-trip — true asymmetry)
        c.set_faults("net.partition=p:1.0,"
                     "pairs:mon.0>mon.2|mon.1>mon.2")
        deadline = time.monotonic() + 3.0
        i = 0
        while time.monotonic() < deadline:
            # the majority serves commands throughout the cut, across
            # whatever re-elections rank 2's blind proposes force
            cli.put(1, f"cut-{i}", b"served")
            i += 1
            time.sleep(0.2)
        assert i >= 5
        c.set_faults("")
        faults.reset()
        # settle: rank 2 back as a peon under the rank-0 leader
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            q = c.mons[2].quorum
            if q.state == "peon" and q.leader_rank == 0 and \
                    c.mons[0].quorum.is_leader():
                break
            time.sleep(0.1)
        assert c.mons[2].quorum.state == "peon"
        assert c.mons[2].quorum.leader_rank == 0
        # the rejoined rank must NOT depose: the election epoch holds
        # still across several lease+retry windows
        epoch0 = c.mons[0].quorum.election_epoch
        time.sleep(2.0)
        assert c.mons[0].quorum.is_leader()
        assert c.mons[0].quorum.election_epoch == epoch0
        cli.put(1, "healed", b"stable")
        assert cli.get(1, "healed") == b"stable"
        assert_no_fork(c)
    finally:
        c.shutdown()

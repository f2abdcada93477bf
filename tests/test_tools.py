"""Tools tests — crushtool/osdmaptool/ec_benchmark end-to-end.

Mirrors the reference's CLI QA (src/test/cli/crushtool,
src/test/cli/osdmaptool): compile ⇄ decompile round-trips, --test
stats, --build, --compare, map-pgs and the upmap flow — all through
the CLI mains, on the scalar path (tiny inputs, no compile cost).
"""

import json

import numpy as np
import pytest

from ceph_tpu.crush.wrapper import CrushWrapper
from ceph_tpu.tools import crushtool, ec_benchmark, osdmaptool
from ceph_tpu.tools.compiler import (CompileError, compile_crushmap,
                                     decompile_crushmap)
from ceph_tpu.tools.tester import CrushTester

SAMPLE = """\
# begin crush map
tunable choose_local_tries 0
tunable choose_local_fallback_tries 0
tunable choose_total_tries 50
tunable chooseleaf_descend_once 1
tunable chooseleaf_vary_r 1
tunable chooseleaf_stable 1

# devices
device 0 osd.0 class ssd
device 1 osd.1 class ssd
device 2 osd.2 class hdd
device 3 osd.3 class hdd

# types
type 0 osd
type 1 host
type 2 root

# buckets
host host0 {
\tid -1
\talg straw2
\thash 0
\titem osd.0 weight 1.000
\titem osd.2 weight 1.000
}
host host1 {
\tid -2
\talg straw2
\thash 0
\titem osd.1 weight 2.000
\titem osd.3 weight 1.000
}
root default {
\tid -3
\talg straw2
\thash 0
\titem host0 weight 2.000
\titem host1 weight 3.000
}

# rules
rule replicated_rule {
\tid 0
\ttype replicated
\tstep take default
\tstep chooseleaf firstn 0 type host
\tstep emit
}
rule ssd_rule {
\tid 1
\ttype replicated
\tstep take default class ssd
\tstep chooseleaf firstn 0 type host
\tstep emit
}
# end crush map
"""


def test_compile_basics():
    w = compile_crushmap(SAMPLE)
    assert w.crush.tunables.choose_total_tries == 50
    assert w.get_item_id("default") == -3
    assert w.get_item_class(0) == "ssd"
    assert w.get_item_weight(1) == 0x20000
    assert 0 in w.crush.rules and 1 in w.crush.rules
    # class rule resolved to the shadow root
    take = w.crush.rules[1].steps[0]
    root = w.get_item_id("default")
    cid = w.get_or_create_class_id("ssd")
    assert take.arg1 == w.class_bucket[(root, cid)]


def test_compiled_map_places_correctly():
    w = compile_crushmap(SAMPLE)
    weight = [0x10000] * 4
    for x in range(32):
        res = w.do_rule(0, x, 2, weight)
        assert len(res) == 2
        assert {o // 1 for o in res}  # non-empty
        # ssd rule only places on ssd devices (0, 1)
        res = w.do_rule(1, x, 2, weight)
        assert all(o in (0, 1) for o in res)


def test_decompile_roundtrip():
    w1 = compile_crushmap(SAMPLE)
    text = decompile_crushmap(w1)
    w2 = compile_crushmap(text)
    # identical placement behavior after a full round-trip
    weight = [0x10000] * 4
    for rno in (0, 1):
        for x in range(64):
            assert w1.do_rule(rno, x, 2, weight) == \
                w2.do_rule(rno, x, 2, weight)
    # and a second decompile is textually stable
    assert decompile_crushmap(w2) == text


def test_compile_errors():
    with pytest.raises(CompileError):
        compile_crushmap("nonsense line\n")
    with pytest.raises(CompileError):
        compile_crushmap("tunable bogus_knob 1\n")
    with pytest.raises(CompileError):
        compile_crushmap("type 0 osd\nhost h {\n\titem osd.9 weight "
                         "1.0\n}\n")


def test_tester_stats_scalar():
    w = compile_crushmap(SAMPLE)
    t = CrushTester(w)
    rep = t.test_rule(0, 2, 0, 255, scalar=True)
    assert rep.total == 256
    assert rep.size_counts.get(2, 0) == 256
    assert int(rep.device_stored.sum()) == 512
    assert abs(float(rep.device_expected.sum()) - 512) < 1e-6
    # expected derives from the TESTER's weight vector (default all
    # equal — CrushTester.cc:521-545), not the crush weights
    assert rep.device_expected[1] == rep.device_expected[0]
    # --weight halves a device: its expected share drops
    t.set_device_weight(3, 0.5)
    rep2 = t.test_rule(0, 2, 0, 255, scalar=True)
    assert rep2.device_expected[3] < rep2.device_expected[0]
    # and stored placements on it drop too (weight-based rejection)
    assert int(rep2.device_stored[3]) < int(rep.device_stored[3])


@pytest.mark.parametrize("engine", ["scalar", "batched", "native"])
def test_tester_stats_match_loop_reference(engine):
    """The array-derived statistics equal a per-mapping loop over the
    same results; num_rep 3 on two hosts leaves every mapping short."""
    t = CrushTester(compile_crushmap(SAMPLE))
    t.set_device_weight(2, 0.5)
    rep = t.test_rule(0, 3, 0, 511, scalar=engine == "scalar",
                      native=engine == "native", collect_mappings=True)
    n_dev = len(rep.device_stored)
    stored = np.zeros(n_dev, np.int64)
    sizes, bad = {}, []
    for x, r in enumerate(rep.mappings):
        sizes[len(r)] = sizes.get(len(r), 0) + 1
        for o in r:
            if 0 <= o < n_dev:
                stored[o] += 1
        if len(r) != 3:
            bad.append((x, r))
    assert rep.size_counts == sizes
    assert np.array_equal(rep.device_stored, stored)
    assert rep.bad == bad and len(bad) == 512
    assert all(type(o) is int for r in rep.mappings for o in r)


def test_tester_compare_detects_difference():
    w1 = compile_crushmap(SAMPLE)
    w2 = compile_crushmap(SAMPLE)
    t1, t2 = CrushTester(w1), CrushTester(w2)
    diff, total = t1.compare(t2, 0, 2, 0, 127, scalar=True)
    assert diff == 0
    w2.adjust_item_weight(3, 0x80000)
    diff, total = t1.compare(t2, 0, 2, 0, 127, scalar=True)
    assert diff > 0


def test_crushtool_cli_flow(tmp_path):
    src = tmp_path / "map.txt"
    src.write_text(SAMPLE)
    out = tmp_path / "map.json"
    assert crushtool.main(["-c", str(src), "-o", str(out)]) == 0
    d = json.loads(out.read_text())
    assert "map" in d and "name_map" in d
    # decompile back
    txt = tmp_path / "back.txt"
    assert crushtool.main(["-d", str(out), "-o", str(txt)]) == 0
    assert "root default" in txt.read_text()
    # --test on the scalar path
    assert crushtool.main(["-i", str(out), "--test", "--num-rep", "2",
                           "--max-x", "63", "--scalar",
                           "--show-statistics"]) == 0
    # --tree
    assert crushtool.main(["-i", str(out), "--tree"]) == 0


def test_crushtool_build(tmp_path):
    out = tmp_path / "built.json"
    assert crushtool.main(
        ["--build", "--num-osds", "8", "-o", str(out),
         "host", "straw2", "2", "root", "straw2", "0"]) == 0
    w = crushtool.load_map(str(out))
    root = w.get_item_id("root")
    assert len(w.get_leaves(root)) == 8
    # a built map has no rules: --test says so (crushtool.cc behavior)
    assert crushtool.main(["-i", str(out), "--test", "--scalar"]) == 1
    # add a rule, then test works
    assert crushtool.main(
        ["-i", str(out), "--create-replicated-rule",
         "replicated_rule", "root", "host"]) == 0
    w = crushtool.load_map(str(out))
    assert w.get_rule_id("replicated_rule") == 0
    assert crushtool.main(["-i", str(out), "--test", "--num-rep", "2",
                           "--max-x", "31", "--scalar"]) == 0


def test_osdmaptool_flow(tmp_path):
    mapfn = tmp_path / "osdmap.json"
    assert osdmaptool.main([str(mapfn), "--createsimple", "8",
                            "--pg-bits", "3"]) == 0
    m_d = json.loads(mapfn.read_text())
    assert m_d["max_osd"] == 8
    # test-map-pgs on the scalar path
    assert osdmaptool.main([str(mapfn), "--test-map-pgs",
                            "--scalar"]) == 0
    # upmap flow writes commands
    cmds = tmp_path / "upmap.sh"
    assert osdmaptool.main([str(mapfn), "--upmap", str(cmds),
                            "--upmap-deviation", "1",
                            "--upmap-max", "16", "--scalar"]) == 0
    text = cmds.read_text()
    if text:  # balancer found improvements
        assert "pg-upmap-items" in text


def test_osdmaptool_dump(tmp_path, capsys):
    mapfn = tmp_path / "om.json"
    assert osdmaptool.main([str(mapfn), "--createsimple", "4",
                            "--pg-bits", "2"]) == 0
    capsys.readouterr()
    assert osdmaptool.main([str(mapfn), "--test-map-pgs-dump",
                            "--scalar"]) == 0
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith("1.")]
    assert len(lines) == 16  # 4 osds << 2 pg bits
    pgid, up, up_p, acting, act_p = lines[0].split("\t")
    assert pgid == "1.0" and int(up_p) >= 0


def test_ec_benchmark_cli(capsys):
    assert ec_benchmark.main(
        ["--plugin", "jerasure", "-P", "k=4", "-P", "m=2",
         "--workload", "encode", "--size", "8192",
         "--iterations", "2"]) == 0
    out = capsys.readouterr().out.strip().split("\t")
    assert float(out[0]) > 0 and int(out[1]) == 16
    assert ec_benchmark.main(
        ["--plugin", "lrc", "-P", "k=4", "-P", "m=2", "-P", "l=3",
         "--workload", "decode", "--size", "4096", "--erasures", "1",
         "--erasures-generation", "exhaustive", "--verify"]) == 0


def test_rados_cli_and_objectstore_tool(tmp_path):
    """The rados CLI round-trips through a live cluster by mon
    address, and objectstore-tool inspects/exports/imports the downed
    OSD's store offline."""
    import json
    import os

    from ceph_tpu.common.config import Config
    from ceph_tpu.services.cluster import MiniCluster
    from ceph_tpu.tools import objectstore_tool, rados

    conf = Config()
    conf.set("osd_heartbeat_interval", 0.3)
    conf.set("osd_heartbeat_grace", 2.0)
    data_dir = str(tmp_path / "cluster")
    c = MiniCluster(n_osds=3, config=conf, data_dir=data_dir).start()
    try:
        c.create_replicated_pool(1, pg_num=8, size=2)
        mon = f"{c.mon.addr[0]}:{c.mon.addr[1]}"
        src = tmp_path / "in.bin"
        src.write_bytes(b"rados-cli-payload" * 100)
        out = tmp_path / "out.bin"
        assert rados.main(["--mon", mon, "-p", "1", "put", "obj-a",
                           str(src)]) == 0
        assert rados.main(["--mon", mon, "-p", "1", "get", "obj-a",
                           str(out)]) == 0
        assert out.read_bytes() == src.read_bytes()

        import contextlib
        import io

        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rados.main(["--mon", mon, "-p", "1", "ls"])
        assert "obj-a" in buf.getvalue().splitlines()

        assert rados.main(["--mon", mon, "-p", "1", "rm",
                           "obj-a"]) == 0
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rados.main(["--mon", mon, "-p", "1", "ls"])
        assert "obj-a" not in buf.getvalue().splitlines()

        # seed an object, then take osd.0 down for offline surgery
        rados.main(["--mon", mon, "-p", "1", "put", "obj-b",
                    str(src)])
        c.kill_osd(0)
    finally:
        c.shutdown()

    store_path = os.path.join(data_dir, "osd0", "osd.0.wal")
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        objectstore_tool.main(["--data-path", store_path,
                               "--op", "list"])
    listing = json.loads(buf.getvalue())
    pgs = [cid for cid, objs in listing.items()
           if any(o.startswith("obj-b") for o in objs)]
    if pgs:  # osd.0 held a shard: export -> import round-trip
        pgid = pgs[0]
        exp = tmp_path / "pg.export"
        objectstore_tool.main(["--data-path", store_path,
                               "--op", "export", "--pgid", pgid,
                               "--file", str(exp)])
        fresh = tmp_path / "fresh.wal"
        from ceph_tpu.os.wal_store import WALStore

        w = WALStore(str(fresh))
        w.mkfs()
        w.umount()
        objectstore_tool.main(["--data-path", str(fresh),
                               "--op", "import", "--file", str(exp)])
        w2 = WALStore(str(fresh))
        w2.mount()
        assert set(w2.list_objects(pgid)) == set(listing[pgid])
        w2.umount()


def test_ceph_cli(capsys):
    """The `ceph` admin CLI: status/health/osd tree/pool verbs against
    a live cluster by monitor address."""
    from ceph_tpu.common.config import Config
    from ceph_tpu.services.cluster import MiniCluster
    from ceph_tpu.tools import ceph_cli

    conf = Config()
    conf.set("osd_heartbeat_interval", 0.3)
    conf.set("osd_heartbeat_grace", 3.0)
    c = MiniCluster(n_osds=3, config=conf).start()
    try:
        c.create_replicated_pool(1, pg_num=4, size=2)
        mon = f"{c.mon.addr[0]}:{c.mon.addr[1]}"
        assert ceph_cli.main(["--mon", mon, "status"]) == 0
        out = capsys.readouterr().out
        assert "osds:    3 up" in out and "pools:   1" in out

        assert ceph_cli.main(["--mon", mon, "osd", "tree"]) == 0
        out = capsys.readouterr().out
        # the wire map carries structure, not the builder's name maps
        assert "root" in out and "host" in out
        assert any(ln.strip().startswith("0\t")
                   for ln in out.splitlines())

        assert ceph_cli.main(["--mon", mon, "pool", "create", "5",
                              "4", "2"]) == 0
        capsys.readouterr()
        assert ceph_cli.main(["--mon", mon, "pool", "ls"]) == 0
        out = capsys.readouterr().out
        assert "pool 5:" in out
        assert ceph_cli.main(["--mon", mon, "pool", "delete",
                              "5"]) == 0
        capsys.readouterr()
        assert ceph_cli.main(["--mon", mon, "osd", "reweight", "1",
                              "0.5"]) == 0
        capsys.readouterr()
        payload = c.mon_command({"type": "get_map"})
        from ceph_tpu.osdmap.bincode_maps import payload_map
        assert payload_map(payload).osd_weight[1] == 0x8000

        # health returns nonzero on WARN
        c.kill_osd(2)
        c.wait_for_down(2, timeout=10)
        rc = ceph_cli.main(["--mon", mon, "health"])
        out = capsys.readouterr().out
        assert rc == 1 and "HEALTH_WARN" in out
    finally:
        c.shutdown()

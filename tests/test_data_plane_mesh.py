"""Mesh-sharded data plane: EC stripe-batch sharding, the plugin and
batcher mesh paths, the meshed OSDMap pipeline + CrushTester sweep,
per-device work accounting, and the bench/perf_history multichip lane.

The CRUSH half (PlacementPlane) lives in test_placement.py; this file
covers everything the data-plane mesh touches downstream of it.  All
tests run on the conftest's 8-virtual-CPU-device layout, with the
1-device degenerate cases exercised explicitly.
"""

import json
import os

import numpy as np
import pytest

import conftest  # noqa: F401

import jax

from ceph_tpu.common import device_metrics
from ceph_tpu.ec.registry import factory
from ceph_tpu.ec.rs_jax import RSCode
from ceph_tpu.parallel.placement import (data_plane, data_plane_mesh,
                                         make_mesh,
                                         set_data_plane_mesh)

N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    devs = jax.devices()
    if len(devs) < N_DEV:
        pytest.skip(f"need {N_DEV} virtual devices, have {len(devs)}")
    return make_mesh(devs[:N_DEV], axis_name="ec")


def _bitplane(profile):
    """Plugin under the bitplane engine: the sharded path needs the
    JITted BitCode (the native GF engine is host-only)."""
    old = os.environ.get("CEPH_TPU_EC_ENGINE")
    os.environ["CEPH_TPU_EC_ENGINE"] = "bitplane"
    try:
        plugin, prof = profile
        return factory(plugin, dict(prof))
    finally:
        if old is None:
            os.environ.pop("CEPH_TPU_EC_ENGINE", None)
        else:
            os.environ["CEPH_TPU_EC_ENGINE"] = old


# the EC corpus grid (mirrors tests/test_ec_batch.py PROFILES): every
# technique/w/packetsize family, plus the layered/sub-chunked plugins
# that must take the (still byte-identical) fallback path
PROFILES = [
    ("jerasure", {"technique": "reed_sol_van", "k": "4", "m": "2",
                  "w": "8"}),
    ("jerasure", {"technique": "reed_sol_van", "k": "3", "m": "2",
                  "w": "16"}),
    ("jerasure", {"technique": "reed_sol_van", "k": "3", "m": "2",
                  "w": "32"}),
    ("jerasure", {"technique": "cauchy_good", "k": "4", "m": "2",
                  "w": "8", "packetsize": "8"}),
    ("jerasure", {"technique": "liberation", "k": "3", "m": "2",
                  "w": "7", "packetsize": "8"}),
    ("isa", {"k": "4", "m": "2"}),
    ("lrc", {"k": "4", "m": "2", "l": "3"}),
    ("shec", {"k": "4", "m": "3", "c": "2"}),
    ("clay", {"k": "4", "m": "2"}),
]


def _objects(n, size, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, size, dtype=np.uint8).tobytes()
            for _ in range(n)]


# -- engine level -----------------------------------------------------------

def test_engine_sharded_byte_identical_all_layouts(mesh):
    """encode_batched_sharded == per-stripe encode for every layout
    family (w8 bytes, w16/w32 words, packet), divisible and
    non-divisible batch sizes, 1-device and 8-device meshes."""
    mesh1 = make_mesh(jax.devices()[:1], axis_name="ec")
    rng = np.random.default_rng(11)
    cases = [
        RSCode(4, 2)._bit,                               # w8
        _bitplane(PROFILES[1])._code,                    # w16
        _bitplane(PROFILES[2])._code,                    # w32
        _bitplane(PROFILES[3])._code,                    # packet
    ]
    for bc in cases:
        blk = bc.layout.w * bc.layout.packetsize \
            if bc.layout.is_packet else max(1, bc.layout.w // 8)
        L = 64 * blk
        for B in (8, 5, 1):
            stripes = rng.integers(0, 256, (B, bc.k, L),
                                   dtype=np.uint8)
            for m in (mesh, mesh1):
                got = np.asarray(
                    bc.encode_batched_sharded(stripes, m))
                assert got.shape == (B, bc.m, L)
                for b in range(B):
                    ref = np.asarray(bc.encode(stripes[b]))
                    assert got[b].tobytes() == ref.tobytes(), \
                        (bc.layout.w, bc.layout.packetsize, B, b)


def test_engine_default_mesh_routing(mesh):
    """encode_batched with no explicit mesh takes the process-default
    data-plane mesh — and stays unsharded when none is installed or
    when the installed mesh is single-device."""
    bc = RSCode(4, 2)._bit
    rng = np.random.default_rng(12)
    stripes = rng.integers(0, 256, (8, 4, 1024), dtype=np.uint8)
    ref = np.asarray(bc.encode_batched(stripes))
    assert data_plane_mesh() is None
    with data_plane(mesh):
        assert data_plane_mesh() is mesh
        got = np.asarray(bc.encode_batched(stripes))
    assert data_plane_mesh() is None
    assert got.tobytes() == ref.tobytes()


def test_engine_sharded_recompile_budget(mesh):
    """Warmed sharded batch shapes must hit the jit cache: pad-and-
    mask batches that land on a warmed pow2 shape book zero new XLA
    compiles inside the steady-state window, on both mesh sizes."""
    from ceph_tpu.analysis import jaxcheck

    bc = RSCode(4, 2)._bit
    mesh1 = make_mesh(jax.devices()[:1], axis_name="ec")
    rng = np.random.default_rng(13)
    for m in (mesh, mesh1):       # warmup: one compile per mesh size
        s = rng.integers(0, 256, (8, 4, 1024), dtype=np.uint8)
        np.asarray(bc.encode_batched_sharded(s, m))
    base = len(jaxcheck.recompile_violations())
    with jaxcheck.steady_state("ec.encode_batched_sharded.mesh_sizes"):
        for m in (mesh, mesh1):
            for B in (8, 5, 7):   # all pad to the warmed 8
                s = rng.integers(0, 256, (B, 4, 1024), dtype=np.uint8)
                out = np.asarray(bc.encode_batched_sharded(s, m))
                assert out.shape == (B, 2, 1024)
    assert len(jaxcheck.recompile_violations()) == base


# -- plugin + batcher level -------------------------------------------------

@pytest.mark.parametrize("profile", PROFILES,
                         ids=lambda p: p[0] + "-" + "-".join(
                             f"{k}{v}" for k, v in sorted(p[1].items())))
def test_plugin_encode_batched_mesh_byte_identical(mesh, profile):
    """Plugin-level encode_batched under the mesh == per-object
    encode, over the corpus grid.  BitCode-backed plugins (jerasure,
    isa) take the sharded stripe-batch path; layered/sub-chunked ones
    (lrc, shec, clay) keep the fallback — both must stay
    byte-identical."""
    code = _bitplane(profile)
    n = code.get_chunk_count()
    want = set(range(n))
    for B, size in ((3, 4096), (5, 8192)):
        raws = _objects(B, size, seed=B)
        batched = code.encode_batched(want, raws, mesh=mesh)
        assert len(batched) == B
        for raw, got in zip(raws, batched):
            ref = code.encode(want, raw)
            assert set(got) == set(ref)
            for i in ref:
                assert np.asarray(got[i], np.uint8).tobytes() == \
                    np.asarray(ref[i], np.uint8).tobytes(), \
                    (profile[0], i)


def test_plugin_mesh_path_actually_shards(mesh):
    """The jerasure/bitplane mesh path must really run the sharded
    kernel: the per-device mesh table grows on every mesh device."""
    device_metrics.reset_for_tests()
    code = _bitplane(PROFILES[0])
    assert hasattr(code._code, "encode_batched_sharded")
    raws = _objects(4, 4096, seed=21)
    code.encode_batched(set(range(code.get_chunk_count())), raws,
                        mesh=mesh)
    table = device_metrics.mesh_device_table()
    ids = {int(d.id) for d in np.asarray(mesh.devices).ravel()}
    assert ids <= set(table), (sorted(table), sorted(ids))
    assert all(table[i]["launches"] >= 1 for i in ids)


def test_encode_batcher_mesh_coalesced_identical(mesh):
    """Concurrent encodes through an EncodeBatcher carrying the mesh:
    outputs identical to the direct path and at least one multi-object
    batch dispatched."""
    import threading

    from ceph_tpu.ec.batcher import EncodeBatcher
    from ceph_tpu.ec.engine import _pc

    code = _bitplane(PROFILES[0])
    want = set(range(code.get_chunk_count()))
    batcher = EncodeBatcher(max_delay_us=5000, mesh=mesh)
    raws = _objects(8, 4096, seed=3)
    refs = [code.encode(want, r) for r in raws]
    base = _pc.dump()["ec_batch_size"]["buckets"]
    outs = [None] * len(raws)
    errs = []

    def worker(i):
        try:
            outs[i] = batcher.encode(code, want, raws[i])
        except Exception as e:  # surfaced below
            errs.append(e)

    ths = [threading.Thread(target=worker, args=(i,))
           for i in range(len(raws))]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    assert not errs
    for got, ref in zip(outs, refs):
        for i in ref:
            assert np.asarray(got[i]).tobytes() == \
                np.asarray(ref[i]).tobytes()
    cur = _pc.dump()["ec_batch_size"]["buckets"]
    grew = [c - b for c, b in zip(cur, base)]
    assert sum(grew[1:]) > 0, "no multi-object batch ever dispatched"


# -- osdmap + tester sweeps -------------------------------------------------

def test_pool_mapper_mesh_equals_unsharded(mesh):
    """The meshed OSDMap pipeline (ps axis + exception tables sharded,
    pow2-padded non-divisible pg_num) == the unsharded PoolMapper,
    through upmap/pg_temp edits and refresh_tables."""
    from ceph_tpu.crush.builder import sample_cluster_map
    from ceph_tpu.osdmap.osdmap import (OSDMap, PgPool,
                                        POOL_TYPE_REPLICATED)
    from ceph_tpu.osdmap.pipeline_jax import PoolMapper

    cmap = sample_cluster_map(3, 4, 4)
    m = OSDMap(cmap)
    for o in range(48):
        m.add_osd(o)
    m.pools[1] = PgPool(pool_type=POOL_TYPE_REPLICATED, size=3,
                        pg_num=100, crush_rule=0)   # non-divisible
    m.pg_upmap[(1, 5)] = [1, 2, 3]
    m.pg_upmap_items[(1, 3)] = [(0, 47)]
    m.pg_temp[(1, 7)] = [9, 10, 11]
    m.primary_temp[(1, 8)] = 12
    pm_ref = PoolMapper(m, 1)
    pm_mesh = PoolMapper(m, 1, mesh=make_mesh())
    a, b = pm_ref.map_all(), pm_mesh.map_all()
    for k in a:
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k
    m.pg_upmap[(1, 6)] = [2, 3, 4]
    pm_ref.refresh_tables()
    pm_mesh.refresh_tables()
    a, b = pm_ref.map_all(), pm_mesh.map_all()
    for k in a:
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k


def test_pool_mapper_mesh_equals_straggler_pass(mesh):
    """At 4,096 PGs the unmeshed PoolMapper maps through the straggler
    pass (``mapper_spec.map_stragglers``) and books its re-runs; the
    meshed one keeps the plain vmapped loops and books none.  With
    OSDs out, so that PGs re-run, both give the same sets."""
    from ceph_tpu.common.perf_counters import collection
    from ceph_tpu.crush.builder import sample_cluster_map
    from ceph_tpu.osdmap.osdmap import (OSDMap, PgPool,
                                        POOL_TYPE_REPLICATED)
    from ceph_tpu.osdmap.pipeline_jax import PoolMapper

    m = OSDMap(sample_cluster_map(3, 4, 4))
    for o in range(48):
        m.add_osd(o)
    for o in (1, 6, 20, 33, 47):
        m.osd_weight[o] = 0
    m.pools[1] = PgPool(pool_type=POOL_TYPE_REPLICATED, size=3,
                        pg_num=4096, crush_rule=0)

    def reruns():
        c = collection().dump("crush.mapper")["crush.mapper"]
        return c["spec_rerun_pgs"], c["spec_rerun_chunks"]

    base = reruns()
    a = {k: np.asarray(v) for k, v in PoolMapper(m, 1).map_all().items()}
    ref = reruns()
    assert ref[0] > base[0] and ref[1] > base[1], (base, ref)
    b = PoolMapper(m, 1, mesh=make_mesh()).map_all()
    assert reruns() == ref
    for k in a:
        assert np.array_equal(a[k], np.asarray(b[k])), k


def test_crush_tester_mesh_sweep_matches_scalar(mesh):
    """CrushTester.test_rule over the mesh: same mappings, same
    utilization tally (the all-reduced on-device counts) as the
    scalar sweep."""
    from ceph_tpu.crush.builder import sample_cluster_map
    from ceph_tpu.crush.wrapper import CrushWrapper
    from ceph_tpu.tools.tester import CrushTester

    w = CrushWrapper(sample_cluster_map(2, 2, 4))
    t = CrushTester(w)
    rep_mesh = t.test_rule(0, 3, 0, 99, mesh=make_mesh())
    rep_scalar = t.test_rule(0, 3, 0, 99, scalar=True)
    assert rep_mesh.total == rep_scalar.total == 100
    assert rep_mesh.size_counts == rep_scalar.size_counts
    assert np.array_equal(rep_mesh.device_stored,
                          rep_scalar.device_stored)
    assert rep_mesh.bad == rep_scalar.bad


# -- bench lane + trajectory ------------------------------------------------

def test_bench_multichip_worker_smoke():
    """The multichip lane end-to-end in a subprocess: init + multichip
    stages land, with 1-dev vs N-dev rates, scaling-efficiency
    figures, a per-device breakdown row per mesh device, and passing
    SLO blocks (floors sized for one CPU core time-slicing the
    virtual mesh)."""
    import pathlib
    import subprocess
    import sys

    repo = pathlib.Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "CEPH_TPU_MULTICHIP_MAP": "map_flat12",
        "CEPH_TPU_MULTICHIP_BATCH": "2048",
        "CEPH_TPU_MULTICHIP_ITERS": "2",
        "CEPH_TPU_MULTICHIP_EC_BATCH": "8",
        "CEPH_TPU_MULTICHIP_EC_CHUNK": "16384",
    })
    out = subprocess.run(
        [sys.executable, str(repo / "bench.py"), "--worker",
         "multichip"],
        env=env, cwd=str(repo), capture_output=True, text=True,
        timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    results = [json.loads(line[len("BENCH_RESULT "):])
               for line in out.stdout.splitlines()
               if line.startswith("BENCH_RESULT ")]
    stages = {r["stage"]: r for r in results}
    assert "init" in stages and stages["init"]["n_devices"] >= 2
    mc = stages["multichip"]
    n = mc["n_devices"]
    assert mc["crush_1dev_mappings_per_sec"] > 0
    assert mc["crush_ndev_mappings_per_sec"] > 0
    want_eff = mc["crush_ndev_mappings_per_sec"] / (
        n * mc["crush_1dev_mappings_per_sec"])
    assert mc["crush_scaling_efficiency"] == pytest.approx(
        want_eff, rel=0.01)
    assert mc["ec_ndev_gbps"] > 0 and mc["ec_1dev_gbps"] > 0
    assert len(mc["per_device"]) == n
    assert all(d.get("kernel_launches", 0) > 0
               for d in mc["per_device"])
    slos = {b["metric"]: b for b in mc["slo"]}
    assert slos["multichip_crush_mappings_per_sec"]["pass"] is True
    assert slos["multichip_encode_gbps"]["pass"] is True


def test_perf_history_ingests_multichip(tmp_path):
    """perf_history merges the bench lane's multichip stage JSON and
    the MULTICHIP_rNN dryrun records into the trajectory, and
    red-checks a >25% scaling-efficiency drop between runs."""
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve()
                           .parent.parent))
    from tools import perf_history

    def mc_tail(ndev_rate, eff, ec_eff):
        return "# multichip json: " + json.dumps({
            "stage": "multichip", "n_devices": 8,
            "crush_ndev_mappings_per_sec": ndev_rate,
            "crush_scaling_efficiency": eff,
            "ec_scaling_efficiency": ec_eff})

    def write_bench(n, rate, tail):
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps({
            "n": n, "cmd": "bench", "rc": 0, "tail": tail,
            "parsed": {"metric": "crush_mappings_per_sec",
                       "value": rate, "platform": "cpu"}}))

    # a MULTICHIP dryrun record with no same-numbered bench run gets
    # its own trajectory row; its efficiency lands in the mc_dry_*
    # columns (smaller workload — never delta'd against bench-lane
    # values) and its small-map absolute rate is dropped
    (tmp_path / "MULTICHIP_r01.json").write_text(json.dumps({
        "n_devices": 8, "rc": 0, "ok": True, "skipped": False,
        "tail": "multichip scaling: " + json.dumps({
            "n_devices": 8, "crush_ndev_mappings_per_sec": 50000.0,
            "crush_scaling_efficiency": 0.8,
            "ec_scaling_efficiency": 0.9})}))
    write_bench(2, 100000.0, mc_tail(52000.0, 0.82, 0.88))
    write_bench(3, 101000.0, mc_tail(53000.0, 0.80, 0.91))
    rows = perf_history.load_all(str(tmp_path))
    assert [r["run"] for r in rows] == ["r01", "r02", "r03"]
    assert rows[0]["metrics"]["mc_dry_crush_eff"] == 0.8
    assert "mc_crush_ndev_s" not in rows[0]["metrics"]
    assert rows[1]["metrics"]["mc_crush_ndev_s"] == 52000.0
    perf_history.compute_deltas(rows)
    assert "mc_crush_eff" in rows[2]["deltas"]
    assert perf_history.main([str(tmp_path), "--check"]) == 0
    # a 50% efficiency collapse in the latest run is a red check
    write_bench(4, 102000.0, mc_tail(26000.0, 0.40, 0.89))
    assert perf_history.main([str(tmp_path), "--check"]) == 1
    rows = perf_history.load_all(str(tmp_path))
    perf_history.compute_deltas(rows)
    assert any("mc_crush_eff" in r for r in rows[-1]["regressions"])

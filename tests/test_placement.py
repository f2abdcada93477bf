"""Multi-device CI tests — the sharded path must equal the scalar spec.

Runs on the 8-virtual-CPU-device mesh the conftest provisions (the
driver's separate dryrun validates the same layout; here CI pins the
*values*): ``sharded_rule_fn`` over the mesh == unsharded
``BatchedMapper`` == the scalar ``mapper_ref`` specification, and the
all-reduced utilization tally == a numpy bincount.  This is the
TPU-native re-expression of the reference's multi-process QA
(qa/standalone/ — many OSDs, one host): many devices, one host.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ceph_tpu.analysis import jaxcheck
from ceph_tpu.crush.builder import sample_cluster_map
from ceph_tpu.crush.map import CrushMap
from ceph_tpu.crush.lowering import BatchedMapper
from ceph_tpu.crush import mapper_ref
from ceph_tpu.ec.rs_jax import RSCode
from ceph_tpu.parallel.placement import (PlacementPlane, make_mesh,
                                         pad_batch, sharded_rule_fn,
                                         utilization)

N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    devs = jax.devices()
    if len(devs) < N_DEV:
        pytest.skip(f"need {N_DEV} virtual devices, have {len(devs)}")
    return make_mesh(devs[:N_DEV])


@pytest.fixture(scope="module")
def cmap():
    return sample_cluster_map(racks=3, hosts_per_rack=2, osds_per_host=4)


def _scalar_results(cmap, ruleno, numrep, weight, xs):
    out = []
    for x in xs:
        r = mapper_ref.crush_do_rule(cmap, ruleno, int(x), numrep,
                                     list(weight))
        out.append(r)
    return out


def test_sharded_equals_unsharded_equals_scalar(mesh, cmap):
    numrep = 3
    weight = [0x10000] * cmap.max_devices
    xs_np = np.arange(N_DEV * 16, dtype=np.uint32)

    fn, static, arrays = sharded_rule_fn(cmap, 0, numrep, mesh)
    repl = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P("pg"))
    A = jax.tree_util.tree_map(
        lambda a: jax.device_put(jnp.asarray(a), repl), arrays)
    w_dev = jax.device_put(
        jnp.asarray(np.asarray(weight, np.uint32)), repl)
    xs = jax.device_put(jnp.asarray(xs_np), shard)

    res_sh, lens_sh, counts = fn(A, w_dev, xs)
    res_sh, lens_sh = np.asarray(res_sh), np.asarray(lens_sh)

    # unsharded BatchedMapper on the same inputs
    bm = BatchedMapper(cmap)
    res_un, lens_un = bm.map_batch(0, xs_np, numrep,
                                   np.asarray(weight, np.uint32))
    res_un, lens_un = np.asarray(res_un), np.asarray(lens_un)
    assert np.array_equal(res_sh, res_un)
    assert np.array_equal(lens_sh, lens_un)

    # scalar executable spec
    want = _scalar_results(cmap, 0, numrep, weight, xs_np)
    for i, w in enumerate(want):
        assert list(res_sh[i, :lens_sh[i]]) == w, f"x={i}"

    # utilization == numpy bincount over valid entries
    valid = []
    for i, w in enumerate(want):
        valid.extend(v for v in w if 0 <= v < static.max_devices)
    want_counts = np.bincount(np.asarray(valid, np.int64),
                              minlength=static.max_devices)
    assert np.array_equal(np.asarray(counts), want_counts)


def test_utilization_matches_bincount_random():
    rng = np.random.default_rng(7)
    max_dev = 24
    res = rng.integers(-1, max_dev, (64, 3)).astype(np.int32)
    lens = rng.integers(0, 4, 64).astype(np.int32)
    got = np.asarray(utilization(jnp.asarray(res), jnp.asarray(lens),
                                 max_dev))
    want = np.zeros(max_dev, np.int64)
    for i in range(64):
        for j in range(lens[i]):
            v = res[i, j]
            if 0 <= v < max_dev:
                want[v] += 1
    assert np.array_equal(got, want)


def test_sharded_ec_encode_equals_single_device(mesh):
    """The dryrun's stripe-byte-axis sharding, value-checked: encode of
    a stripe batch sharded over the mesh == single-device encode."""
    code = RSCode(4, 2)
    rng = np.random.default_rng(3)
    data_np = rng.integers(0, 256, (4, 128 * N_DEV), dtype=np.uint8)

    single = np.asarray(code.encode(jnp.asarray(data_np)))

    sh = NamedSharding(mesh, P(None, "pg"))
    data_sh = jax.device_put(jnp.asarray(data_np), sh)
    enc = jax.jit(code.encode, in_shardings=(sh,), out_shardings=sh)
    parity = np.asarray(enc(data_sh))
    assert np.array_equal(parity, single)


# -- PlacementPlane: the production mesh-sharded distribution layer --------

@pytest.mark.parametrize("ruleno,numrep", [(0, 3), (0, 5), (1, 3),
                                           (1, 6)])
def test_placement_plane_bit_exact_grid(mesh, cmap, ruleno, numrep):
    """Sharded results/lens/utilization identical to the unsharded
    ``BatchedMapper`` output across the rule 0/1 (firstn/indep) x R
    grid — including a batch NOT divisible by the mesh (pad lanes
    masked out of the tally)."""
    weight = np.full(cmap.max_devices, 0x10000, np.uint32)
    weight[3] = 0x8000
    plane = PlacementPlane(cmap, mesh=mesh)
    bm = BatchedMapper(cmap)
    for n in (N_DEV * 8, 100):    # divisible and pad-and-mask
        xs = np.arange(n, dtype=np.uint32)
        res, lens, counts = plane.map_batch(ruleno, xs, numrep,
                                            weight,
                                            gather_stats=True)
        res_un, lens_un = bm.map_batch(ruleno, xs, numrep, weight)
        res_un = np.asarray(res_un)
        lens_un = np.asarray(lens_un)
        assert np.array_equal(np.asarray(res), res_un), (ruleno, n)
        assert np.array_equal(np.asarray(lens), lens_un), (ruleno, n)
        want = np.zeros(cmap.max_devices, np.int64)
        for i in range(n):
            for v in res_un[i, :lens_un[i]]:
                if 0 <= v < cmap.max_devices:
                    want[v] += 1
        assert np.array_equal(np.asarray(counts), want), (ruleno, n)


def test_placement_plane_choose_args_bit_exact(mesh):
    """The choose_args grid point: the golden chooseargs map through
    the plane == the unsharded mapper with the same choose_args."""
    import json
    import pathlib

    d = json.load(open(pathlib.Path(__file__).parent /
                       "golden/map_tree3_chooseargs.json"))
    cmap = CrushMap.from_dict(d["map"])
    cargs = cmap.choose_args.get("golden")
    assert cargs is not None, "golden chooseargs map lost its args"
    case = d["cases"][0]
    n = min(64, case["x1"] - case["x0"])
    xs = np.arange(case["x0"], case["x0"] + n, dtype=np.uint32)
    weight = np.asarray(case["weight"], np.uint32)

    plane = PlacementPlane(cmap, choose_args=cargs, mesh=mesh)
    res, lens = plane.map_batch(case["ruleno"], xs, case["numrep"],
                                weight)
    bm = BatchedMapper(cmap, choose_args=cargs)
    res_un, lens_un = bm.map_batch(case["ruleno"], xs, case["numrep"],
                                   weight)
    assert np.array_equal(np.asarray(res), np.asarray(res_un))
    assert np.array_equal(np.asarray(lens), np.asarray(lens_un))
    res, lens = np.asarray(res), np.asarray(lens)
    for i in range(n):
        assert list(res[i, :lens[i]]) == case["results"][i], f"x={i}"


def test_placement_plane_single_device_mesh(cmap):
    """The degenerate 1-device mesh: same code path, same results —
    the tier-1 guarantee that nothing forks on single-chip hosts
    (runs regardless of how many devices the env provides)."""
    mesh1 = make_mesh(jax.devices()[:1])
    plane = PlacementPlane(cmap, mesh=mesh1)
    weight = np.full(cmap.max_devices, 0x10000, np.uint32)
    xs = np.arange(37, dtype=np.uint32)   # non-pow2, non-divisible
    res, lens, counts = plane.map_batch(0, xs, 3, weight,
                                        gather_stats=True)
    bm = BatchedMapper(cmap)
    res_un, lens_un = bm.map_batch(0, xs, 3, weight)
    assert np.array_equal(np.asarray(res), np.asarray(res_un))
    assert np.array_equal(np.asarray(lens), np.asarray(lens_un))
    assert int(np.asarray(counts).sum()) == int(
        np.asarray(lens_un).sum())


def test_pad_batch_bounds_signatures():
    """pow2 padding: every batch size in [1, 4096] lands on one of
    O(log) padded shapes, all divisible by the mesh size."""
    for n_dev in (1, 3, 8):
        pads = {pad_batch(n, n_dev) for n in range(1, 4097)}
        assert len(pads) <= 14, (n_dev, sorted(pads))
        assert all(p % n_dev == 0 for p in pads)
        assert all(pad_batch(n, n_dev) >= n for n in range(1, 4097))


def test_placement_plane_recompile_budget(mesh, cmap):
    """Mesh size changes must not leak compile signatures beyond the
    pow2-padding budget: after warming a plane per mesh size, every
    further batch that pads to a warmed shape hits the jit cache —
    zero new compiles in the steady-state window (the conftest gate
    fails this test on any violation; the assert is the explicit
    twin)."""
    weight = np.full(cmap.max_devices, 0x10000, np.uint32)
    planes = [PlacementPlane(cmap, mesh=mesh),
              PlacementPlane(cmap, mesh=make_mesh(jax.devices()[:1]))]
    for plane in planes:          # warmup: one compile per mesh size
        plane.map_batch(0, np.arange(64, dtype=np.uint32), 3, weight)
    base = len(jaxcheck.recompile_violations())
    with jaxcheck.steady_state("placement.plane.mesh_sizes"):
        for plane in planes:
            for n in (64, 40, 33, 64):   # all pad to the warmed 64
                res, lens = plane.map_batch(
                    0, np.arange(n, dtype=np.uint32), 3, weight)
                assert np.asarray(res).shape == (n, 3)
    assert len(jaxcheck.recompile_violations()) == base


def test_golden_map_sharded(mesh):
    """Production-shaped check: the 10k-OSD golden map, sharded over the
    mesh, still reproduces the reference C core's golden vectors."""
    import json
    import pathlib

    d = json.load(open(pathlib.Path(__file__).parent /
                       "golden/map_big10k.json"))
    cmap10k = CrushMap.from_dict(d["map"])
    case = d["cases"][0]
    n = 64  # first 64 golden xs, padded to a multiple of N_DEV
    fn, static, arrays = sharded_rule_fn(cmap10k, case["ruleno"],
                                         case["numrep"], mesh,
                                         gather_stats=False)
    repl = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P("pg"))
    A = jax.tree_util.tree_map(
        lambda a: jax.device_put(jnp.asarray(a), repl), arrays)
    w = jax.device_put(
        jnp.asarray(np.asarray(case["weight"], np.uint32)), repl)
    xs = jax.device_put(
        jnp.arange(case["x0"], case["x0"] + n, dtype=np.uint32), shard)
    res, lens = fn(A, w, xs)
    res, lens = np.asarray(res), np.asarray(lens)
    for i in range(n):
        assert list(res[i, :lens[i]]) == case["results"][i], f"i={i}"

"""The chip programs of the main path, compiled for a described v5e.

No chip is attached: the TPU compiler compiles for a topology it is
given, and refuses what the chip would refuse (tiling, fast memory,
programs that do not fit HBM).  These compiles guard every later PR at
no chip time; chip_smoke.py runs the same programs on the chip.

The topology is described inside a module fixture (never at import:
only one process may load libtpu, and every xdist worker imports this
file), and the persistent compilation cache is off around the compiles
(a described-chip entry cannot be read back without a chip).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import GOLDEN_DIR

V5E_HBM = 16 << 30        # bytes of HBM on one v5e chip
OBJ = 4 << 20             # chip_smoke's object size
N_OBJ = 64                # chip_smoke's encode batch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", prior)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prior)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _shape(a, sharding):
    a = np.asarray(a)
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)


@pytest.mark.parametrize("k,m,rows", [
    (8, 3, 3),    # isa k=8,m=3 encode
    (8, 3, 8),    # its decode: the 8k x 8k inverse over k survivors
    (4, 2, 2),    # jerasure reed_sol_van k=4,m=2 encode
], ids=["encode_k8m3", "decode_k8", "encode_k4m2"])
def test_fused_ec_kernel_compiles(one_chip, k, m, rows):
    """The fused kernel over chip_smoke's batch of 4 MiB objects lowers
    to the compiled Mosaic kernel, not interpret mode — with x64 on, as
    in every process that has loaded the CRUSH mapper."""
    import ceph_tpu.crush.mapper_jax  # noqa: F401 — turns x64 on
    from ceph_tpu.ec import pallas_kernels as PK

    assert jax.config.jax_enable_x64

    lanes = N_OBJ * OBJ // k
    bm = jax.ShapeDtypeStruct((8 * rows, 8 * k), jnp.int8,
                              sharding=one_chip)
    data = jax.ShapeDtypeStruct((k, lanes), jnp.uint8, sharding=one_chip)
    lowered = PK._call.lower(bm, data, k=k, m=rows, interpret=False,
                             tile=PK._LANE_TILE)
    assert "tpu_custom_call" in lowered.as_text()
    ma = lowered.compile().memory_analysis()
    assert ma.output_size_in_bytes >= rows * lanes
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes) <= V5E_HBM


@pytest.mark.parametrize("spec", [True, False], ids=["spec", "general_vm"])
def test_crushtool_launch_fits_chip(one_chip, monkeypatch, spec):
    """``crushtool --test`` over chip_smoke's 2^20 inputs on the 10k-OSD
    map, at the launch size ``BatchedMapper`` picks on an idle chip
    (half its HBM): the speculative lowering maps the range in one
    launch; the general rule VM (``build_rule_fn``, the path of rules
    the speculative lowering refuses), at about 0.5 MB per lane, in
    several.  Either launch compiles and fits."""
    import json

    from ceph_tpu.crush.lowering import BatchedMapper, fit_lanes
    from ceph_tpu.crush.map import CrushMap
    from ceph_tpu.crush.mapper_jax import build_rule_fn

    monkeypatch.setenv("CEPH_TPU_STRAW2", "table")
    cmap = CrushMap.from_dict(
        json.load(open(GOLDEN_DIR / "map_big10k.json"))["map"])
    bm = BatchedMapper(cmap)
    fn = bm.rule_fn(0, 3) if spec else \
        build_rule_fn(cmap, 0, 3, encoded=bm._encoded)[0]
    arrays = jax.tree_util.tree_map(lambda a: _shape(a, one_chip),
                                    bm._encoded[1])
    weight = jax.ShapeDtypeStruct((cmap.max_devices,), jnp.uint32,
                                  sharding=one_chip)

    def compile_at(lanes):
        xs = jax.ShapeDtypeStruct((lanes,), jnp.uint32,
                                  sharding=one_chip)
        return fn.lower(arrays, weight, xs).compile()

    n = 1 << 20
    lanes = fit_lanes(n, compile_at, V5E_HBM // 2)
    assert (lanes == n) == spec, lanes
    ma = compile_at(lanes).memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert need <= V5E_HBM // 2, (lanes, need)

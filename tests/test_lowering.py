"""One lowering decision for every CRUSH front end.

``lowering.lower_rule`` takes the speculative program exactly where
``mapper_spec.analyze`` accepts the rule, and the general rule VM
elsewhere; ``BatchedMapper``, ``PlacementPlane`` and ``PoolMapper`` each
book the lowering they took, and the mapping front ends give the golden
vectors of the reference C core either way.
"""

import jax
import numpy as np
import pytest

from test_mapper_spec import ELIGIBLE, INELIGIBLE, load

from ceph_tpu.common.perf_counters import collection
from ceph_tpu.crush.lowering import BatchedMapper, lower_rule
from ceph_tpu.crush.mapper_spec import Ineligible, analyze
from ceph_tpu.osdmap.osdmap import OSDMap, PgPool
from ceph_tpu.osdmap.pipeline_jax import PoolMapper
from ceph_tpu.parallel.placement import PlacementPlane, make_mesh

CASES = sorted(ELIGIBLE) + sorted(INELIGIBLE)
KINDS = ("lowered_spec", "lowered_general")


def lowered():
    c = collection().dump("crush.mapper")["crush.mapper"]
    return {k: c[k] for k in KINDS}


@pytest.mark.parametrize("name,ruleno", CASES,
                         ids=[f"{n}-rule{r}" for n, r in CASES])
def test_every_front_end_takes_the_lowering_analyze_picks(name, ruleno):
    cmap, d = load(name)
    cargs = cmap.choose_args.get("golden")
    case = next(c for c in d["cases"] if c["ruleno"] == ruleno)
    numrep = case["numrep"]
    try:
        analyze(cmap, ruleno, numrep)
        spec = True
    except Ineligible:
        spec = False
    assert spec == ((name, ruleno) in ELIGIBLE)
    assert lower_rule(cmap, ruleno, numrep, cargs).speculative == spec
    want = {"lowered_spec": int(spec), "lowered_general": int(not spec)}

    n = min(case["x1"] - case["x0"], 48)
    xs = np.arange(case["x0"], case["x0"] + n, dtype=np.uint32)
    weight = np.asarray(case["weight"], np.uint32)
    fronts = {
        "BatchedMapper": BatchedMapper(cmap, choose_args=cargs),
        "PlacementPlane": PlacementPlane(
            cmap, choose_args=cargs, mesh=make_mesh(jax.devices()[:1])),
    }
    for front, mapper in fronts.items():
        before = lowered()
        res, lens = (np.asarray(v) for v in mapper.map_batch(
            ruleno, xs, numrep, weight))
        after = lowered()
        assert {k: after[k] - before[k] for k in KINDS} == want, front
        for i in range(n):
            assert list(res[i, :lens[i]]) == case["results"][i], \
                (front, int(xs[i]))

    # the OSDMap pipeline books its lowering when it is built
    m = OSDMap(cmap)
    for osd in range(cmap.max_devices):
        m.add_osd(osd)
    m.pools[1] = PgPool(pool_type=1, size=numrep, min_size=1, pg_num=16,
                        crush_rule=ruleno)
    before = lowered()
    PoolMapper(m, 1)
    after = lowered()
    assert {k: after[k] - before[k] for k in KINDS} == want

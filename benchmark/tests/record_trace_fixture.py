"""Record the small profiler trace ``test_trace.py`` reads.

    python3 benchmark/tests/record_trace_fixture.py OUT.xplane.pb

Run on a machine with a TPU.  Inside one ``bench.window`` span it maps
a 1,024-PG pool of the 10,000-OSD map once (``bench.map_all``), sleeps
50 ms with nothing on the device (``bench.sleep``), encodes two 64 KiB
objects in one isa k=8,m=3 batch (``bench.encode``) and decodes one of
them with a data chunk missing (``bench.decode``), with the profiler's
Python tracer off, as the harness runs it.
"""

import pathlib
import shutil
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))


def main(out: str) -> int:
    import jax
    import numpy as np

    from benchmark.lib.crushmap import build_map
    from benchmark.lib.harness import enable_compile_cache
    from benchmark.lib.spec import BENCH
    from ceph_tpu.crush.map import CrushMap
    from ceph_tpu.ec.registry import profile_factory
    from ceph_tpu.osdmap.osdmap import OSDMap, PgPool
    from ceph_tpu.osdmap.pipeline_jax import PoolMapper

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    import json

    cfg = json.loads((BENCH / "configs" / "crush10k.json").read_text())
    m = OSDMap(CrushMap.from_dict(build_map(cfg["crush"])))
    for osd in range(m.crush.max_devices):
        m.add_osd(osd)
    m.pools[1] = PgPool(size=3, min_size=2, pg_num=1024, crush_rule=0)
    pm = PoolMapper(m, 1)
    np.asarray(pm.map_all()["up"])
    code = profile_factory({"plugin": "isa", "technique": "reed_sol_van",
                            "k": "8", "m": "3", "engine": "pallas-fused"})
    rng = np.random.default_rng(0)
    raws = [rng.bytes(1 << 16) for _ in range(2)]
    enc = code.encode_batched(range(11), raws)
    chunks = {i: np.asarray(enc[0][i]) for i in range(11) if i != 2}
    code.decode_concat(chunks)

    tdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.map_all"):
            np.asarray(pm.map_all()["up"])
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.05)
        with jax.profiler.TraceAnnotation("bench.encode"):
            enc = code.encode_batched(range(11), raws)
            np.asarray(enc[1][10])
        with jax.profiler.TraceAnnotation("bench.decode"):
            assert code.decode_concat(chunks) == raws[0]
    jax.profiler.stop_trace()
    src = next(pathlib.Path(tdir).rglob("*.xplane.pb"))
    shutil.copy(src, out)
    shutil.rmtree(tdir, ignore_errors=True)
    print(f"wrote {out} ({pathlib.Path(out).stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

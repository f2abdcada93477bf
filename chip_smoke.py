"""chip_smoke — the system's main path on one TPU chip, every phase
checked against its CPU reference.

    python chip_smoke.py             five phases on jax.devices()[0]
    python chip_smoke.py --chips 4   the four-chip phase and its 1-chip
                                     reference only (a 2x2 v5e host)

Phases (one process, no child process; any mismatch or exception exits
non-zero at once, and no phase falls back to the CPU):

1. device   — the default backend is a TPU.
2. crush    — ``crushtool --test`` over x in [0, 2^20) on the 10k-OSD
              map, every mapping against the native C++ mapper and the
              golden cases against tests/golden.
3. osdmap   — ``PoolMapper.map_all`` over a 2^20-PG size-3 pool with
              all 10,000 OSDs up and in, against the host
              ``OSDMap.pg_to_up_acting_osds`` on 2,048 PGs.
4. ec       — 64 x 4 MiB objects through ``encode_batched`` on the
              compiled Pallas kernel (isa k=8,m=3 and jerasure
              reed_sol_van k=4,m=2), parity against the native engine
              and a decode with m chunks erased against the original.
5. served   — a MiniCluster of 11 hosts: 32 x 4 MiB objects written to
              and read back from a replicated size-3 pool and an isa
              k=8,m=3 ``engine=pallas-fused`` pool; the EC pool's
              encodes are booked as device launches.

Each phase prints one line with its compile and run seconds, its sizes
and its verdict; the last line of stdout is the JSON result.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent
BIG_MAP = REPO / "tests" / "golden" / "map_big10k.json"
OBJ = 4 << 20          # the rados bench default object size
ISA_83 = {"plugin": "isa", "technique": "reed_sol_van", "k": "8",
          "m": "3"}
JER_42 = {"plugin": "jerasure", "technique": "reed_sol_van", "k": "4",
          "m": "2"}

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_s = [0.0]
_listening: list = []


class Mismatch(AssertionError):
    """A result that disagrees with its reference."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Mismatch(what)


def _on_event(event: str, seconds: float, **_kw) -> None:
    if event in _COMPILE_EVENTS:
        _compile_s[0] += seconds


def run_phase(name: str, fn, *args, **kw) -> dict:
    """Run one phase; print its line (compile seconds from JAX's own
    compile events, run seconds the rest of its wall time)."""
    if not _listening:
        import jax

        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _listening.append(True)
    c0, t0 = _compile_s[0], time.perf_counter()
    sizes = fn(*args, **kw)
    wall = time.perf_counter() - t0
    comp = _compile_s[0] - c0
    print(f"phase {name}: compile_s={comp:.3f} run_s={wall - comp:.3f} "
          f"{json.dumps(sizes, sort_keys=True)} ok", flush=True)
    return sizes


def _crush_map():
    from ceph_tpu.crush.map import CrushMap

    d = json.loads(BIG_MAP.read_text())
    return CrushMap.from_dict(d["map"]), d["cases"]


def _crushtool(*argv) -> list:
    """``crushtool.main`` in-process; its stdout lines."""
    from ceph_tpu.tools import crushtool

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = crushtool.main([str(a) for a in argv])
    check(rc == 0, f"crushtool exited {rc}")
    return buf.getvalue().splitlines()


def phase_crush(n_x: int) -> dict:
    import numpy as np

    from ceph_tpu.crush.native import NativeMapper

    cmap, cases = _crush_map()
    lines = _crushtool("-i", BIG_MAP, "--test", "--rule", 0,
                       "--num-rep", 3, "--min-x", 0, "--max-x", n_x - 1,
                       "--show-mappings", "--show-statistics")
    got = [ln for ln in lines if ln.startswith("CRUSH rule 0 x ")]
    check(len(got) == n_x, f"{len(got)} mapping lines for {n_x} inputs")
    xs = np.arange(n_x, dtype=np.uint32)
    res, lens = NativeMapper(cmap).map_batch(
        0, xs, 3, np.full(cmap.max_devices, 0x10000, np.uint32))
    want = [f"CRUSH rule 0 x {x} {r[:n].tolist()}"
            for x, r, n in zip(range(n_x), res, lens)]
    bad = [(a, b) for a, b in zip(got, want) if a != b]
    check(not bad, f"{len(bad)}/{n_x} mappings differ from the native "
                   f"mapper, first {bad[:1]}")
    stats = [ln for ln in lines if "result size ==" in ln]
    check(stats == [f"rule 0 (rule0) num_rep 3 result size == 3:\t"
                    f"{n_x}/{n_x}"], f"statistics {stats}")
    # the golden cases: rule 0 from the sweep above, rule 1 (indep,
    # 11 shards: the EC rule's shape) through its own crushtool run
    for case in cases:
        n = case["x1"] - case["x0"]
        lines = got if case["ruleno"] == 0 else [
            ln for ln in _crushtool(
                "-i", BIG_MAP, "--test", "--rule", case["ruleno"],
                "--num-rep", case["numrep"], "--min-x", case["x0"],
                "--max-x", case["x1"] - 1, "--show-mappings")
            if ln.startswith("CRUSH rule")]
        gold = [f"CRUSH rule {case['ruleno']} x {case['x0'] + i} {r}"
                for i, r in enumerate(case["results"])]
        check(lines[:n] == gold, f"golden rule {case['ruleno']} differs")
    return {"inputs": n_x, "osds": cmap.max_devices, "num_rep": 3,
            "golden_cases": len(cases)}


def big_osdmap(pg_num: int):
    """All 10,000 OSDs of the big map up and in; pool 1 replicated
    size 3 with ``pg_num`` PGs."""
    from ceph_tpu.osdmap.osdmap import OSDMap, PgPool

    cmap, _ = _crush_map()
    m = OSDMap(cmap)
    for osd in range(cmap.max_devices):
        m.add_osd(osd)
    m.pools[1] = PgPool(size=3, pg_num=pg_num)
    return m


def phase_osdmap(pg_num: int, n_check: int) -> dict:
    import numpy as np

    from ceph_tpu.osdmap.pipeline_jax import PoolMapper

    m = big_osdmap(pg_num)
    out = {k: np.asarray(v)
           for k, v in PoolMapper(m, 1).map_all().items()}
    check(out["up"].shape == (pg_num, 3), f"up shape {out['up'].shape}")
    for ps in np.unique(np.linspace(0, pg_num - 1, n_check).astype(int)):
        up, upp, act, actp = m.pg_to_up_acting_osds(1, int(ps))
        got = (out["up"][ps, :out["up_len"][ps]].tolist(),
               int(out["up_primary"][ps]),
               out["acting"][ps, :out["acting_len"][ps]].tolist(),
               int(out["acting_primary"][ps]))
        check(got == (up, upp, act, actp),
              f"pg 1.{ps:x}: device {got} host {(up, upp, act, actp)}")
    full = int((out["up_len"] == 3).sum())
    return {"pg_num": pg_num, "osds": m.max_osd, "checked": n_check,
            "pgs_with_3_up": full}


def _objects(n: int, size: int, seed: int):
    import numpy as np

    return np.random.default_rng(seed).integers(
        0, 256, (n, size), dtype=np.uint8)


def _ec_counters() -> dict:
    from ceph_tpu.common.perf_counters import collection

    return dict(collection().dump("ec.engine")["ec.engine"])


def _launches(c0: dict, c1: dict) -> tuple:
    """(EC ops, compiled device launches, interpret-mode launches)
    booked between two ``ec.engine`` counter snapshots."""
    d = {k: c1[k] - c0[k] for k in ("encode_ops", "decode_ops",
                                    "device_launches",
                                    "interpret_launches")}
    return (d["encode_ops"] + d["decode_ops"], d["device_launches"],
            d["interpret_launches"])


def _check_kernel_form(what: str, ops: int, launches: int,
                       interp: int) -> None:
    """Every EC op ran the fused kernel compiled for the chip, as the
    engine booked it (off the chip — the CPU rehearsal — every op ran
    it in interpret mode)."""
    from ceph_tpu.ec import pallas_kernels as PK

    want = (ops, 0) if PK.on_tpu() else (0, ops)
    check(ops > 0 and (launches, interp) == want,
          f"{what}: {launches} compiled and {interp} interpret-mode "
          f"kernel launches for {ops} EC ops")


def _fused_kernel_text(code, k: int, lanes: int, interpret: bool) -> str:
    """The lowered text of the engine's fused kernel call for a
    u8[k, lanes] operand in the form the engine booked."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.ec import pallas_kernels as PK

    bm = jnp.asarray(code._code._enc_dev, jnp.int8)
    lanes += (-lanes) % PK._LANE_TILE
    return PK._call.lower(bm, jax.ShapeDtypeStruct((k, lanes), jnp.uint8),
                          k=k, m=bm.shape[0] // 8, interpret=interpret,
                          tile=PK._LANE_TILE).as_text()


def phase_ec(n_obj: int, size: int, seed: int) -> dict:
    import numpy as np

    from ceph_tpu.ec import pallas_kernels as PK
    from ceph_tpu.ec.registry import profile_factory

    raws = _objects(n_obj, size, seed)
    sizes = {"objects": n_obj, "object_bytes": size}
    for prof in (ISA_83, JER_42):
        fused = profile_factory(dict(prof, engine="pallas-fused"))
        native = profile_factory(dict(prof, engine="native"))
        k, n = fused.get_data_chunk_count(), fused.get_chunk_count()
        m = n - k
        L = fused.get_chunk_size(size)
        check(L * k == size, f"{prof}: {size} B objects pad to {L * k}")
        want = native.encode_batched(range(n), list(raws))
        c0 = _ec_counters()
        got = fused.encode_batched(range(n), list(raws))
        for b in range(n_obj):
            for i in range(n):
                check(np.array_equal(got[b][i], want[b][i]),
                      f"{prof['plugin']} k={k},m={m} object {b} chunk "
                      f"{i}: pallas-fused != native")
        ops, launches, interp = _launches(c0, _ec_counters())
        _check_kernel_form(f"{prof['plugin']} encode", ops, launches,
                           interp)
        text = _fused_kernel_text(fused, k, n_obj * L, interp > 0)
        check(("tpu_custom_call" in text) == PK.on_tpu(),
              "the encode's kernel did not lower to a Mosaic kernel")
        # decode the whole batch at once (chunk i = every object's
        # chunk i, as encode_batched lays it out) with m chunks erased
        erased = list(range(0, 2 * m, 2))
        cat = {i: np.concatenate([got[b][i] for b in range(n_obj)])
               for i in range(n) if i not in erased}
        dec = fused.decode(set(range(k)), cat)
        data = raws.reshape(n_obj, k, L)
        for i in range(k):
            check(np.array_equal(np.asarray(dec[i]),
                                 data[:, i, :].reshape(-1)),
                  f"{prof['plugin']} decode with {erased} erased: "
                  f"chunk {i} != original")
        ops, launches, interp = _launches(c0, _ec_counters())
        _check_kernel_form(f"{prof['plugin']} encode+decode", ops,
                           launches, interp)
        sizes[f"{prof['plugin']}_k{k}m{m}"] = {
            "erased": erased, "device_launches": launches,
            "interpret_launches": interp}
    return sizes


def phase_served(n_obj: int, size: int, seed: int) -> dict:
    from ceph_tpu.common.config import Config
    from ceph_tpu.services.cluster import MiniCluster

    raws = _objects(n_obj, size, seed)
    conf = Config()
    # the reference's defaults: this phase drives the data path, and
    # the drill-sized 2 s grace marks busy in-process OSDs down
    conf.set("osd_heartbeat_grace", 20.0)
    conf.set("mon_osd_down_out_interval", 600.0)
    cluster = MiniCluster(11, config=conf).start()
    try:
        cluster.create_replicated_pool(1, pg_num=32, size=3)
        cluster.create_ec_pool(2, "isa83", dict(ISA_83,
                                                engine="pallas-fused"),
                               pg_num=32)
        client = cluster.client()
        c0 = _ec_counters()
        for pool in (1, 2):
            for i in range(n_obj):
                client.put(pool, f"obj{i}", raws[i].tobytes())
        for pool in (1, 2):
            for i in range(n_obj):
                got = client.get(pool, f"obj{i}")
                check(got == raws[i].tobytes(),
                      f"pool {pool} obj{i}: read != written")
        c1 = _ec_counters()
    finally:
        cluster.shutdown()
    enc = c1["encode_ops"] - c0["encode_ops"]
    check(enc > 0, "the EC pool booked no encode")
    ops, launches, interp = _launches(c0, c1)
    _check_kernel_form("served EC pool", ops, launches, interp)
    return {"osds": 11, "objects_per_pool": n_obj, "object_bytes": size,
            "ec_encode_ops": enc, "device_launches": launches,
            "interpret_launches": interp}


def phase_four_chips(devices, pg_num: int, n_obj: int, size: int,
                     seed: int) -> dict:
    """The mesh data plane against its 1-chip result: the PG-sharded
    placement pipeline and the stripe-sharded encode."""
    import numpy as np

    from ceph_tpu.ec.registry import profile_factory
    from ceph_tpu.osdmap.pipeline_jax import PoolMapper
    from ceph_tpu.parallel.placement import make_mesh, mesh_device_report

    mesh = make_mesh(devices)
    m = big_osdmap(pg_num)
    one = PoolMapper(m, 1).map_all()
    four = PoolMapper(m, 1, mesh=mesh).map_all()
    shards = {s.device.id for s in four["up"].addressable_shards}
    check(shards == {d.id for d in devices},
          f"up rows live on devices {sorted(shards)}")
    for key in one:
        check(np.array_equal(np.asarray(one[key]), np.asarray(four[key])),
              f"PoolMapper {key}: 4-chip != 1-chip")
    code = profile_factory(dict(ISA_83, engine="pallas-fused"))
    n = code.get_chunk_count()
    raws = list(_objects(n_obj, size, seed))
    c0 = _ec_counters()
    a = code.encode_batched(range(n), raws)
    b = code.encode_batched(range(n), raws, mesh=mesh)
    _check_kernel_form("1-chip and 4-chip encodes",
                       *_launches(c0, _ec_counters()))
    for i in range(n_obj):
        for j in range(n):
            check(np.array_equal(np.asarray(a[i][j]), np.asarray(b[i][j])),
                  f"object {i} chunk {j}: 4-chip encode != 1-chip")
    rows = mesh_device_report(mesh)
    for row in rows:
        print(f"device {json.dumps(row, sort_keys=True)}", flush=True)
    check(all(r.get("kernel_launches", 0) > 0 for r in rows),
          "a chip ran no encode kernel")
    check(all(r.get("peak_bytes_in_use", 1) > 0 for r in rows),
          "a chip never held a buffer")
    return {"chips": len(devices), "pg_num": pg_num, "objects": n_obj,
            "object_bytes": size}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="chip_smoke")
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    t0 = time.perf_counter()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: jax.devices()[0].platform is "
              f"{dev.platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"visible", file=sys.stderr)
        return 2

    from ceph_tpu.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    print(f"phase device: kind={dev.device_kind!r} visible="
          f"{len(devices)} compile_cache={cache} ok", flush=True)
    try:
        if args.chips == 4:
            run_phase("four_chips", phase_four_chips, devices[:4],
                      1 << 20, 64, OBJ, args.seed)
        else:
            run_phase("crush", phase_crush, 1 << 20)
            run_phase("osdmap", phase_osdmap, 1 << 20, 2048)
            run_phase("ec", phase_ec, 64, OBJ, args.seed)
            run_phase("served", phase_served, 32, OBJ, args.seed)
    except Exception as e:
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        raise
    print(f"chip_smoke: wall_s={time.perf_counter() - t0:.3f}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

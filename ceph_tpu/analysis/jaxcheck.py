"""JAX kernel-contract registry + recompilation budget gate.

The XLA-axis twin of lockdep (PR 1): on TPU the silent killers are not
segfaults but recompilation storms, dtype drift, and host-device sync
points.  None of those are Python exceptions, so — like lock order —
they are CHECKED as structure, not assumed:

- **Contract registry**: every jitted kernel in ``ceph_tpu/ec/`` and
  ``ceph_tpu/crush/`` registers a declarative shape/dtype contract
  (inputs over a k/m/stripe grid → exact output ShapeDtypeStructs).
  ``verify_all()`` proves them via ``jax.eval_shape`` — abstract
  tracing only, no device execution, no XLA compile — under
  ``jax_numpy_dtype_promotion='strict'``, so a silent weak-type
  promotion to int64/float64 anywhere in a kernel fails the contract
  the way a lock-order inversion fails lockdep.  Integer lanes must
  stay uint8 (EC chunk bytes) / int32 (CRUSH results): any output
  leaf drifting to a 64-bit or float dtype is a violation even if the
  declared dtype matched nothing.
- **Recompile gate**: ``steady_state()`` marks a phase that must hit
  the XLA jit cache.  The EC engine and the batched CRUSH mapper
  already book first-call compiles per shape signature
  (``ec.engine``/``crush.mapper`` ``jit_compiles`` perf counters, PR
  2); any growth inside the window is recorded as a violation that
  the per-test conftest gate turns into a test failure — the
  "recompilation storm" class (a shape-unstable batch axis, a
  forgotten static arg) caught at the test that introduces it.

The static half of this layer lives in ``tools/lint_jax.py``
(JAX001..JAX004), mirrored on ``tools/lint_concurrency.py``.
"""

from __future__ import annotations

import contextlib
import os
from dataclasses import dataclass
from typing import Callable, Dict, List, Sequence, Tuple

# NOTE: jax is imported lazily inside functions — this module is
# imported by the analysis package for every process, including ones
# that never touch a device.

# dtypes an integer kernel may legitimately produce; anything outside
# (int64/float64 from weak-type promotion, float32 from an accidental
# true-divide) is dtype drift.  The CRUSH mapper runs under
# jax_enable_x64 by DESIGN (straw2 is 64-bit fixed-point) but its
# public outputs are int32 — internal i64 lanes never leak out.
_INTEGER_LANES = ("uint8", "int32", "uint32")


@dataclass
class ContractViolation:
    contract: str
    case: str
    message: str

    def __str__(self) -> str:
        return f"[{self.contract}/{self.case}] {self.message}"


@dataclass
class Case:
    """One (kernel, input-grid-point) check.

    ``mode='eval_shape'`` (the default) proves the contract abstractly;
    ``mode='concrete'`` runs the kernel on the tiny given inputs — only
    for host-side engines (native GF) that have no traceable form.
    ``allow64`` exempts a case from the integer-lane drift check (none
    of the builtin contracts need it)."""

    label: str
    fn: Callable
    args: Sequence
    want: Sequence[Tuple[Tuple[int, ...], str]]
    mode: str = "eval_shape"
    allow64: bool = False


_REGISTRY: Dict[str, Callable[[], List[Case]]] = {}


def register_contract(name: str,
                      builder: Callable[[], List[Case]]) -> None:
    """``builder()`` returns the contract's cases; it runs at verify
    time so registering costs nothing at import."""
    _REGISTRY[name] = builder


def contracts() -> List[str]:
    return sorted(_REGISTRY)


def _leaf_specs(out) -> List[Tuple[Tuple[int, ...], str]]:
    import jax

    leaves = jax.tree_util.tree_leaves(out)
    return [(tuple(leaf.shape), str(leaf.dtype)) for leaf in leaves]


def _run_case(contract: str, case: Case) -> List[ContractViolation]:
    import jax

    out: List[ContractViolation] = []
    try:
        with jax.numpy_dtype_promotion("strict"):
            if case.mode == "eval_shape":
                got = jax.eval_shape(case.fn, *case.args)
            else:
                got = case.fn(*case.args)
    except Exception as e:
        return [ContractViolation(
            contract, case.label,
            f"kernel failed to trace under strict dtype promotion: "
            f"{e!r}")]
    specs = _leaf_specs(got)
    want = [(tuple(s), str(d)) for s, d in case.want]
    if specs != want:
        out.append(ContractViolation(
            contract, case.label,
            f"output signature mismatch: got {specs}, want {want}"))
    if not case.allow64:
        for shape, dtype in specs:
            if dtype not in _INTEGER_LANES:
                out.append(ContractViolation(
                    contract, case.label,
                    f"integer-lane drift: output {shape} has dtype "
                    f"{dtype} (allowed: {_INTEGER_LANES}) — a silent "
                    f"weak-type promotion or float leak"))
    return out


def verify(name: str) -> List[ContractViolation]:
    builder = _REGISTRY.get(name)
    if builder is None:
        raise KeyError(f"no contract {name!r}; have {contracts()}")
    try:
        cases = builder()
    except Exception as e:
        return [ContractViolation(name, "<build>",
                                  f"contract builder failed: {e!r}")]
    out: List[ContractViolation] = []
    for case in cases:
        out.extend(_run_case(name, case))
    return out


def verify_all() -> List[ContractViolation]:
    """Prove every registered contract.  Empty list = all kernels honor
    their declared shape/dtype signatures under strict promotion."""
    out: List[ContractViolation] = []
    for name in contracts():
        out.extend(verify(name))
    return out


# ---------------------------------------------------------------------------
# recompilation budget gate
# ---------------------------------------------------------------------------

_recompile_violations: List[Dict] = []

# the perf counters that book first-call JIT compiles per shape
# signature (PR 2): ec.engine (bit-plane + Pallas engines) and
# crush.mapper (BatchedMapper launches)
_COMPILE_COUNTERS = ("ec.engine", "crush.mapper")


def compile_counters() -> Dict[str, float]:
    """Snapshot of every booked-compile counter that currently exists
    (a counter appears when its module first imports)."""
    from ..common.perf_counters import collection

    out: Dict[str, float] = {}
    for name in _COMPILE_COUNTERS:
        try:
            dumped = collection().dump(name)
        except KeyError:
            continue
        pc = dumped.get(name, {})
        if "jit_compiles" in pc:
            out[f"{name}.jit_compiles"] = pc["jit_compiles"]
    return out


@contextlib.contextmanager
def steady_state(label: str = ""):
    """Wrap a phase that must be compile-free: every shape signature it
    launches has already been traced+compiled (warmup ran outside the
    window).  A new compile inside the window — a shape-unstable batch
    axis, a dtype flip, a missing static arg — records a violation
    that the per-test conftest gate fails the test on."""
    before = compile_counters()
    yield
    after = compile_counters()
    grew = {key: (before.get(key, 0), val)
            for key, val in after.items() if val > before.get(key, 0)}
    if grew:
        detail = ", ".join(f"{key} {int(a)}->{int(b)}"
                           for key, (a, b) in sorted(grew.items()))
        _recompile_violations.append({
            "label": label or "<steady-state>",
            "message": (f"steady-state phase {label or '?'!r} "
                        f"triggered new XLA compile(s): {detail} — a "
                        f"shape/dtype-unstable launch is recompiling "
                        f"per call"),
            "counters": grew,
        })


def recompile_violations() -> List[Dict]:
    return list(_recompile_violations)


def clear_recompile_violations() -> None:
    del _recompile_violations[:]


# ---------------------------------------------------------------------------
# builtin contracts: every jitted EC / CRUSH kernel
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _bitplane_engine():
    """Plugin construction under CEPH_TPU_EC_ENGINE=bitplane: contracts
    check the JITted array kernels, and the registry would otherwise
    put the host-native GF engine behind w=8 matrix techniques."""
    old = os.environ.get("CEPH_TPU_EC_ENGINE")
    os.environ["CEPH_TPU_EC_ENGINE"] = "bitplane"
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("CEPH_TPU_EC_ENGINE", None)
        else:
            os.environ["CEPH_TPU_EC_ENGINE"] = old


def _u8(*shape):
    import jax

    return jax.ShapeDtypeStruct(shape, "uint8")


def _bitcode_cases(label: str, bc, L: int) -> List[Case]:
    """Encode + decode-with-erasures contracts for one engine.BitCode:
    the exact to_rows → mod-2 MXU matmul → from_rows composition the
    XLA path executes (the Pallas fusion has its own contract)."""
    from ..ec.engine import _mod2_matmul

    k, m, layout = bc.k, bc.m, bc.layout
    layout.check(L)

    def enc(data):
        rows = layout.to_rows(data)
        return layout.from_rows(_mod2_matmul(bc._enc_dev, rows), m, L)

    # erase one data chunk and one parity chunk (the classic
    # double-fault), survive on the first k of what remains
    erased = {0, k} if m > 1 else {0}
    present = tuple(i for i in range(k + m) if i not in erased)[:k]
    (inv,) = bc._decode_mats(present)

    def dec(stack):
        rows = layout.to_rows(stack)
        return layout.from_rows(_mod2_matmul(inv, rows), k, L)

    tag = f"{label}/L={L}"
    return [
        Case(f"{tag}/encode", enc, [_u8(k, L)], [((m, L), "uint8")]),
        Case(f"{tag}/decode[erased={sorted(erased)}]", dec,
             [_u8(k, L)], [((k, L), "uint8")]),
    ]


def _plugin_chunk(plugin, object_size: int = 1 << 12) -> int:
    return plugin.get_chunk_size(object_size)


def _contract_mod2_matmul() -> List[Case]:
    from ..ec.engine import _mod2_matmul

    out = []
    for (r, c, n) in ((8, 16, 512), (24, 64, 4096), (256, 128, 1024)):
        out.append(Case(
            f"({r}x{c})@({c}x{n})", _mod2_matmul,
            [_u8(r, c), _u8(c, n)], [((r, n), "uint8")]))
    return out


def _contract_rs_jax() -> List[Case]:
    from ..ec import gf
    from ..ec.rs_jax import RSCode, gf_matmul_bits

    out: List[Case] = []
    for k, m in ((2, 1), (4, 2), (8, 3)):
        code = RSCode(k, m)
        out.extend(_bitcode_cases(f"rs(k={k},m={m})", code._bit, 4096))
    # the expanded-bitmatrix byte API the stripe layer shares
    bm = gf.expand_bitmatrix(gf.rs_vandermonde_matrix(4, 2)[4:])
    out.append(Case(
        "gf_matmul_bits(4->2)", gf_matmul_bits,
        [bm, _u8(4, 1024)], [((2, 1024), "uint8")]))
    return out


def _contract_jerasure() -> List[Case]:
    from ..ec.jerasure import make_jerasure

    grids = [
        ("reed_sol_van", {"k": "2", "m": "1", "w": "8"}),
        ("reed_sol_van", {"k": "4", "m": "2", "w": "8"}),
        ("reed_sol_van", {"k": "3", "m": "2", "w": "16"}),
        ("reed_sol_van", {"k": "3", "m": "2", "w": "32"}),
        ("reed_sol_r6_op", {"k": "4", "m": "2", "w": "8"}),
        ("cauchy_good", {"k": "4", "m": "2", "w": "8",
                         "packetsize": "8"}),
        ("cauchy_orig", {"k": "3", "m": "2", "w": "8",
                         "packetsize": "8"}),
        ("liberation", {"k": "3", "m": "2", "w": "7",
                        "packetsize": "8"}),
        ("blaum_roth", {"k": "3", "m": "2", "w": "6",
                        "packetsize": "8"}),
        ("liber8tion", {"k": "4", "m": "2", "w": "8",
                        "packetsize": "8"}),
    ]
    out: List[Case] = []
    with _bitplane_engine():
        for tech, prof in grids:
            plugin = make_jerasure(dict(prof, technique=tech))
            L = _plugin_chunk(plugin)
            label = (f"{tech}(k={prof['k']},m={prof['m']},"
                     f"w={prof['w']})")
            out.extend(_bitcode_cases(label, plugin._code, L))
    return out


def _contract_isa() -> List[Case]:
    from ..ec.isa import make_isa

    out: List[Case] = []
    with _bitplane_engine():
        for tech, k, m in (("reed_sol_van", 7, 3),
                           ("reed_sol_van", 4, 2),
                           ("cauchy", 4, 2)):
            plugin = make_isa({"technique": tech, "k": str(k),
                               "m": str(m)})
            out.extend(_bitcode_cases(
                f"{tech}(k={k},m={m})", plugin._code,
                _plugin_chunk(plugin)))
    return out


def _contract_lrc() -> List[Case]:
    """LRC is layered: each layer executes on its own jerasure BitCode,
    so the jitted kernels ARE the layers' engines."""
    from ..ec.registry import factory

    out: List[Case] = []
    with _bitplane_engine():
        for prof in ({"k": "4", "m": "2", "l": "3"},
                     {"k": "2", "m": "2", "l": "2"}):
            lrc = factory("lrc", dict(prof))
            L = _plugin_chunk(lrc)
            tag = f"k={prof['k']},m={prof['m']},l={prof['l']}"
            for i, layer in enumerate(lrc.layers):
                code = getattr(layer.erasure_code, "_code", None)
                if code is None:
                    continue
                out.extend(_bitcode_cases(
                    f"lrc({tag})/layer{i}", code, L))
    return out


def _contract_shec() -> List[Case]:
    """SHEC has no BitCode facade: encode is to_rows → matmul(enc_bm)
    → from_rows over its multiple-locality matrix; decode solves the
    minimal recovery system per erasure (host GF(w) inversion) and
    runs the same matmul — mirrored here exactly."""
    import numpy as np

    from ..ec.engine import _mod2_matmul
    from ..ec.registry import factory

    out: List[Case] = []
    for prof in ({"k": "4", "m": "3", "c": "2"},
                 {"k": "6", "m": "2", "c": "1"}):
        shec = factory("shec", dict(prof))
        L = _plugin_chunk(shec)
        layout = shec._layout
        enc_bm = np.asarray(shec._enc_bm)
        tag = f"shec(k={prof['k']},m={prof['m']},c={prof['c']})"

        def enc(data, layout=layout, enc_bm=enc_bm, shec=shec, L=L):
            rows = layout.to_rows(data)
            return layout.from_rows(_mod2_matmul(enc_bm, rows),
                                    shec.m, L)

        out.append(Case(f"{tag}/L={L}/encode", enc,
                        [_u8(shec.k, L)],
                        [((shec.m, L), "uint8")]))
        # decode-with-erasures: lose data chunk 0, recover it from the
        # minimal system (the locality win) — the runtime decode_chunks
        # flow: GF(w) sub-matrix inversion on host, expand to bits,
        # one mod-2 matmul over the [rows] survivor stack
        n = shec.k + shec.m
        want = [1] + [0] * (n - 1)
        avails = [0] + [1] * (n - 1)
        found = shec._search(want, avails)
        if found is None:
            out.append(Case(
                f"{tag}/decode[erased=[0]]",
                lambda: (_ for _ in ()).throw(AssertionError(
                    "shec: single data erasure unrecoverable")),
                [], [], mode="concrete"))
            continue
        _dup, rows_idx, cols, _minimum = found
        sub = [[(1 if r == c_ else 0) if r < shec.k
                else shec.matrix[r - shec.k][c_] for c_ in cols]
               for r in rows_idx]
        inv = shec._gf.mat_inv(sub)
        need_idx = [i for i, c_ in enumerate(cols) if not avails[c_]]
        bm = np.asarray(
            shec._gf.expand_bitmatrix([inv[i] for i in need_idx]))

        def dec(stack, layout=layout, bm=bm, L=L,
                nrec=len(need_idx)):
            rows = layout.to_rows(stack)
            return layout.from_rows(_mod2_matmul(bm, rows), nrec, L)

        out.append(Case(
            f"{tag}/L={L}/decode[erased=[0]]", dec,
            [_u8(len(rows_idx), L)],
            [((len(need_idx), L), "uint8")]))
    return out


def _contract_clay() -> List[Case]:
    """CLAY orchestrates sub-chunk planes on the host; every byte of
    device math runs on its scalar-MDS sub-codes (mds + pft), so those
    BitCodes carry the contract.  Geometry (sub_chunk_no = q^t) is
    asserted here too — a wrong sub-chunk count scrambles every plane."""
    from ..ec.registry import factory

    out: List[Case] = []
    with _bitplane_engine():
        for prof in ({"k": "4", "m": "2"},
                     {"k": "3", "m": "3", "d": "5"}):
            clay = factory("clay", dict(prof))
            # geometry invariant checked at build: a wrong sub-chunk
            # count scrambles every plane before any kernel runs
            assert clay.sub_chunk_no == clay.q ** clay.t, \
                (clay.sub_chunk_no, clay.q, clay.t)
            tag = f"clay(k={prof['k']},m={prof['m']})"
            for sub, name in ((clay.mds, "mds"), (clay.pft, "pft")):
                code = getattr(sub, "_code", None)
                if code is not None:
                    out.extend(_bitcode_cases(
                        f"{tag}/{name}", code,
                        _plugin_chunk(sub, 1 << 10)))
    return out


def _contract_native_gf() -> List[Case]:
    """The host GF(2^8) table engine has no traced form; its contract
    runs concrete on tiny chunks (microseconds) — same shape/dtype
    assertions, same strict-promotion context."""
    from ..ec.native_gf import NativeRS

    out: List[Case] = []
    for k, m in ((4, 2), (8, 3)):
        code = NativeRS(k, m)
        L = 64
        data = __import__("numpy").zeros((k, L), "uint8")
        out.append(Case(
            f"native_rs(k={k},m={m})/encode", code.encode, [data],
            [((m, L), "uint8")], mode="concrete"))
        full = code.all_chunks(data)
        chunks = {i: full[i] for i in range(k + m)}
        out.append(Case(
            f"native_rs(k={k},m={m})/decode[erased=[0,1]]",
            code.decode, [chunks, [0, 1]],
            [((k, L), "uint8")], mode="concrete"))
    return out


def _contract_pallas() -> List[Case]:
    """The fused unpack→MXU→pack kernel: same byte-level signature as
    the XLA path it replaces on TPU."""
    import functools

    import numpy as np

    from ..ec import gf
    from ..ec.pallas_kernels import fused_gf2_matmul_w8

    out: List[Case] = []
    for k, m, L in ((4, 2, 4096), (8, 3, 8192)):
        bm = gf.expand_bitmatrix(
            gf.rs_vandermonde_matrix(k, m)[k:]).astype(np.int8)
        out.append(Case(
            f"fused_w8(k={k},m={m},L={L})",
            functools.partial(fused_gf2_matmul_w8, interpret=True),
            [bm, _u8(k, L)], [((m, L), "uint8")]))
    return out


def _contract_pallas_engine() -> List[Case]:
    """The registry-promoted 'pallas-fused' engine: a jerasure/isa
    profile with ``engine=pallas-fused`` must honor the same byte
    signatures as the engines it replaces, on the single-device path
    (encode + batched encode) AND the mesh path (per-device fused
    dispatch).  Concrete mode on tiny shapes: the per-device split is
    host-side orchestration with no single traceable form."""
    import numpy as np

    from ..ec.jerasure import make_jerasure
    from ..parallel.placement import make_mesh

    plugin = make_jerasure({"technique": "reed_sol_van", "k": "4",
                            "m": "2", "w": "8",
                            "engine": "pallas-fused"})
    bc = plugin._code
    assert bc.force_fused, "profile engine=pallas-fused not routed"
    k, m, L, B = bc.k, bc.m, 64, 4
    rng = np.random.default_rng(0xFA)
    data = rng.integers(0, 256, (k, L), dtype=np.uint8)
    stripes = rng.integers(0, 256, (B, k, L), dtype=np.uint8)
    out = [
        Case("encode", bc.encode, [data], [((m, L), "uint8")],
             mode="concrete"),
        Case(f"encode_batched/B={B}",
             lambda s: bc.encode_batched(s, mesh=None), [stripes],
             [((B, m, L), "uint8")], mode="concrete"),
    ]
    import jax

    devs = jax.devices()
    meshes = [(1, make_mesh(devs[:1], axis_name="ec"))]
    if len(devs) > 1:
        meshes.append((len(devs), make_mesh(devs, axis_name="ec")))
    for n_dev, mesh in meshes:
        out.append(Case(
            f"encode_batched_sharded/B={B}/ndev={n_dev}",
            lambda s, mesh=mesh: bc.encode_batched_sharded(s, mesh),
            [stripes], [((B, m, L), "uint8")], mode="concrete"))
    return out


def _contract_crush_mapper() -> List[Case]:
    """crush_do_rule_batched: (arrays, weight u32[D], xs u32[N]) →
    (results i32[N, R], lens i32[N]) for both rule families (firstn
    chooseleaf and indep/EC) on a production-shaped 3-level map.  The
    mapper computes in 64-bit fixed point BY DESIGN (straw2); the
    contract pins that none of it leaks into the outputs."""
    import jax

    from ..crush.builder import sample_cluster_map
    from ..crush.mapper_jax import build_rule_fn

    cmap = sample_cluster_map(racks=2, hosts_per_rack=2,
                              osds_per_host=2)

    def abstract_args(arrays, n):
        return [
            jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                arrays),
            jax.ShapeDtypeStruct((cmap.max_devices,), "uint32"),
            jax.ShapeDtypeStruct((n,), "uint32"),
        ]

    out: List[Case] = []
    for ruleno in (0, 1):
        for result_max, n in ((3, 64), (5, 256)):
            fn, _static, arrays = build_rule_fn(cmap, ruleno,
                                                result_max)
            out.append(Case(
                f"rule{ruleno}/R={result_max}/N={n}", fn,
                abstract_args(arrays, n),
                [((n, result_max), "int32"), ((n,), "int32")]))
    # the division-free table-key straw2 lowering (the TPU default;
    # CPU defaults to the arithmetic path, so force it)
    old = os.environ.get("CEPH_TPU_STRAW2")
    os.environ["CEPH_TPU_STRAW2"] = "table"
    try:
        fn, _static, arrays = build_rule_fn(cmap, 0, 3)
    finally:
        if old is None:
            os.environ.pop("CEPH_TPU_STRAW2", None)
        else:
            os.environ["CEPH_TPU_STRAW2"] = old
    out.append(Case(
        "rule0/R=3/N=64/straw2=table", fn, abstract_args(arrays, 64),
        [((64, 3), "int32"), ((64,), "int32")]))
    return out


def _contract_crush_mapper_spec() -> List[Case]:
    """The divergence-free speculative lowering (the fast TPU engine):
    same public signature as the general rule VM."""
    import jax

    from ..crush.builder import sample_cluster_map
    from ..crush.mapper_spec import build_spec_rule_fn

    cmap = sample_cluster_map(racks=2, hosts_per_rack=2,
                              osds_per_host=2)
    out: List[Case] = []
    for ruleno in (0, 1):
        fn, _static, arrays = build_spec_rule_fn(cmap, ruleno, 3,
                                                 k_tries=1)
        abstract = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), arrays)
        weight = jax.ShapeDtypeStruct((cmap.max_devices,), "uint32")
        xs = jax.ShapeDtypeStruct((64,), "uint32")
        out.append(Case(
            f"rule{ruleno}/R=3/N=64", fn, [abstract, weight, xs],
            [((64, 3), "int32"), ((64,), "int32")]))
    return out


def _contract_encode_batched() -> List[Case]:
    """The batched-encode entry (engine.BitCode.encode_batched): B
    same-shape stripes stack on a leading batch axis, flatten to one
    (k, B*L) launch of the SAME mod-2 kernel, and split back — the
    exact composition the data-plane coalescer dispatches."""
    from ..ec.engine import _mod2_matmul
    from ..ec.rs_jax import RSCode

    out: List[Case] = []
    for k, m, B, L in ((2, 1, 4, 4096), (4, 2, 8, 4096),
                       (8, 3, 16, 1024)):
        bc = RSCode(k, m)._bit
        layout, enc = bc.layout, bc._enc_dev

        def encb(stripes, bc=bc, layout=layout, enc=enc, B=B, L=L):
            flat = stripes.transpose(1, 0, 2).reshape(bc.k, B * L)
            rows = layout.to_rows(flat)
            par = layout.from_rows(_mod2_matmul(enc, rows), bc.m,
                                   B * L)
            return par.reshape(bc.m, B, L).transpose(1, 0, 2)

        out.append(Case(
            f"rs(k={k},m={m})/B={B}/L={L}", encb,
            [_u8(B, k, L)], [((B, m, L), "uint8")]))
    return out


def _contract_sharded_rule_fn() -> List[Case]:
    """parallel.sharded_rule_fn (the PlacementPlane engine): the
    masked, PG-axis-sharded batched mapper over a 1-device mesh (the
    degenerate CI case) and the full device mesh when more than one
    device exists.  Outputs: PG-sharded (results, lens) plus — with
    gather_stats — the all-reduced utilization tally, all int32."""
    import jax

    from ..crush.builder import sample_cluster_map
    from ..parallel.placement import make_mesh, sharded_rule_fn

    cmap = sample_cluster_map(racks=2, hosts_per_rack=2,
                              osds_per_host=2)
    devs = jax.devices()
    meshes = [(1, make_mesh(devs[:1]))]
    if len(devs) > 1:
        meshes.append((len(devs), make_mesh(devs)))
    out: List[Case] = []
    for n_dev, mesh in meshes:
        for gather in (False, True):
            fn, static, arrays = sharded_rule_fn(
                cmap, 0, 3, mesh, gather_stats=gather, masked=True)
            N = 64
            args = [
                jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                    arrays),
                jax.ShapeDtypeStruct((cmap.max_devices,), "uint32"),
                jax.ShapeDtypeStruct((N,), "uint32"),
                jax.ShapeDtypeStruct((N,), "bool"),
            ]
            want = [((N, 3), "int32"), ((N,), "int32")]
            if gather:
                want.append(((static.max_devices,), "int32"))
            out.append(Case(
                f"rule0/R=3/N={N}/ndev={n_dev}/gather={gather}",
                fn, args, want))
    return out


def _contract_encode_batched_sharded() -> List[Case]:
    """ec.engine.encode_batched_sharded: the stripe-batch-sharded
    encode — u8[B, k, L] with B sharded across the mesh -> parity
    u8[B, m, L] sharded the same way, on the 1-device degenerate mesh
    and the full mesh."""
    import jax

    from ..ec.rs_jax import RSCode
    from ..parallel.placement import make_mesh

    devs = jax.devices()
    meshes = [(1, make_mesh(devs[:1], axis_name="ec"))]
    if len(devs) > 1:
        meshes.append((len(devs), make_mesh(devs, axis_name="ec")))
    out: List[Case] = []
    for k, m, B, L in ((4, 2, 8, 4096), (8, 3, 16, 1024)):
        bc = RSCode(k, m)._bit
        for n_dev, mesh in meshes:
            fn = bc._mesh_fn(mesh, "ec")
            out.append(Case(
                f"rs(k={k},m={m})/B={B}/L={L}/ndev={n_dev}", fn,
                [_u8(B, k, L)], [((B, m, L), "uint8")]))
    return out


def _register_builtin_contracts() -> None:
    register_contract("ec.engine.mod2_matmul", _contract_mod2_matmul)
    register_contract("ec.engine.encode_batched",
                      _contract_encode_batched)
    register_contract("ec.engine.encode_batched_sharded",
                      _contract_encode_batched_sharded)
    register_contract("parallel.sharded_rule_fn",
                      _contract_sharded_rule_fn)
    register_contract("ec.rs_jax", _contract_rs_jax)
    register_contract("ec.jerasure", _contract_jerasure)
    register_contract("ec.isa", _contract_isa)
    register_contract("ec.lrc", _contract_lrc)
    register_contract("ec.shec", _contract_shec)
    register_contract("ec.clay", _contract_clay)
    register_contract("ec.native_gf", _contract_native_gf)
    register_contract("ec.pallas", _contract_pallas)
    register_contract("ec.pallas_engine", _contract_pallas_engine)
    register_contract("crush.mapper_jax", _contract_crush_mapper)
    register_contract("crush.mapper_spec", _contract_crush_mapper_spec)


_register_builtin_contracts()

#!/usr/bin/env python
"""JAX compile-hygiene lint — static companion to analysis/jaxcheck.

AST-level checks for the XLA-axis bug classes that never raise on CPU
but destroy TPU throughput (recompilation storms, host-device sync
points, device work on latency-critical threads), enforced by
tests/test_lint.py like the CONC rules:

JAX001  a ``jnp.*`` / ``jax.*`` / ``lax.*`` call lexically inside a
        ``with <lock>`` block or inside a messenger handler (a
        function named ``_h_*``, the services dispatch convention).
        Device dispatch blocks on the backend and — worse — the first
        call with a new shape blocks on XLA *compilation*; doing that
        while holding a lock or occupying a dispatch-pool worker
        stalls every thread behind it (the CONC002 class, XLA
        edition).

JAX002  a host-device sync point in a hot-path module: ``.item()``,
        ``float(x)``, ``np.asarray(...)``, ``.block_until_ready()``.
        Each one forces the async dispatch queue to drain — the
        silent serializer that turns an overlapped pipeline into
        lockstep.  ``__init__`` bodies are exempt (setup is not the
        hot path); benchmark/sync points that are deliberate carry a
        ``# jax-ok: <reason>``.

JAX003  a jit-decorated function whose body reads ``self.*`` or
        declares ``global``.  jax.jit captures closed-over values at
        TRACE time: mutated state silently serves stale values from
        the compiled cache (or retraces per call if used as a
        hashable static) — the classic "jit ate my update" bug.

JAX004  a Python ``if``/``while`` testing a parameter of a
        jit-decorated function (minus ``static_argnames``).  Traced
        values have no truth value — this either raises
        ``TracerBoolConversionError`` at runtime or, when the branch
        collapses at trace time, silently bakes one path in.

Suppression: append ``# jax-ok: <reason>`` to the offending line (or
the introducing ``with``/``def`` line).  The reason is mandatory — it
is the allowlist entry.  tests/test_lint.py additionally carries a
committed allowlist for known-acceptable hits in ``ceph_tpu/``.

Usage:
    python tools/lint_jax.py [paths...]   # default: ceph_tpu/
Exit status 1 when violations are found.
"""

from __future__ import annotations

import ast
import pathlib
import sys
from dataclasses import dataclass
from typing import Iterable, List, Optional

SUPPRESS_MARK = "jax-ok:"

# the roots whose attribute calls mean "device work"
_JAX_ROOTS = {"jnp", "jax", "lax"}

# modules where a host-device sync point is a throughput bug, not a
# style point: the EC engines, both CRUSH lowerings and the module that
# picks between them, the fused OSDMap pipeline, and the mesh data plane
HOT_MODULES = (
    "ec/engine.py",
    "ec/rs_jax.py",
    "ec/pallas_kernels.py",
    "crush/lowering.py",
    "crush/mapper_jax.py",
    "crush/mapper_spec.py",
    "crush/ln.py",
    "crush/hash.py",
    "osdmap/pipeline_jax.py",
    "parallel/placement.py",
)

_SYNC_ATTRS = {"item", "block_until_ready"}
# lock-ish context-manager spellings (shared with lint_concurrency)
LOCKISH_MARKERS = ("lock", "_cv", "_cond", "_serial", "mutex")


@dataclass
class Violation:
    path: str
    line: int
    code: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: {self.code} {self.message}"


def _suppressed(src_lines: List[str], *linenos: int) -> bool:
    for ln in linenos:
        if 1 <= ln <= len(src_lines) and \
                SUPPRESS_MARK in src_lines[ln - 1]:
            return True
    return False


def _is_lockish(expr: ast.AST) -> bool:
    try:
        text = ast.unparse(expr)
    except Exception:
        return False
    tail = text.split("(", 1)[0].rsplit(".", 1)[-1].lower()
    return any(m in tail for m in LOCKISH_MARKERS)


def _dotted_root(expr: ast.AST) -> Optional[str]:
    """'jnp' for jnp.where(...), 'jax' for jax.lax.cond(...), etc."""
    while isinstance(expr, ast.Attribute):
        expr = expr.value
    if isinstance(expr, ast.Name):
        return expr.id
    return None


def _is_jax_call(node: ast.Call) -> bool:
    return _dotted_root(node.func) in _JAX_ROOTS


def _jit_static_argnames(deco: ast.AST) -> Optional[List[str]]:
    """Non-None when ``deco`` spells a jax.jit decoration; the list
    holds any literal static_argnames."""
    target = deco
    statics: List[str] = []
    if isinstance(deco, ast.Call):
        # functools.partial(jax.jit, static_argnames=(...)) or
        # jax.jit(...)-with-options used as a decorator factory
        root = _dotted_root(deco.func)
        name = deco.func.attr if isinstance(deco.func, ast.Attribute) \
            else (deco.func.id if isinstance(deco.func, ast.Name)
                  else "")
        if name == "partial" and deco.args:
            target = deco.args[0]
        elif name == "jit":
            target = deco.func
        else:
            return None
        for kw in deco.keywords:
            if kw.arg in ("static_argnames", "static_argnums"):
                try:
                    val = ast.literal_eval(kw.value)
                except Exception:
                    continue
                if isinstance(val, str):
                    statics.append(val)
                elif isinstance(val, (tuple, list)):
                    statics.extend(str(v) for v in val)
        del root
    if isinstance(target, ast.Attribute) and target.attr == "jit":
        return statics
    if isinstance(target, ast.Name) and target.id == "jit":
        return statics
    return None


class _FileLinter(ast.NodeVisitor):
    def __init__(self, path: str, rel: str, src: str):
        self.path = path
        self.rel = rel
        self.lines = src.splitlines()
        self.out: List[Violation] = []
        self.hot = any(self.rel.endswith(m) for m in HOT_MODULES)
        self._with_lock_stack: List[int] = []
        self._handler_stack: List[str] = []  # _h_* function names
        self._init_depth = 0  # inside an __init__ body

    def _emit(self, code: str, node: ast.AST, message: str,
              *extra_lines: int) -> None:
        if _suppressed(self.lines, node.lineno, *extra_lines):
            return
        self.out.append(Violation(self.rel, node.lineno, code,
                                  message))

    # -- JAX001 / JAX002 ----------------------------------------------
    def visit_Call(self, node: ast.Call) -> None:
        if _is_jax_call(node):
            if self._with_lock_stack:
                self._emit(
                    "JAX001", node,
                    f"device call {ast.unparse(node.func)!r} while a "
                    f"lock is held (with-block at line "
                    f"{self._with_lock_stack[-1]}): dispatch — and "
                    f"first-shape XLA compilation — blocks every "
                    f"thread behind this lock",
                    self._with_lock_stack[-1])
            elif self._handler_stack:
                self._emit(
                    "JAX001", node,
                    f"device call {ast.unparse(node.func)!r} inside "
                    f"messenger handler {self._handler_stack[-1]!r}: "
                    f"device work on a dispatch-pool worker "
                    f"head-of-line-blocks the daemon's message plane")
        if self.hot and not self._init_depth:
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr in _SYNC_ATTRS:
                self._emit(
                    "JAX002", node,
                    f"host-device sync {ast.unparse(f)!r}() in "
                    f"hot-path module: drains the async dispatch "
                    f"queue")
            elif isinstance(f, ast.Attribute) and \
                    f.attr == "asarray" and \
                    _dotted_root(f) == "np":
                self._emit(
                    "JAX002", node,
                    "np.asarray() in hot-path module copies device "
                    "memory to host (a sync point); keep hot data on "
                    "device or mark the deliberate boundary with "
                    "# jax-ok:")
            elif isinstance(f, ast.Name) and f.id == "float" and \
                    node.args and not isinstance(node.args[0],
                                                 ast.Constant):
                self._emit(
                    "JAX002", node,
                    "float(x) in hot-path module forces a scalar "
                    "device→host readback")
        self.generic_visit(node)

    # -- lock-scope tracking (the CONC002 walker) ---------------------
    def visit_With(self, node: ast.With) -> None:
        lockish = any(_is_lockish(item.context_expr)
                      for item in node.items)
        for item in node.items:
            self.visit(item)
        if lockish:
            self._with_lock_stack.append(node.lineno)
        for stmt in node.body:
            self.visit(stmt)
        if lockish:
            self._with_lock_stack.pop()

    # -- JAX003 / JAX004 ----------------------------------------------
    def _check_jit_body(self, node, statics: List[str]) -> None:
        params = {a.arg for a in (node.args.posonlyargs
                                  + node.args.args
                                  + node.args.kwonlyargs)}
        traced = params - set(statics) - {"self"}
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name) and sub.id == "self":
                self._emit(
                    "JAX003", sub,
                    f"jitted {node.name!r} reads 'self': jit captures "
                    f"closed-over state at trace time — a later "
                    f"mutation silently serves stale compiled "
                    f"results", node.lineno)
                break
        for sub in ast.walk(node):
            if isinstance(sub, ast.Global):
                self._emit(
                    "JAX003", sub,
                    f"jitted {node.name!r} declares global state; "
                    f"thread it through as an argument", node.lineno)
        for sub in ast.walk(node):
            if not isinstance(sub, (ast.If, ast.While)):
                continue
            names = {n.id for n in ast.walk(sub.test)
                     if isinstance(n, ast.Name)}
            hit = names & traced
            if hit:
                self._emit(
                    "JAX004", sub,
                    f"Python {'if' if isinstance(sub, ast.If) else 'while'} "
                    f"on traced value(s) {sorted(hit)} inside jitted "
                    f"{node.name!r}: traced values have no truth "
                    f"value — use lax.cond/lax.select (or mark the "
                    f"arg static)", node.lineno)

    def _visit_function(self, node) -> None:
        statics = None
        for deco in node.decorator_list:
            s = _jit_static_argnames(deco)
            if s is not None:
                statics = s
                break
        is_handler = node.name.startswith("_h_")
        is_init = node.name == "__init__"
        # a nested def is a fresh frame: locks held around the def are
        # not held when it runs
        saved = self._with_lock_stack
        self._with_lock_stack = []
        if is_handler:
            self._handler_stack.append(node.name)
        if is_init:
            self._init_depth += 1
        self.generic_visit(node)
        if is_init:
            self._init_depth -= 1
        if is_handler:
            self._handler_stack.pop()
        self._with_lock_stack = saved
        if statics is not None and not _suppressed(self.lines,
                                                   node.lineno):
            self._check_jit_body(node, statics)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._visit_function(node)

    def visit_AsyncFunctionDef(self, node) -> None:
        self._visit_function(node)


def lint_file(path: pathlib.Path,
              root: Optional[pathlib.Path] = None) -> List[Violation]:
    rel = str(path if root is None else path.relative_to(root))
    src = path.read_text()
    try:
        tree = ast.parse(src, filename=str(path))
    except SyntaxError as e:
        return [Violation(rel, e.lineno or 0, "JAX000",
                          f"unparseable: {e.msg}")]
    linter = _FileLinter(str(path), rel, src)
    linter.visit(tree)
    return sorted(linter.out, key=lambda v: v.line)


def lint_paths(paths: Iterable[pathlib.Path]) -> List[Violation]:
    out: List[Violation] = []
    for p in paths:
        p = pathlib.Path(p)
        if p.is_dir():
            root = p.parent
            for f in sorted(p.rglob("*.py")):
                out.extend(lint_file(f, root=root))
        else:
            out.extend(lint_file(p))
    return out


# Known-acceptable JAX002 hits in ceph_tpu/: every one is a deliberate
# host<->device API boundary, not a hot-loop sync point.  An entry is
# (path suffix, code, substring that must appear on the flagged line);
# a NEW violation matches none of these and fails both the CLI and
# tests/test_lint.py (which imports this table — one source of truth,
# so `python tools/lint_jax.py` and the unified tools/lint.py runner
# agree with the test about what is clean).
ALLOWLIST = (
    # batch ingest: normalize caller arrays once before device upload
    ("crush/lowering.py", "JAX002", "np.asarray(xs, np.uint32)"),
    ("crush/lowering.py", "JAX002", "np.asarray(weight, np.uint32)"),
    # the explicit *_np host-egress API of the RS facade
    ("ec/rs_jax.py", "JAX002", "np.asarray(self.encode(data))"),
    ("ec/rs_jax.py", "JAX002", "np.asarray(self.decode(chunks"),
    # per-epoch upload of the mutable OSD map vectors
    ("osdmap/pipeline_jax.py", "JAX002", "np.asarray(m.osd_weight"),
    ("osdmap/pipeline_jax.py", "JAX002", "np.asarray(m.osd_state"),
    ("osdmap/pipeline_jax.py", "JAX002", "np.asarray("),
    # np.asarray over the device LIST building a Mesh (no data moved)
    ("parallel/placement.py", "JAX002", "np.asarray(devices)"),
)


def allowlisted(v: Violation) -> bool:
    """Does this violation match a committed ALLOWLIST entry (path
    suffix + code + line substring)?"""
    src = pathlib.Path(v.path)
    if not src.is_absolute():
        src = pathlib.Path(__file__).resolve().parents[1] / v.path
    try:
        line = src.read_text().splitlines()[v.line - 1]
    except (OSError, IndexError):
        return False
    return any(v.path.endswith(path) and v.code == code and sub in line
               for path, code, sub in ALLOWLIST)


def main(argv: List[str]) -> int:
    targets = [pathlib.Path(a) for a in argv] or \
        [pathlib.Path(__file__).resolve().parents[1] / "ceph_tpu"]
    violations = [v for v in lint_paths(targets) if not allowlisted(v)]
    for v in violations:
        print(v)
    if violations:
        print(f"{len(violations)} JAX hygiene lint violation(s)")
        return 1
    print("jax hygiene lint clean")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))

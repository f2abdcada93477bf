"""Test harness config.

Tests run hermetically on CPU (``JAX_PLATFORMS=cpu``) with 8 virtual XLA
devices so every multi-chip sharding path (pjit/shard_map over a Mesh) is
exercised without TPU hardware; tests/test_chip_compile.py compiles the
chip programs for a described v5e, and chip_smoke.py runs them on the chip.
The virtual-device XLA flag is injected before any backend client exists.
"""

import os
import pathlib
import sys
import threading
import time
import warnings

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long soaks excluded from the tier-1 lane "
        "(-m 'not slow'); run explicitly with -m slow")

# lockdep on for the WHOLE suite (overridable with CEPH_TPU_LOCKDEP=0):
# every test inherits the lock-order checker, so a future PR that
# introduces an inversion fails its own tests with both witness
# stacks.  Must precede any ceph_tpu import — make_lock() decides
# wrapper-vs-raw at construction time.
os.environ.setdefault("CEPH_TPU_LOCKDEP", "1")
# racecheck rides lockdep's held-set: the data-race lockset checker is
# on for the whole suite too (overridable with CEPH_TPU_RACECHECK=0).
# Must also precede any ceph_tpu import — guarded_by()/shared()
# decide instrument-vs-identity at class decoration time.
os.environ.setdefault("CEPH_TPU_RACECHECK", "1")

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

# Persistent XLA compilation cache (ceph_tpu/utils/compile_cache.py:
# JAX_COMPILATION_CACHE_DIR when set, else <repo>/.jax_cache): the
# suite's wall time is dominated by compiling the big golden mapper
# programs, and warm runs skip recompiling identical programs.
import jax  # noqa: E402

from ceph_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache(min_compile_secs=0.5)

GOLDEN_DIR = pathlib.Path(__file__).resolve().parent / "golden"

from ceph_tpu.analysis import (jaxcheck, lockdep, racecheck,  # noqa: E402
                               watchdog)
from ceph_tpu.common import bufpool, tracing  # noqa: E402

# -- JAX hygiene gates (the XLA twin of the concurrency gates below) --
#
# Kernel test modules run under jax_numpy_dtype_promotion=strict: a
# silent int64/float64 weak-type promotion in EC/CRUSH math becomes a
# TypePromotionError at the test that introduces it (the contract
# checker pins the fixed dtypes; this keeps new code honest at
# runtime too).
STRICT_DTYPE_MODULES = {
    "test_ec", "test_jerasure", "test_lrc_isa", "test_shec",
    "test_clay", "test_stripe", "test_native_gf", "test_pallas",
    "test_mapper_jax", "test_mapper_spec", "test_contracts",
}
# jax.checking_leaks for the kernel suites that exercise every jitted
# kernel cheaply: test_contracts traces them all (and this gate caught
# a real leaked-tracer bug in the straw2 table-key path), test_pallas
# covers the fused kernel.  NOT the wide EC roundtrip matrices — leak
# checking disables trace caching and turned test_ec's 2s erasure
# sweeps into 75s (measured), blowing the tier-1 time budget.
TRACER_LEAK_MODULES = {"test_contracts", "test_pallas"}


@pytest.fixture(scope="session", autouse=True)
def _stall_watchdog():
    """Session-wide stall watchdog: a test that wedges a lock or a
    messenger handler gets an all-thread stack dump on stderr while
    it hangs, instead of an opaque suite timeout."""
    yield watchdog.start_global(threshold=30.0)


@pytest.fixture(autouse=True)
def _jax_hygiene_gate(request):
    """Per-test JAX gates, mirroring the concurrency gates below.

    1. Strict dtype promotion + tracer-leak checking for the kernel
       test modules (see the module sets above).
    2. Recompile budget: any ``jaxcheck.steady_state()`` window that
       booked a new XLA compile (the ec.engine / crush.mapper
       per-shape-signature counters) fails THAT test — the
       recompilation-storm class caught at the test introducing it.
    """
    import contextlib

    mod = getattr(getattr(request, "module", None), "__name__", "")
    mod = mod.rsplit(".", 1)[-1]
    base = len(jaxcheck.recompile_violations())
    with contextlib.ExitStack() as stack:
        if mod in STRICT_DTYPE_MODULES:
            stack.enter_context(jax.numpy_dtype_promotion("strict"))
        if mod in TRACER_LEAK_MODULES:
            stack.enter_context(jax.checking_leaks())
        yield
    vs = jaxcheck.recompile_violations()[base:]
    if vs:
        jaxcheck.clear_recompile_violations()  # don't re-fail later tests
        detail = "\n".join(f"- [{v['label']}] {v['message']}"
                           for v in vs)
        pytest.fail(f"recompile gate: {len(vs)} steady-state "
                    f"compile violation(s) during this test:\n{detail}")


@pytest.fixture(autouse=True)
def _concurrency_gate(request):
    """Per-test concurrency gates.

    1. Lockdep: any lock-order violation recorded during the test
       fails THAT test (witness stacks were already printed).
    2. Thread leak: threads a test spawned must be gone shortly after
       it finishes.  Leaked non-daemon threads fail the test; leaked
       daemon threads (a cluster not fully shut down — the exact
       cross-test interference that made the quorum rejoin test
       flaky) get a grace period to die, then a warning.  Either way
       the NEXT test starts from a quiesced process.
    3. Buffer leak: every pooled recv segment acquired during the
       test must be released by test end (after the thread quiesce) —
       a held segment means a messenger/dispatch path dropped its
       ``Segment.release()``, the use-after-free-in-waiting the
       refcount contract exists to catch.  Like the span gate, live
       daemon threads (a shared cluster fixture still draining) may
       yet release — warn instead of fail.
    4. Span leak: every tracing span opened during the test must be
       finished by test end (after the thread quiesce above).  A span
       left open with no daemon thread alive to ever finish it means a
       code path began a span outside a ``with`` (lint CONC004's
       runtime twin) or an op died mid-trace — that fails the test,
       and the spans are abandoned so one leaky test cannot re-fail
       every later one.  With live daemon threads still draining (a
       shared cluster fixture's background recovery/heartbeat RPCs),
       an open span may yet finish — warn, like the thread gate.
    """
    before = set(threading.enumerate())
    before_spans = {id(s) for _svc, s in tracing.active_spans()}
    before_segs = len(bufpool.outstanding())
    base = len(lockdep.violations())
    race_base = racecheck.mark()
    yield
    vs = lockdep.violations()[base:]
    if vs:
        lockdep.clear_violations()  # don't re-fail every later test
        detail = "\n".join(
            f"- {v['message']} [{v['thread']}]\n"
            f"  existing order recorded at:\n{v['existing_stack']}"
            f"  conflicting order taken at:\n{v['current_stack']}"
            for v in vs)
        pytest.fail(f"lockdep: {len(vs)} lock-order violation(s) "
                    f"during this test:\n{detail}")

    # racecheck gate: a data-race violation (empty candidate lockset,
    # broken thread confinement) fails the owning test with both
    # access stacks, exactly like the lockdep gate above
    race_msg = racecheck.gate_check(race_base)
    if race_msg is not None:
        pytest.fail(race_msg)

    def leaked():
        return [t for t in threading.enumerate()
                if t not in before and t.is_alive()]

    # daemon-only stragglers get a short grace (they die with their
    # sockets); anything non-daemon gets longer before failing
    deadline = time.monotonic() + 1.5
    hard_deadline = time.monotonic() + 5.0
    left = leaked()
    while left and time.monotonic() < deadline:
        time.sleep(0.05)
        left = leaked()
    while left and any(not t.daemon for t in left) and \
            time.monotonic() < hard_deadline:
        time.sleep(0.05)
        left = leaked()
    bad = [t for t in left if not t.daemon]
    assert not bad, (f"test leaked non-daemon thread(s): "
                     f"{[t.name for t in bad]}")
    if left:
        warnings.warn(
            f"{request.node.nodeid} leaked daemon thread(s): "
            f"{sorted(t.name for t in left)[:10]}"
            f"{'...' if len(left) > 10 else ''}")

    # bufpool leak gate: in-flight dispatch gets a short drain window;
    # comparing against the BEFORE count means a segment stuck forever
    # fails only the test that leaked it, not every later one
    seg_deadline = time.monotonic() + 2.0
    held = bufpool.outstanding()
    while len(held) > before_segs and time.monotonic() < seg_deadline:
        time.sleep(0.05)
        held = bufpool.outstanding()
    if len(held) > before_segs:
        detail = "\n".join(f"- tag={tag!r} nbytes={n}"
                           for tag, n in held[:20])
        if left:
            warnings.warn(
                f"{request.node.nodeid}: {len(held) - before_segs} "
                f"pooled segment(s) still held at test end:\n{detail}")
        else:
            pytest.fail(
                f"{len(held) - before_segs} pooled buffer segment(s) "
                f"leaked (acquired during this test, never "
                f"released):\n{detail}")

    # span-leak gate: give in-flight ops a short drain window (the
    # thread gate above already quiesced daemon threads)
    def new_spans():
        return [(svc, s) for svc, s in tracing.active_spans()
                if id(s) not in before_spans]

    span_deadline = time.monotonic() + 2.0
    leaked_spans = new_spans()
    while leaked_spans and time.monotonic() < span_deadline:
        time.sleep(0.05)
        leaked_spans = new_spans()
    if leaked_spans:
        detail = "\n".join(
            f"- [{svc}] {s.name} (trace {s.trace_id}, "
            f"open {time.monotonic() - s._t0:.1f}s, "
            f"tags {s.tags})"
            for svc, s in leaked_spans[:20])
        if left:
            # live daemon threads may still finish these (background
            # ops of a shared cluster fixture) — not a proven leak
            warnings.warn(
                f"{request.node.nodeid}: {len(leaked_spans)} span(s) "
                f"still open at test end:\n{detail}")
        else:
            tracing.abandon_all_active()
            pytest.fail(
                f"{len(leaked_spans)} tracing span(s) left "
                f"unfinished at test end with no thread alive to "
                f"finish them:\n{detail}")

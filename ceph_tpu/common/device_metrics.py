"""Device-plane metrics — the accelerator half of the telemetry plane.

The reference's perf counters stop at the syscall boundary; this
framework's hot path crosses another one — host -> XLA device -> host —
and the failure modes on that axis (recompilation storms, HBM
highwater creep, transfer-bound kernels) are invisible to the
OS-level counters.  This module is the process-global accounting the
jitted kernels (``ec.engine``, ``crush.mapper_jax``) book into:

- ``device`` perf logger: h2d/d2h transfer bytes, kernel launch
  count and host dispatch time, live-buffer count/bytes gauges with a
  highwater mark (the DaemonHealthMetrics role for the device plane).
- a per-shape-signature table: dispatch time + transfer volume keyed by
  ``<logger>|<signature>`` — the same shape key XLA's jit cache uses,
  so a new row appearing in steady state IS a recompile (the
  jaxcheck budget gate's observability twin).  Bounded; sampled into
  every daemon's metrics-history ring (common/metrics_history.py).

``sample_memory()`` deliberately never *initializes* a backend: it
reads ``jax.live_arrays()`` only when jax is already imported, so a
monitor daemon that never touches device code pays nothing and never
opens the chip.
"""

from __future__ import annotations

import sys
from typing import Dict, List, Optional

from ..analysis.lockdep import make_lock
from .perf_counters import collection

_pc = collection().create("device")
for _k in ("h2d_bytes", "d2h_bytes", "kernel_launches"):
    _pc.add_u64_counter(_k)
_pc.add_time("kernel_time")
for _k in ("live_buffers", "live_buffer_bytes",
           "live_buffer_bytes_hw"):
    _pc.add_u64(_k)

# <logger>|<signature> -> aggregate launch stats; bounded so a shape
# leak degrades to a truncated table, never unbounded memory
_MAX_SHAPES = 256
_shapes: Dict[str, Dict[str, float]] = {}
_shapes_lock = make_lock("device::shapes")
_buffer_hw = 0

# device id -> aggregate mesh-launch stats: the per-chip half of the
# multichip story.  A pjit launch over an N-device mesh is SPMD — every
# chip runs the program for ~the wall time while holding 1/N of the
# sharded data — so each participating device books the full wall time
# and its 1/N share of the transfer volume.  mesh_device_report joins
# this onto the per-device id/platform/memory rows, which is how the
# multichip bench lane proves real work landed on every chip.
_mesh_devices: Dict[int, Dict[str, float]] = {}


def record_launch(logger: str, sig: object, seconds: float,
                  h2d_bytes: int = 0, d2h_bytes: int = 0) -> None:
    """Book one device-kernel launch: callers pass the bytes they
    moved host->device (inputs) and device->host (materialized
    outputs) alongside the host wall time of the dispatch.  Launches
    are asynchronous and no caller waits for the kernel, so
    ``seconds`` is not device time; a profiler trace gives that."""
    _pc.inc("kernel_launches")
    _pc.tinc("kernel_time", seconds)
    if h2d_bytes:
        _pc.inc("h2d_bytes", h2d_bytes)
    if d2h_bytes:
        _pc.inc("d2h_bytes", d2h_bytes)
    key = f"{logger}|{sig}"
    with _shapes_lock:
        rec = _shapes.get(key)
        if rec is None:
            if len(_shapes) >= _MAX_SHAPES:
                return
            rec = _shapes[key] = {"count": 0, "time_s": 0.0,
                                  "h2d_bytes": 0, "d2h_bytes": 0}
        rec["count"] += 1
        rec["time_s"] += seconds
        rec["h2d_bytes"] += h2d_bytes
        rec["d2h_bytes"] += d2h_bytes


def record_mesh_launch(logger: str, sig: object, seconds: float,
                       device_ids, h2d_bytes: int = 0,
                       d2h_bytes: int = 0) -> None:
    """Book one mesh (pjit) launch: the aggregate booking of
    ``record_launch`` plus a per-device row for every mesh participant,
    so ``mesh_device_report`` shows kernel time on every chip rather
    than one hot device and N-1 idle rows."""
    ids = [int(i) for i in device_ids]
    record_launch(logger, sig, seconds,
                  h2d_bytes=h2d_bytes, d2h_bytes=d2h_bytes)
    n = max(1, len(ids))
    with _shapes_lock:
        for did in ids:
            rec = _mesh_devices.get(did)
            if rec is None:
                rec = _mesh_devices[did] = {
                    "launches": 0, "kernel_time_s": 0.0,
                    "h2d_bytes": 0, "d2h_bytes": 0}
            rec["launches"] += 1
            rec["kernel_time_s"] += seconds
            rec["h2d_bytes"] += h2d_bytes // n
            rec["d2h_bytes"] += d2h_bytes // n


def mesh_device_table() -> Dict[int, Dict[str, float]]:
    """Per-device mesh-launch aggregates (copied)."""
    with _shapes_lock:
        return {k: dict(v) for k, v in _mesh_devices.items()}


def shape_table() -> Dict[str, Dict[str, float]]:
    """Per-shape-signature launch aggregates (copied)."""
    with _shapes_lock:
        return {k: dict(v) for k, v in _shapes.items()}


def sample_memory() -> None:
    """Refresh the live-buffer gauges + highwater.  A no-op unless jax
    is already imported in this process: sampling must never trigger
    backend initialization (which would claim the chip)."""
    global _buffer_hw
    jax = sys.modules.get("jax")
    if jax is None:
        return
    try:
        arrs = jax.live_arrays()
    except Exception:
        return  # backend half-initialized / API moved: skip the sample
    total = 0
    n = 0
    for a in arrs:
        n += 1
        total += int(getattr(a, "nbytes", 0) or 0)
    _pc.set("live_buffers", n)
    _pc.set("live_buffer_bytes", total)
    if total > _buffer_hw:
        _buffer_hw = total
    _pc.set("live_buffer_bytes_hw", _buffer_hw)


def per_device() -> List[Dict]:
    """Per-device breakdown for the multichip lane: id, platform, and
    the backend's memory stats when it exposes them.  INITIALIZES the
    backend — only call from code that already owns device work
    (bench multichip lane, dryrun), never from a sampler."""
    jax = sys.modules.get("jax")
    if jax is None:
        import jax  # noqa: F811 — explicit opt-in to backend init
    out: List[Dict] = []
    for d in jax.devices():
        rec: Dict = {"id": int(d.id), "platform": str(d.platform)}
        try:
            stats = d.memory_stats()
            if stats:
                rec["bytes_in_use"] = int(stats.get("bytes_in_use", 0))
                rec["peak_bytes_in_use"] = int(
                    stats.get("peak_bytes_in_use", 0))
        except Exception:
            pass  # CPU/virtual devices often expose no stats
        out.append(rec)
    return out


def reset_for_tests() -> None:
    global _buffer_hw
    with _shapes_lock:
        _shapes.clear()
        _mesh_devices.clear()
    _buffer_hw = 0

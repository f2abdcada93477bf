"""One measured run of one cell.

Set-up (building the cell from the seed and warming the shapes it
uses) runs first and is timed from process start; then the window runs
for ``--seconds`` on the host's clock, with the profiler on when
``--trace 1``; then the answers the window produced are compared with
the plain reference, and one JSON line is printed.  End-to-end metrics
come from ``--trace 0`` runs, per-layer metrics from ``--trace 1``
runs.
"""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Dict, Optional

from . import spec as S
from . import trace as T
from . import compile_events
from .stats import Window


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell needs."""


@dataclass
class Run:
    """What a metric reader may read about one run."""

    window: Window
    setup_s: float
    device_kind: str
    trace: Optional[T.Trace] = None
    counters: Dict[str, float] = field(default_factory=dict)
    facts: Dict = field(default_factory=dict)


def seed_sequence(seed: int):
    """The seed as numpy's SeedSequence; any whole number is taken."""
    import numpy as np

    return np.random.SeedSequence(seed % (1 << 64))


def enable_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set
    (JAX reads it itself), else the fixed ``.jax_cache`` at the root of
    the checkout, the directory the program's own tools use."""
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = str(S.ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache


def devices_for(cell: S.Cell, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"jax.devices()[0].platform is "
                     f"{devs[0].platform!r}, not 'tpu'")
    if len(devs) < cell.chips:
        raise NoChip(f"the cell needs {cell.chips} chips, JAX sees "
                     f"{len(devs)}")
    return devs[:cell.chips]


def _peak_bytes(devs) -> Optional[int]:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(cell: S.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             window_patch=None) -> Dict:
    """Set up, measure and check one cell; returns the result line.
    ``window_patch``: a context manager factory held open around the
    window only (the controls and faults of ``lib/faults.py``)."""
    devs = devices_for(cell, require_tpu)
    enable_compile_cache()
    clock = compile_events.shared()
    drv = S.generator_class(cell.traffic)(cell.config, cell.traffic, seed,
                                       trace)
    try:
        drv.setup()
        setup_s = time.monotonic() - t_start
        compiled0 = clock.snapshot()
        counters0 = drv.counters()
        tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        print(f"window: opens after {setup_s:.3f} s of set-up",
              file=sys.stderr, flush=True)
        with (window_patch() if window_patch else contextlib.nullcontext()):
            win = _measure(drv, seconds, tdir)
        print("window: closed", file=sys.stderr, flush=True)
        compiled1 = clock.snapshot()
        counters1 = drv.counters()
        memory_peak = _peak_bytes(devs)
        tr = _load_trace(tdir, len(devs)) if tdir else None
        drv.release()
        check = drv.check()
    finally:
        drv.close()
    if compiled1[1] > compiled0[1]:
        print(f"warning: {compiled1[1] - compiled0[1]} programs compiled "
              f"inside the window ({compiled1[0] - compiled0[0]:.3f} s)",
              file=sys.stderr)
    run = Run(window=win, setup_s=setup_s,
              device_kind=devs[0].device_kind, trace=tr,
              counters={k: counters1[k] - counters0.get(k, 0)
                        for k in counters1},
              facts=drv.facts())
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = S.metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    line = {"correct": all(v <= lim for v, lim in check.values()),
            "attempted": win.attempted, "failed": win.failed,
            "metrics": metrics, "device": device}
    if tr is not None and tr.window is not None:
        busy = [T.busy_ns(tr, d) for d in range(len(devs))]
        device["busy_s"] = sum(busy) / len(busy) / 1e9
        device["window_s"] = (tr.window[1] - tr.window[0]) / 1e9
        line["breakdown"] = T.breakdown(tr, 0)
    line["check"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in check.items()}
    return line


def _measure(drv, seconds: float, tdir: Optional[str]) -> Window:
    import jax

    if tdir is None:
        with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
            return drv.window(seconds)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # host spans, no per-call trace
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
            return drv.window(seconds)
    finally:
        jax.profiler.stop_trace()


def _load_trace(tdir: str, chips: int) -> Optional[T.Trace]:
    try:
        for dirpath, _dirs, files in os.walk(tdir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return T.load(os.path.join(dirpath, f), chips)
        return None
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def emit_and_exit(line: Dict) -> None:
    """Each compared number beside its limit as the last lines on
    standard error, then the result as the last line on standard
    output; then the process ends at once.  Whatever the system under
    test may still write to standard error from a thread that outlived
    its shutdown goes to /dev/null from here on, so that the check's
    lines stay the last."""
    sys.stdout.flush()
    sys.stderr.flush()
    err = os.fdopen(os.dup(2), "w")
    null = os.open(os.devnull, os.O_WRONLY)
    os.dup2(null, 2)
    for k, c in line["check"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})", file=err)
    err.flush()
    print(json.dumps(line), flush=True)
    os._exit(0)

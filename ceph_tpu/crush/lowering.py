"""Which lowering a CRUSH rule takes on the device, for every front end.

A rule compiles to a per-lane program ``single(arrays, weight, x) ->
(result i32[R], len i32)`` in one of two lowerings:

- the speculative program (``mapper_spec``), for the rules its
  ``analyze`` accepts: straw2 ``take / choose(leaf) / emit``, and the
  two-step ``choose indep / chooseleaf indep`` of locality-aware EC
  pools, under modern tunables;
- the general rule VM (``mapper_jax``), for every other rule.

:func:`lower_rule` is the one place that picks between them.  The
front ends (``PoolMapper``, :class:`BatchedMapper`,
``parallel.PlacementPlane``) call it and share what else is here: the
``crush.mapper`` counters, the booking of the straggler pass's stats
and of each launch, and the launch sizing.  The two lowerings import
neither this module nor each other.
"""

from __future__ import annotations

import collections
import os
import time
from typing import Callable, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..analysis.lockdep import make_lock
from ..common import device_metrics
from ..common.log import getLogger
from ..common.perf_counters import collection
from .map import ChooseArgMap, CrushMap
from .map_arrays import MapArrays, MapStatic, encode_map
from .mapper_jax import make_single_fn
from .mapper_spec import Ineligible, analyze, make_single_spec, map_stragglers

# process-global batched-mapper metrics (served through every daemon's
# `perf dump`, which merges the global collection): launch count/size,
# steady-state host dispatch time (not device time: launches are
# asynchronous and not waited for), and first-call JIT compile
# count/time kept SEPARATE so compile cost never pollutes the
# steady-state histogram
_pc = collection().create("crush.mapper")
for _k in ("map_calls", "xs_mapped", "jit_compiles"):
    _pc.add_u64_counter(_k)
_pc.add_time("map_time")
_pc.add_time("jit_compile_time")
_pc.add_histogram("map_lat")
# the speculative straggler pass (mapper_spec.map_stragglers): PGs the
# first round left unfinished, and the chunks of N/64 lanes that re-ran
# them with the full retry loops
for _k in ("spec_rerun_pgs", "spec_rerun_chunks"):
    _pc.add_u64_counter(_k)
# rules lowered: onto the speculative program, or the general rule VM;
# and the outer straw2 levels per slot that bounding a one-step rule's
# descent by its target type left out (mapper_spec.Plan.levels_cut)
for _k in ("lowered_spec", "lowered_general", "spec_levels_cut"):
    _pc.add_u64_counter(_k)
_refusals_logged = set()
_rerun_lock = make_lock("crush::reruns")
_rerun_pending = collections.deque()   # stats i32[2] still on the device


def defer_rerun_stats(stats) -> None:
    """Keep a launch's straggler stats (``map_stragglers``' device
    array) to book once they are read: the launch is not waited for."""
    with _rerun_lock:
        _rerun_pending.append(stats)


def book_rerun_stats(wait: bool = False) -> None:
    """Book the kept stats of launches that have finished; with
    ``wait``, of all of them (``perf dump`` waits)."""
    with _rerun_lock:
        ready = []
        while _rerun_pending and (wait or _rerun_pending[0].is_ready()):
            ready.append(_rerun_pending.popleft())
    for stats in ready:
        pgs, chunks = (int(v) for v in np.asarray(stats))  # jax-ok: finished launches only, unless a dump waits
        _pc.inc("spec_rerun_pgs", pgs)
        _pc.inc("spec_rerun_chunks", chunks)


_pc.before_dump(lambda: book_rerun_stats(wait=True))


class Lowering(NamedTuple):
    """One rule's unjitted per-lane program, as :func:`lower_rule`
    picked it.  ``one_round`` is the speculative program's one-round
    variant (``mapper_spec.make_single_spec``), None for the general
    rule VM."""

    single: Callable
    one_round: Optional[Callable]
    static: MapStatic
    arrays_np: MapArrays
    speculative: bool


def lower_rule(cmap: CrushMap, ruleno: int, result_max: int,
               choose_args: Optional[ChooseArgMap] = None,
               encoded=None) -> Lowering:
    """Lower rule ``ruleno`` for ``result_max`` results: onto the
    speculative program where ``mapper_spec.analyze`` accepts it
    (booked as ``lowered_spec``, with its plan's ``levels_cut`` as
    ``spec_levels_cut``), else onto the general rule VM (booked as
    ``lowered_general``; the refusal's reason is logged once per rule
    and reason).  Books on every call and caches nothing."""
    try:
        plan = analyze(cmap, ruleno, result_max)
        single, one_round, static, arrays_np = make_single_spec(
            cmap, ruleno, result_max, choose_args, encoded, k_tries=1)
    except Ineligible as e:
        if (ruleno, str(e)) not in _refusals_logged:
            _refusals_logged.add((ruleno, str(e)))
            # not at import: the first logger made fixes the log
            # core's stream to the sys.stderr of that moment
            getLogger("crush").dout(
                1, f"rule {ruleno} takes the general rule VM: {e}")
        _pc.inc("lowered_general")
        single, static, arrays_np = make_single_fn(
            cmap, ruleno, result_max, choose_args, encoded)
        return Lowering(single, None, static, arrays_np, False)
    _pc.inc("lowered_spec")
    _pc.inc("spec_levels_cut", plan.levels_cut)
    return Lowering(single, one_round, static, arrays_np, True)


def book_map_batch(sig, dt: float, n_xs: int, result_max: int,
                   first_launch: bool, h2d_bytes: int, d2h_bytes: int,
                   device_ids=None) -> None:
    """Shared perf/device-plane booking for one batched-mapper launch
    (the single-device ``BatchedMapper`` and the mesh-sharded
    ``parallel.PlacementPlane`` both land here, so `perf dump` and the
    recompile-budget gate see ONE ``crush.mapper`` story).  ``dt`` is
    the host wall time of the dispatch, not device time.  First-call
    compiles book separately from steady-state dispatch; mesh launches
    additionally book a per-device row for every participating chip."""
    _pc.inc("map_calls")
    _pc.inc("xs_mapped", n_xs)
    if first_launch:
        _pc.inc("jit_compiles")
        _pc.tinc("jit_compile_time", dt)
    else:
        _pc.tinc("map_time", dt)
        _pc.hist_add("map_lat", dt)
    if device_ids:
        device_metrics.record_mesh_launch(
            "crush.mapper", sig, dt, device_ids,
            h2d_bytes=h2d_bytes, d2h_bytes=d2h_bytes)
    else:
        device_metrics.record_launch(
            "crush.mapper", sig, dt,
            h2d_bytes=h2d_bytes, d2h_bytes=d2h_bytes)


def launch_budget_bytes() -> int:
    """Bytes one mapper launch may claim on the default device: half of
    what the backend reports free, or, where it reports nothing (the
    CPU backend), half of the host memory the OS reports available."""
    stats = jax.devices()[0].memory_stats()
    if stats and "bytes_limit" in stats:
        free = stats["bytes_limit"] - stats.get("bytes_in_use", 0)
    else:
        free = os.sysconf("SC_AVPHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    return free // 2


PROBE_LANES = 4096   # lanes of the program that measures the footprint


def footprint_bytes(compiled) -> int:
    """Device bytes a compiled launch claims: its arguments, output and
    scratch, as ``memory_analysis()`` reports them."""
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes +
            ma.temp_size_in_bytes)


def fit_lanes(n: int, compile_at, budget: int) -> int:
    """Lanes per launch for ``n`` inputs within ``budget`` bytes.

    ``compile_at(lanes)`` returns the program compiled for that many
    lanes.  A program too large for the device never compiles, so the
    footprint is read from a probe of ``min(n, PROBE_LANES)`` lanes
    (the launch itself when ``n`` is no larger) and charged to its
    lanes in full: the map arrays' fixed share makes that an upper
    bound per lane (measured for a v5e on map_big10k: the general rule
    VM about 540 KB per lane at any size, the speculative lowering
    5.5 KB at the probe and 2.8 KB at 2^20 lanes).  All ``n`` lanes
    when they fit, else the largest power of two that does."""
    probe = min(n, PROBE_LANES)
    per_lane = footprint_bytes(compile_at(probe)) / probe
    fit = int(budget // per_lane)
    if fit < 1:
        raise MemoryError(f"one lane needs {per_lane:.0f} bytes of a "
                          f"{budget}-byte launch budget")
    return n if fit >= n else 1 << (fit.bit_length() - 1)


class BatchedMapper:
    """User-facing handle: compile-per-rule cache + array residency.

    >>> m = BatchedMapper(cmap)
    >>> res, lens = m.map_batch(ruleno, xs, result_max, weight)

    Each rule takes the lowering :func:`lower_rule` picks; a
    speculative one maps through the straggler pass
    (``mapper_spec.map_stragglers``).

    A batch of any length maps in launches that fit the device: the
    launch size is read from a small compiled probe's
    ``memory_analysis()`` against :func:`launch_budget_bytes`
    (:func:`fit_lanes`).

    ``mesh``: a ``jax.sharding.Mesh`` routes every ``map_batch``
    through the mesh-sharded ``parallel.PlacementPlane`` (PG axis
    data-parallel across the mesh devices, map arrays replicated) —
    same lowering, same results, same booking, one pjit launch over
    all chips.
    """

    def __init__(self, cmap: CrushMap,
                 choose_args: Optional[ChooseArgMap] = None,
                 mesh=None):
        self.cmap = cmap
        self.choose_args = choose_args
        self._cache = {}
        self._lanes = {}   # (rule, result_max, N) -> launch lanes
        self._exe = {}     # (rule, result_max, lanes) -> compiled
        self._encoded = encode_map(cmap, choose_args)
        self._arrays = jax.tree_util.tree_map(
            jnp.asarray, self._encoded[1])
        self._plane = None
        if mesh is not None:
            # deferred import: parallel.placement imports this module
            from ..parallel.placement import PlacementPlane

            self._plane = PlacementPlane(cmap, choose_args=choose_args,
                                         mesh=mesh,
                                         encoded=self._encoded)

    def rule_fn(self, ruleno: int, result_max: int):
        key = (ruleno, result_max)
        if key not in self._cache:
            lo = lower_rule(self.cmap, ruleno, result_max,
                            self.choose_args, encoded=self._encoded)
            if lo.speculative:
                def map_batch(A, weight, xs):
                    return map_stragglers(lo.single, lo.one_round, A,
                                          weight, xs)[:2]

                self._cache[key] = jax.jit(map_batch)
            else:
                self._cache[key] = jax.jit(
                    jax.vmap(lo.single, in_axes=(None, None, 0)))
        return self._cache[key]

    @property
    def arrays(self) -> MapArrays:
        return self._arrays

    def _compiled(self, ruleno: int, result_max: int, lanes: int,
                  weight):
        key = (ruleno, result_max, lanes)
        exe = self._exe.get(key)
        if exe is None:
            t0 = time.monotonic()
            exe = self.rule_fn(ruleno, result_max).lower(
                self._arrays, weight,
                jax.ShapeDtypeStruct((lanes,), jnp.uint32)).compile()
            _pc.inc("jit_compiles")
            _pc.tinc("jit_compile_time", time.monotonic() - t0)
            self._exe[key] = exe
        return exe

    def _launch_lanes(self, ruleno: int, result_max: int, n: int,
                      weight) -> int:
        key = (ruleno, result_max, n)
        if key not in self._lanes:
            self._lanes[key] = fit_lanes(
                n, lambda lanes: self._compiled(ruleno, result_max,
                                                lanes, weight),
                launch_budget_bytes())
        return self._lanes[key]

    def map_batch(self, ruleno: int, xs, result_max: int, weight):
        """Map a batch: xs uint32[N], weight 16.16 uint32[max_devices]."""
        if self._plane is not None:
            return self._plane.map_batch(ruleno, xs, result_max, weight)
        xs = np.asarray(xs, np.uint32)
        n = int(xs.shape[0])
        if n == 0:
            return (jnp.zeros((0, result_max), jnp.int32),
                    jnp.zeros((0,), jnp.int32))
        weight = jnp.asarray(np.asarray(weight, np.uint32))
        lanes = self._launch_lanes(ruleno, result_max, n, weight)
        exe = self._compiled(ruleno, result_max, lanes, weight)
        res, lens = [], []
        for lo in range(0, n, lanes):
            chunk = xs[lo:lo + lanes]
            if len(chunk) < lanes:   # the tail pads with x=0 lanes
                chunk = np.pad(chunk, (0, lanes - len(chunk)))
            t0 = time.monotonic()
            r, ln = exe(self._arrays, weight, jnp.asarray(chunk))
            # device plane: xs + weight cross host->device, the result
            # block (results + lens, i32) crosses back when consumed
            book_map_batch((ruleno, result_max, (lanes,)),
                           time.monotonic() - t0, lanes, result_max,
                           False, h2d_bytes=lanes * 4 +
                           int(weight.size) * 4,
                           d2h_bytes=lanes * (result_max + 1) * 4)
            res.append(r)
            lens.append(ln)
        if len(res) == 1 and lanes == n:
            return res[0], lens[0]
        return (jnp.concatenate(res)[:n], jnp.concatenate(lens)[:n])

"""Multi-chip placement + EC step — the framework's distribution layer.

The reference scales by sharding PGs across OSD processes and fanning
EC chunks across shard OSDs over its AsyncMessenger TCP fabric
(src/osd/OSDMapMapping.h:18 thread-pool PG batching;
src/osd/ECBackend.cc:934 chunk fan-out; src/msg/async/* transport).
The TPU-native re-expression (SURVEY §2.6): the PG axis is data-parallel
over the device mesh, the EC stripe batch axis is data-parallel too,
and all cross-chip movement is XLA collectives over ICI — an
all-reduce for cluster-wide utilization tallies, an all-gather when the
full placement table must be host-visible.  No NCCL/MPI translation; the
mesh + shardings ARE the communication backend.

``PlacementPlane`` is the production entry (the DrJAX-style map-reduce
decomposition, arXiv:2403.07128, over the t5x mesh idiom): one pjit
launch maps millions of PGs across every chip, with

- the map arrays and weight vector REPLICATED (they are the cluster
  map — every chip holds it, exactly as every OSD/client holds the
  OSDMap),
- the PG axis sharded ``NamedSharding(mesh, P("pg"))``,
- utilization tallies all-reduced back to every chip,
- pow2-padded batch shapes so the compile-signature set stays inside
  the jaxcheck recompile budget, and pad lanes masked out of the
  tally (pad-and-mask covers batches not divisible by the mesh),
- a single-device mesh as the degenerate case: the same code path,
  no fork on CPU CI.
"""

from __future__ import annotations

import contextlib
import time
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..crush.map import ChooseArgMap, CrushMap
from ..crush.map_arrays import encode_map
from ..crush.lowering import book_map_batch, lower_rule
from .meshctx import pad_batch  # noqa: F401  (re-export; see meshctx)
from . import meshctx


def make_mesh(devices: Optional[Sequence] = None,
              axis_name: str = "pg") -> Mesh:
    """A 1-D mesh over the PG (data) axis — the framework's default
    topology, matching how the reference shards everything by PG."""
    devices = list(devices if devices is not None else jax.devices())
    return Mesh(np.asarray(devices), (axis_name,))


# -- process-default data-plane mesh ----------------------------------------
#
# The EC engine and the OSD-side EncodeBatcher pick this up when no
# explicit mesh is threaded through (the OSD data path has no natural
# place to carry a Mesh handle): install once at daemon/bench startup,
# every batched encode shards its stripe axis from then on.  None (the
# default) means unsharded — CPU CI and single-chip hosts never fork.
# The holder lives in dependency-free ``meshctx`` so the EC engine can
# read it without importing this module's CRUSH dependencies.

def set_data_plane_mesh(mesh: Optional[Mesh]) -> None:
    """Install (or clear, with None) the process-default mesh the EC
    batched-encode paths shard over."""
    meshctx.set_mesh(mesh)


def data_plane_mesh() -> Optional[Mesh]:
    return meshctx.get_mesh()


@contextlib.contextmanager
def data_plane(mesh: Optional[Mesh]):
    """Scoped ``set_data_plane_mesh`` for tests and bench stages."""
    prev = meshctx.get_mesh()
    set_data_plane_mesh(mesh)
    try:
        yield mesh
    finally:
        set_data_plane_mesh(prev)


def utilization(results, lens, max_devices: int):
    """Per-OSD placement tallies — the CrushTester stats pass
    (src/crush/CrushTester.cc:588-648) as one scatter-add."""
    R = results.shape[-1]
    pos = jnp.arange(R, dtype=jnp.int32)
    valid = (pos[None, :] < lens[:, None]) & (results >= 0) \
        & (results < max_devices)
    flat = jnp.where(valid, results, max_devices)
    counts = jnp.zeros(max_devices + 1, jnp.int32).at[flat].add(1)
    return counts[:max_devices]


def sharded_rule_fn(cmap: CrushMap, ruleno: int, result_max: int,
                    mesh: Mesh, axis_name: str = "pg",
                    choose_args: Optional[ChooseArgMap] = None,
                    gather_stats: bool = True, masked: bool = False,
                    encoded=None):
    """Compile the batched mapper sharded over ``mesh`` — the engine
    behind ``PlacementPlane``.

    The rule takes the lowering ``lowering.lower_rule`` picks, vmapped
    with its plain retry loops: the straggler pass gathers across the
    PG axis, which the mesh shards.

    Returns ``fn(arrays, weight, xs)`` (or ``fn(arrays, weight, xs,
    valid)`` when ``masked``) where ``xs`` is sharded on the PG axis,
    the map arrays and weight vector are replicated, results stay
    PG-sharded, and the utilization tally is all-reduced to every
    chip.  ``masked`` adds a per-lane validity mask (sharded like
    ``xs``) that zeroes pad lanes out of the tally — the pad-and-mask
    half of the pow2 padding story.
    """
    lo = lower_rule(cmap, ruleno, result_max, choose_args,
                    encoded=encoded)
    fn = jax.vmap(lo.single, in_axes=(None, None, 0))
    repl = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P(axis_name))

    if masked:
        def step(A, weight, xs, valid):
            res, lens = fn(A, weight, xs)
            if gather_stats:
                counts = utilization(
                    res, jnp.where(valid, lens, 0),
                    lo.static.max_devices)
                return res, lens, counts
            return res, lens

        in_sh = (repl, repl, shard, shard)
    else:
        def step(A, weight, xs):
            res, lens = fn(A, weight, xs)
            if gather_stats:
                counts = utilization(res, lens, lo.static.max_devices)
                return res, lens, counts
            return res, lens

        in_sh = (repl, repl, shard)

    out_sh = (shard, shard, repl) if gather_stats else (shard, shard)
    sharded = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
    return sharded, lo.static, lo.arrays_np


class PlacementPlane:
    """The mesh-sharded CRUSH distribution layer: compile-per-rule
    cache + replicated map-array residency over an N-device mesh.

    >>> plane = PlacementPlane(cmap)            # mesh = all devices
    >>> res, lens = plane.map_batch(0, xs, 3, weight)
    >>> res, lens, counts = plane.map_batch(0, xs, 3, weight,
    ...                                     gather_stats=True)

    One ``map_batch`` is ONE pjit launch: the xs batch is pow2-padded
    (bounded compile signatures) and sharded across the mesh, every
    chip maps its shard against the replicated map, and — with
    ``gather_stats`` — the per-OSD utilization tally is all-reduced so
    every chip (and the host) holds cluster-wide counts.  Works
    unchanged on a 1-device mesh and on batches not divisible by the
    mesh size (pad lanes are masked out of the tally and sliced off
    the results).
    """

    def __init__(self, cmap: CrushMap,
                 choose_args: Optional[ChooseArgMap] = None,
                 mesh: Optional[Mesh] = None, axis_name: str = "pg",
                 encoded=None):
        self.cmap = cmap
        self.choose_args = choose_args
        self.mesh = mesh if mesh is not None else make_mesh(
            axis_name=axis_name)
        self.axis_name = axis_name if axis_name in \
            self.mesh.axis_names else self.mesh.axis_names[0]
        self.n_dev = int(np.asarray(self.mesh.devices).size)
        self._device_ids = [
            int(d.id) for d in np.asarray(self.mesh.devices).ravel()]  # mesh.devices is a host-side numpy array of Device handles
        self._repl = NamedSharding(self.mesh, P())
        self._shard = NamedSharding(self.mesh, P(self.axis_name))
        self._encoded = encoded if encoded is not None \
            else encode_map(cmap, choose_args)
        self._arrays = jax.device_put(
            jax.tree_util.tree_map(jnp.asarray, self._encoded[1]),
            self._repl)
        self._cache = {}            # (rule, R, gather) -> (fn, static)
        self._compiled_sigs: set = set()

    @property
    def static(self):
        return self._encoded[0]

    @property
    def arrays(self):
        return self._arrays

    def rule_fn(self, ruleno: int, result_max: int,
                gather_stats: bool = False):
        key = (ruleno, result_max, bool(gather_stats))
        if key not in self._cache:
            fn, static, _ = sharded_rule_fn(
                self.cmap, ruleno, result_max, self.mesh,
                axis_name=self.axis_name,
                choose_args=self.choose_args,
                gather_stats=gather_stats, masked=True,
                encoded=self._encoded)
            self._cache[key] = (fn, static)
        return self._cache[key][0]

    def map_batch(self, ruleno: int, xs, result_max: int, weight,
                  gather_stats: bool = False):
        """Map a batch across the mesh: xs uint32[N], weight 16.16
        uint32[max_devices].  Returns ``(results i32[N, R], lens
        i32[N])`` plus the all-reduced ``counts i32[max_devices]``
        when ``gather_stats``.

        When N is already padded (pow2, mesh-divisible) the outputs
        stay device-resident and sharded — the hot loop never syncs;
        otherwise pad lanes are sliced off host-side.
        """
        xs_np = np.asarray(xs, np.uint32)  # jax-ok: host-side batch normalization before the sharded upload
        n = int(xs_np.shape[0])
        npad = pad_batch(n, self.n_dev)
        fn = self.rule_fn(ruleno, result_max, gather_stats)
        if npad != n:
            pad = np.zeros(npad, np.uint32)
            pad[:n] = xs_np
            xs_np = pad
        valid_np = np.zeros(npad, np.bool_)
        valid_np[:n] = True
        w_dev = jax.device_put(
            jnp.asarray(np.asarray(weight, np.uint32)), self._repl)  # jax-ok: host-side weight normalization before the replicated upload
        xs_dev = jax.device_put(jnp.asarray(xs_np), self._shard)
        valid = jax.device_put(jnp.asarray(valid_np), self._shard)

        t0 = time.monotonic()
        out = fn(self._arrays, w_dev, xs_dev, valid)
        dt = time.monotonic() - t0
        sig = (ruleno, result_max, npad, self.n_dev,
               bool(gather_stats))
        first = sig not in self._compiled_sigs
        if first:
            self._compiled_sigs.add(sig)
        book_map_batch(
            sig, dt, n, result_max, first,
            h2d_bytes=npad * 5 + int(np.asarray(weight).size) * 4,  # jax-ok: sizing arithmetic on the host-side weight input
            d2h_bytes=npad * (result_max + 1) * 4,
            device_ids=self._device_ids)

        if gather_stats:
            res, lens, counts = out
        else:
            res, lens = out
        if npad != n:
            # pad-and-mask fallback: correctness path, not the hot
            # loop — slice host-side so no per-n slice programs pile
            # up in the jit cache
            res = np.asarray(res)[:n]  # jax-ok: deliberate egress on the padded (cold) path only
            lens = np.asarray(lens)[:n]  # jax-ok: deliberate egress on the padded (cold) path only
        if gather_stats:
            return res, lens, counts
        return res, lens


def mesh_device_report(mesh: Mesh):
    """Per-device breakdown for the multichip lane's telemetry: one
    row per mesh device (id, platform, backend memory stats where the
    PJRT client exposes them, and — once mesh launches have run —
    per-device kernel launches/time/transfer volume) — the
    observability ROADMAP item 1's near-linear-scaling claim is
    judged against this.  Safe here: the caller already owns an
    initialized mesh, so no backend-init risk."""
    from ..common import device_metrics

    by_id = {d["id"]: d for d in device_metrics.per_device()}
    work = device_metrics.mesh_device_table()
    out = []
    for d in np.asarray(mesh.devices).ravel():  # jax-ok: mesh.devices is a host-side numpy array of Device handles, not device data
        rec = by_id.get(int(d.id), {"id": int(d.id),
                                    "platform": str(d.platform)})
        w = work.get(int(d.id))
        if w:
            rec = dict(rec)
            rec["kernel_launches"] = int(w["launches"])
            rec["kernel_time_s"] = round(float(w["kernel_time_s"]), 6)  # jax-ok: host-side dict value, not a device scalar
            rec["h2d_bytes"] = int(w["h2d_bytes"])
            rec["d2h_bytes"] = int(w["d2h_bytes"])
        out.append(rec)
    return out

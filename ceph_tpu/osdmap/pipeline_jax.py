"""The fused batched placement pipeline — map every PG in one launch.

One jitted XLA program for the full OSDMap chain (OSDMap.cc:2665
_pg_to_up_acting_osds): pps seed → CRUSH → nonexistent-filter → upmap →
up-filter → primary affinity → pg_temp overlay.  The reference runs this
per-PG on CPU and batches with a thread pool (ParallelPGMapper,
src/osd/OSDMapMapping.h:18); here the PG axis is the vmapped batch axis
and shards across the TPU mesh.  Unsharded, a speculative rule's CRUSH
step runs one retry round over every PG and its full retry loops over
the PGs that need more (``mapper_spec.map_stragglers``).

Exception tables (pg_upmap/pg_upmap_items/pg_temp/primary_temp) are
lowered host-side to dense per-PG arrays; stages that no PG uses are
statically compiled out.  OSD weights/states/affinities stay runtime
arrays: mark-out and reweight re-run without recompiling — the property
the balancer loop (OSDMap.cc:4618 calc_pg_upmaps) needs.  Upmap/temp
edits go through ``PoolMapper.refresh_tables()``: a cheap host relower
when the same stages stay active, a rebuild when a stage appears.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..crush import hash as H
from ..crush.constants import CRUSH_ITEM_NONE as NONE
from ..crush.lowering import book_rerun_stats, defer_rerun_stats, lower_rule
from ..crush.mapper_spec import map_stragglers
from .osdmap import (DEFAULT_PRIMARY_AFFINITY, FLAG_HASHPSPOOL,
                     MAX_PRIMARY_AFFINITY, OSD_EXISTS, OSD_UP, OSDMap,
                     PgPool)

I32 = jnp.int32
U32 = jnp.uint32


def _stable_mod(x, b: int, bmask: int):
    lo = x & jnp.uint32(bmask)
    return jnp.where(lo < b, lo, x & jnp.uint32(bmask >> 1))


def _compact(row, keep, rlen, R: int):
    """Stable left-compaction of kept entries (can_shift pools); drops
    the rest, pads with NONE.  Returns (row, new_len)."""
    idx = jnp.arange(R, dtype=I32)
    keep = keep & (idx < rlen)
    order = jnp.argsort(jnp.where(keep, idx, idx + R))
    newlen = jnp.sum(keep.astype(I32))
    return jnp.where(idx < newlen, row[order], NONE), newlen


def _mask_none(row, keep, rlen, R: int):
    """Positional pools: non-kept entries become NONE, length kept."""
    idx = jnp.arange(R, dtype=I32)
    return jnp.where(idx < rlen, jnp.where(keep, row, NONE), NONE), rlen


@dataclass
class _DenseTables:
    """Host-lowered exception tables, one row per raw ps."""

    upmap: Optional[np.ndarray]        # i32[pg, R]
    upmap_len: Optional[np.ndarray]    # i32[pg]  (-1 = no entry)
    pairs: Optional[np.ndarray]        # i32[pg, P, 2]
    npairs: Optional[np.ndarray]       # i32[pg]
    temp: Optional[np.ndarray]         # i32[pg, T]
    temp_len: Optional[np.ndarray]     # i32[pg]  (-1 = no entry)
    ptemp: Optional[np.ndarray]        # i32[pg]  (-1 = no entry)


def _lower_tables(m: OSDMap, pool_id: int, pool: PgPool) -> _DenseTables:
    n = pool.pg_num
    R = pool.size

    def rows(table, name, maxw=None):
        # entries with ps >= pg_num are unreachable in the scalar path
        # (lookups go through raw_pg_to_ps < pg_num); drop them here too
        out = {ps: v for (pid, ps), v in table.items()
               if pid == pool_id and ps < n}
        if maxw is not None:
            for ps, v in out.items():
                if len(v) > maxw:
                    raise ValueError(
                        f"{name}[{pool_id}.{ps}] has {len(v)} entries, "
                        f"more than pool size {maxw}; the reference "
                        f"monitor rejects such mappings and the batched "
                        f"pipeline's fixed result width cannot hold them")
        return out

    up = rows(m.pg_upmap, "pg_upmap", R)
    items = rows(m.pg_upmap_items, "pg_upmap_items")
    temps = rows(m.pg_temp, "pg_temp", R)
    ptemps = rows(m.primary_temp, "primary_temp")

    t = _DenseTables(None, None, None, None, None, None, None)
    if up:
        W = R
        t.upmap = np.full((n, W), NONE, np.int32)
        t.upmap_len = np.full(n, -1, np.int32)
        for ps, v in up.items():
            t.upmap[ps, :len(v)] = v
            t.upmap_len[ps] = len(v)
    if items:
        P = max(len(v) for v in items.values())
        t.pairs = np.zeros((n, P, 2), np.int32)
        t.npairs = np.zeros(n, np.int32)
        for ps, v in items.items():
            for j, (a, b) in enumerate(v):
                t.pairs[ps, j] = (a, b)
            t.npairs[ps] = len(v)
    if temps:
        T = R
        t.temp = np.full((n, T), NONE, np.int32)
        t.temp_len = np.full(n, -1, np.int32)
        for ps, v in temps.items():
            t.temp[ps, :len(v)] = v
            t.temp_len[ps] = len(v)
    if ptemps:
        t.ptemp = np.full(n, -1, np.int32)
        for ps, v in ptemps.items():
            t.ptemp[ps] = v
    return t


class PoolMapper:
    """Compiled batched ``pg_to_up_acting`` for one pool.

    >>> pm = PoolMapper(osdmap, pool_id)
    >>> out = pm.map_all()   # dict of arrays over every PG

    ``mesh``: a ``jax.sharding.Mesh`` shards the PG axis (ps, every
    per-PG exception-table row, and every output) across the mesh
    devices — ``map_all`` becomes one pjit launch over all chips, with
    the OSDMap runtime vectors replicated.  The PG count is pow2-
    padded to a mesh multiple (pad lanes carry inactive table rows and
    are sliced off), so non-divisible pools never fork and the compile
    signature set stays bounded.
    """

    def __init__(self, m: OSDMap, pool_id: int, mesh=None):
        self.m = m
        self.pool_id = pool_id
        self.mesh = mesh
        pool = m.pools[pool_id]
        self.pool = pool
        R = pool.size
        D = max(m.max_osd, 1)
        self.R, self.D = R, D
        shift = pool.can_shift_osds()

        cargs = m.crush.choose_args.get(pool_id)
        if pool.crush_rule in m.crush.rules:
            lo = lower_rule(m.crush, pool.crush_rule, R,
                            choose_args=cargs)
            single, one_round = lo.single, lo.one_round
            self.arrays = jax.tree_util.tree_map(jnp.asarray,
                                                 lo.arrays_np)
        else:
            single = one_round = None
            self.arrays = None
        # the straggler pass gathers across the PG axis, which a mesh
        # shards: meshed mappers keep the plain vmapped loops
        if mesh is not None:
            one_round = None

        tabs = _lower_tables(m, pool_id, pool)
        self.tabs = tabs
        has_aff = m.osd_primary_affinity is not None
        pgp, pgp_mask = pool.pgp_num, pool.pgp_num_mask
        hashpspool = bool(pool.flags & FLAG_HASHPSPOOL)
        pid_u32 = pool_id & 0xFFFFFFFF

        def seed(ps):
            mm = _stable_mod(ps, pgp, pgp_mask)
            if hashpspool:
                return H.crush_hash32_2(mm, jnp.uint32(pid_u32))
            return mm + jnp.uint32(pid_u32)

        idx = jnp.arange(R, dtype=I32)

        def osd_ok(osd, exists_up):
            """exists/up lookup with range guard; returns (exists, up)."""
            inr = (osd >= 0) & (osd < D)
            st = exists_up[jnp.clip(osd, 0, D - 1)]
            ex = inr & ((st & OSD_EXISTS) != 0)
            upb = inr & ((st & OSD_UP) != 0)
            return ex, upb

        def after_crush(weight, state, paff, trow, pps, raw, rlen):
            # _remove_nonexistent_osds (OSDMap.cc:2408)
            ex, upb = osd_ok(raw, state)
            if shift:
                raw, rlen = _compact(raw, ex, rlen, R)
            else:
                raw, rlen = _mask_none(raw, ex, rlen, R)

            # _apply_upmap (OSDMap.cc:2463)
            upmap_rejected = jnp.bool_(False)
            if tabs.upmap is not None:
                urow, ulen = trow["upmap"], trow["upmap_len"]
                uvalid = (urow != NONE) & (urow >= 0) & (urow < D)
                marked_out = uvalid & \
                    (weight[jnp.clip(urow, 0, D - 1)] == 0) & \
                    (idx < ulen)
                # a marked-out target rejects the whole exception entry
                # AND skips pg_upmap_items for this PG (OSDMap.cc:2472)
                upmap_rejected = (ulen >= 0) & jnp.any(marked_out)
                use = (ulen >= 0) & ~upmap_rejected
                raw = jnp.where(use,
                                jnp.where(idx < ulen, urow, NONE), raw)
                rlen = jnp.where(use, ulen, rlen)
            if tabs.pairs is not None:
                pr, npair = trow["pairs"], trow["npairs"]
                # width from the traced row, not the closure: stays
                # correct when refresh_tables retraces with more pairs
                P = pr.shape[0]
                for p in range(P):
                    frm, to = pr[p, 0], pr[p, 1]
                    active = p < npair
                    in_seg = idx < rlen
                    has_to = jnp.any(in_seg & (raw == to))
                    to_out = (to != NONE) & (to >= 0) & (to < D) & \
                        (weight[jnp.clip(to, 0, D - 1)] == 0)
                    cand = in_seg & (raw == frm) & ~to_out
                    pos = jnp.argmax(cand)
                    do = active & ~has_to & jnp.any(cand) \
                        & ~upmap_rejected
                    raw = jnp.where(
                        do, raw.at[pos].set(to), raw)

            # _raw_to_up_osds (OSDMap.cc:2510)
            ex, upb = osd_ok(raw, state)
            keep = ex & upb
            if shift:
                up, ulen2 = _compact(raw, keep, rlen, R)
            else:
                up, ulen2 = _mask_none(raw, keep, rlen, R)

            # _pick_primary (OSDMap.cc:2452)
            valid = (idx < ulen2) & (up != NONE)
            first = jnp.argmax(valid)
            up_primary = jnp.where(jnp.any(valid), up[first], -1)

            # _apply_primary_affinity (OSDMap.cc:2535)
            if has_aff:
                a = paff[jnp.clip(up, 0, D - 1)]
                nondefault = valid & (a != DEFAULT_PRIMARY_AFFINITY)
                h = H.crush_hash32_2(pps, _u32i(up)) >> jnp.uint32(16)
                rejected = valid & (a < MAX_PRIMARY_AFFINITY) & (h >= a)
                accept = valid & ~rejected
                pos = jnp.where(jnp.any(accept), jnp.argmax(accept),
                                jnp.where(jnp.any(valid),
                                          jnp.argmax(valid), -1))
                engage = jnp.any(nondefault) & (pos >= 0)
                posc = jnp.clip(pos, 0, R - 1)
                new_primary = jnp.where(engage, up[posc], up_primary)
                if shift:
                    rolled = jnp.where(idx == 0, up[posc],
                                       jnp.where(idx <= posc,
                                                 up[jnp.clip(idx - 1, 0,
                                                             R - 1)],
                                                 up))
                    up = jnp.where(engage & (posc > 0), rolled, up)
                up_primary = new_primary

            # _get_temp_osds overlay (OSDMap.cc:2590)
            acting, alen = up, ulen2
            acting_primary = up_primary
            if tabs.temp is not None:
                trow_t, tlen = trow["temp"], trow["temp_len"]
                tex, tup = osd_ok(trow_t, state)
                tkeep = tex & tup
                if shift:
                    ft, flen = _compact(trow_t, tkeep,
                                        jnp.maximum(tlen, 0), R)
                else:
                    ft, flen = _mask_none(trow_t, tkeep,
                                          jnp.maximum(tlen, 0), R)
                use_t = (tlen >= 0) & (flen > 0)
                tvalid = (idx < flen) & (ft != NONE)
                tprim = jnp.where(jnp.any(tvalid),
                                  ft[jnp.argmax(tvalid)], -1)
                acting = jnp.where(use_t, ft, acting)
                alen = jnp.where(use_t, flen, alen)
                acting_primary = jnp.where(use_t, tprim, acting_primary)
            if tabs.ptemp is not None:
                pt = trow["ptemp"]
                acting_primary = jnp.where(pt != -1, pt, acting_primary)

            return (up, ulen2, up_primary, acting, alen, acting_primary)

        # per-pg table rows
        self._trow = {}
        if tabs.upmap is not None:
            self._trow["upmap"] = jnp.asarray(tabs.upmap)
            self._trow["upmap_len"] = jnp.asarray(tabs.upmap_len)
        if tabs.pairs is not None:
            self._trow["pairs"] = jnp.asarray(tabs.pairs)
            self._trow["npairs"] = jnp.asarray(tabs.npairs)
        if tabs.temp is not None:
            self._trow["temp"] = jnp.asarray(tabs.temp)
            self._trow["temp_len"] = jnp.asarray(tabs.temp_len)
        if tabs.ptemp is not None:
            self._trow["ptemp"] = jnp.asarray(tabs.ptemp)
        trow_axes = {k: 0 for k in self._trow}
        lanes = jax.vmap(after_crush,
                         in_axes=(None, None, None, trow_axes, 0, 0, 0))

        def single_pg(A, weight, state, paff, trow, ps):
            """Every PG of the batch ``ps``: CRUSH, then the OSDMap
            stages.  Where the speculative rule maps through the
            straggler pass, its stats are a seventh output."""
            pps = seed(ps)
            stats = None
            if one_round is not None:
                raw, rlen, stats = map_stragglers(single, one_round, A,
                                                  weight, pps)
            elif single is not None:
                raw, rlen = jax.vmap(single, in_axes=(None, None, 0))(
                    A, weight, pps)
            else:
                raw = jnp.full(ps.shape + (R,), NONE, I32)
                rlen = jnp.zeros(ps.shape, I32)
            out = lanes(weight, state, paff, trow, pps, raw, rlen)
            return out if stats is None else out + (stats,)

        if mesh is None:
            self.fn = jax.jit(single_pg)
            self._npad = None
        else:
            from jax.sharding import NamedSharding, PartitionSpec

            from ..parallel.meshctx import pad_batch

            repl = NamedSharding(mesh, PartitionSpec())
            shard = NamedSharding(mesh,
                                  PartitionSpec(mesh.axis_names[0]))
            self.fn = jax.jit(
                single_pg,
                in_shardings=(repl, repl, repl, repl,
                              {k: shard for k in self._trow}, shard),
                out_shardings=(shard,) * 6)
            self._npad = pad_batch(
                pool.pg_num, int(np.asarray(mesh.devices).size))
            self._pad_trow()

    def _pad_trow(self):
        """Extend every per-PG table row to the padded PG count with
        INACTIVE entries (len fields -1, npairs 0, ptemp -1, row
        contents NONE) — pad lanes execute the same program but engage
        no exception stage, and their outputs are sliced off."""
        npad = self._npad
        inactive = {"upmap_len": -1, "npairs": 0, "temp_len": -1,
                    "ptemp": -1}
        for k, v in list(self._trow.items()):
            n = int(v.shape[0])
            if n >= npad:
                continue
            fill = inactive.get(k, NONE)
            pad_shape = (npad - n,) + tuple(v.shape[1:])
            pad = jnp.full(pad_shape, fill, v.dtype)
            self._trow[k] = jnp.concatenate([v, pad], axis=0)

    def refresh_tables(self):
        """Re-lower the exception tables after upmap/pg_temp edits.

        Cheap when the set of active stages is unchanged (host relower,
        same compiled program; pair-count shape changes just retrace);
        rebuilds the whole mapper when a stage appears or disappears
        (its code was statically compiled in/out)."""
        tabs = _lower_tables(self.m, self.pool_id, self.pool)
        same = all(
            (getattr(tabs, f) is None) == (getattr(self.tabs, f) is None)
            for f in ("upmap", "pairs", "temp", "ptemp"))
        if not same:
            self.__init__(self.m, self.pool_id, self.mesh)
            return
        self.tabs = tabs
        for k, v in (("upmap", tabs.upmap), ("upmap_len", tabs.upmap_len),
                     ("pairs", tabs.pairs), ("npairs", tabs.npairs),
                     ("temp", tabs.temp), ("temp_len", tabs.temp_len),
                     ("ptemp", tabs.ptemp)):
            if v is not None:
                self._trow[k] = jnp.asarray(v)
        if self._npad is not None:
            self._pad_trow()

    def runtime_args(self):
        m = self.m
        weight = jnp.asarray(np.asarray(m.osd_weight, np.uint32))
        state = jnp.asarray(np.asarray(m.osd_state, np.int32))
        paff = jnp.asarray(np.asarray(
            m.osd_primary_affinity
            if m.osd_primary_affinity is not None
            else [DEFAULT_PRIMARY_AFFINITY] * m.max_osd, np.uint32))
        return weight, state, paff

    def map_all(self, weight=None, state=None, paff=None):
        """Map every PG of the pool.  Returns dict of device arrays:
        up[pg,R], up_len[pg], up_primary[pg], acting*, ...

        On a meshed mapper the launch runs over the padded PG axis
        sharded across the chips; pad lanes are sliced off host-side
        before return."""
        w0, s0, p0 = self.runtime_args()
        weight = w0 if weight is None else jnp.asarray(weight)
        state = s0 if state is None else jnp.asarray(state)
        paff = p0 if paff is None else jnp.asarray(paff)
        n = self.pool.pg_num
        ps = jnp.arange(self._npad or n, dtype=jnp.uint32)
        book_rerun_stats()
        up, ulen, uprim, acting, alen, aprim, *stats = self.fn(
            self.arrays, weight, state, paff, self._trow, ps)
        if stats:
            defer_rerun_stats(stats[0])
        out = {"up": up, "up_len": ulen, "up_primary": uprim,
               "acting": acting, "acting_len": alen,
               "acting_primary": aprim}
        if self._npad is not None and self._npad != n:
            out = {k: np.asarray(v)[:n] for k, v in out.items()}
        return out


def _u32i(v):
    return v.astype(jnp.uint32)

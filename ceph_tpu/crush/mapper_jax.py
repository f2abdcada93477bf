"""crush_do_rule_batched — the general rule VM, vmapped on the TPU.

This is the framework's replacement for the reference's scalar map-one-x-at-
a-time core (crush_do_rule, src/crush/mapper.c:878) *and* its thread-pool
batching shim (ParallelPGMapper, src/osd/OSDMapMapping.h:18): one jitted XLA
program maps an entire batch of inputs (PGs) in a single launch.  It takes
every rule; ``lowering.lower_rule`` gives it the ones the speculative
program (``mapper_spec``) refuses.

Bit-exactness contract: identical outputs to the scalar executable spec in
``mapper_ref.py`` (itself golden-tested against the reference C core) for
every map/rule/tunable combination, including the data-dependent retry
descents.  The reformulation:

- ``crush_choose_firstn``'s collision/reject retry descent
  (mapper.c:438-626) becomes a bounded ``lax.while_loop`` whose carried
  state is (current bucket, flocal, ftotal, outcome); one loop iteration is
  one *attempt* (a descend step, a retry, or a terminal outcome), so the
  loop is exactly the C control flow with the gotos flattened.
- ``crush_choose_indep`` (mapper.c:633-821) keeps its breadth-first
  rounds: a while-loop over ftotal < tries, a static unroll over result
  positions, an inner descent while-loop.
- bucket choose methods (mapper.c:51-396) are vectorized over the padded
  item axis: straw2 = masked argmax over fixed-point draws; list = masked
  last-index-satisfying scan; tree = log-depth descent loop; uniform =
  Fisher-Yates permutation state carried functionally.
- the rule VM (mapper.c:923-1080) is unrolled at trace time: rules and
  tunables are static, so each (map-shape, rule, result_max) pair compiles
  to a straight-line XLA program; weights/items/choose_args stay runtime
  arrays so the balancer's mutate-remap loop never recompiles.
- ``vmap`` over x provides the batch axis (the PG/object axis); sharding
  that axis over a device mesh is the job of ``ceph_tpu.parallel``.
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

import jax

# HARD REQUIREMENT: the straw2 draw is 64-bit fixed-point arithmetic
# (crush_ln in (0, 2^48], div64_s64 by 16.16 weights — mapper.c:312-337);
# without real int64 every mapping silently diverges from the reference.
# Enabling x64 is process-global; hosts embedding this library get 64-bit
# jnp defaults from this point on (ln.py refuses to run otherwise).
if not jax.config.jax_enable_x64:
    jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from . import constants as C  # noqa: E402
from . import hash as H  # noqa: E402
from .ln import (LL_NP, RH_LH_NP, ln16_table, recip64,  # noqa: E402
                 straw2_draw, straw2_key)
from .map import ChooseArgMap, CrushMap  # noqa: E402
from .map_arrays import MapStatic, encode_map  # noqa: E402

I32 = jnp.int32
U32 = jnp.uint32
I64 = jnp.int64
UNDEF = C.CRUSH_ITEM_UNDEF
NONE = C.CRUSH_ITEM_NONE


def _u32(v):
    return v.astype(U32) if hasattr(v, "astype") else jnp.uint32(v)


def _h2(hash_type, a, b):
    h = H.crush_hash32_2(_u32(a), _u32(b))
    return jnp.where(hash_type == C.CRUSH_HASH_RJENKINS1, h, jnp.uint32(0))


def _h3(hash_type, a, b, c):
    h = H.crush_hash32_3(_u32(a), _u32(b), _u32(c))
    return jnp.where(hash_type == C.CRUSH_HASH_RJENKINS1, h, jnp.uint32(0))


def _h4(hash_type, a, b, c, d):
    h = H.crush_hash32_4(_u32(a), _u32(b), _u32(c), _u32(d))
    return jnp.where(hash_type == C.CRUSH_HASH_RJENKINS1, h, jnp.uint32(0))


class _RuleCompiler:
    """Trace-time compiler for one (map, rule, result_max) triple.

    Instantiated fresh inside the traced function: all methods close over
    the traced map arrays ``A``, weight vector and x of a single lane.
    """

    def __init__(self, static: MapStatic, result_max: int,
                 needs_perm: bool):
        self.st = static
        self.R = result_max
        self.B = static.max_buckets
        self.S = static.max_size
        self.needs_perm = needs_perm
        self.tabs = (jnp.asarray(RH_LH_NP), jnp.asarray(LL_NP))
        # The straw2 selection has two bit-identical lowerings: the
        # arithmetic crush_ln + 64-bit divide (best on CPU, where integer
        # division is native), and the LN16-table + reciprocal-mulhi key
        # (best on TPU, where the divide and the ln pipeline dominate the
        # whole mapper).  Both are golden-tested; pick per backend, with
        # CEPH_TPU_STRAW2={table,compute} as the override.
        mode = os.environ.get("CEPH_TPU_STRAW2", "")
        if mode not in ("table", "compute"):
            mode = "compute" if jax.default_backend() == "cpu" else "table"
        self.use_table_key = mode == "table"
        self.ln16 = jnp.asarray(ln16_table()) if self.use_table_key \
            else None
        # weight reciprocals for the division-free straw2 key; set per
        # trace by single() so they are computed once per launch (they
        # depend only on the unbatched map arrays, so vmap hoists them)
        self.recip_w = None
        self.recip_aw = None

    # -- workspace ----------------------------------------------------
    def perm_init(self):
        if not self.needs_perm:
            return ()
        return (jnp.zeros(self.B, U32),
                jnp.zeros(self.B, I32),
                jnp.broadcast_to(jnp.arange(self.S, dtype=I32),
                                 (self.B, self.S)))

    # -- bucket choose methods (vectorized over the padded item axis) --
    def _perm_choose(self, A, perm, x, bidx, r):
        """bucket_perm_choose (mapper.c:51-109) with functional state."""
        px, pn, pm = perm
        sz = jnp.maximum(A.size[bidx], 1)  # callers reject empty buckets
        hsh = A.bhash[bidx]
        bid = A.bid[bidx]
        pr = jnp.remainder(r, sz).astype(I32)
        reset = (px[bidx] != _u32(x)) | (pn[bidx] == 0)
        shortcut = reset & (pr == 0)

        def do_shortcut(args):
            px, pn, pm = args
            s = jnp.remainder(_h3(hsh, x, bid, jnp.int32(0)), _u32(sz))
            s = s.astype(I32)
            px = px.at[bidx].set(_u32(x))
            pn = pn.at[bidx].set(0xFFFF)
            pm = pm.at[bidx, 0].set(s)
            return A.items[bidx, s], (px, pn, pm)

        def do_full(args):
            px, pn, pm = args
            iota = jnp.arange(self.S, dtype=I32)
            row = pm[bidx]
            # reset path: fresh identity permutation, start at 0
            row_reset = iota
            # cleanup path after a previous r=0 shortcut (mapper.c:77-83):
            # keep row[0]=s, set row[i]=i for i>=1, then row[s]=0
            s_prev = row[0]
            row_clean = iota.at[0].set(s_prev).at[s_prev].set(0)
            cleanup = (~reset) & (pn[bidx] == 0xFFFF)
            row = jnp.where(reset, row_reset,
                            jnp.where(cleanup, row_clean, row))
            n0 = jnp.where(reset, 0, jnp.where(cleanup, 1, pn[bidx]))
            px = px.at[bidx].set(_u32(x))

            def body(p, row):
                act = (p >= n0) & (p <= pr) & (p < sz - 1)
                i = jnp.remainder(_h3(hsh, x, bid, jnp.int32(p)),
                                  _u32(jnp.maximum(sz - p, 1))).astype(I32)
                pi = jnp.clip(p + i, 0, self.S - 1)
                a, b = row[p], row[pi]
                do_swap = act & (i != 0)
                row = row.at[p].set(jnp.where(do_swap, b, a))
                row = row.at[pi].set(jnp.where(do_swap, a, b))
                return row

            row = lax.fori_loop(0, self.S, body, row)
            pn = pn.at[bidx].set(jnp.maximum(n0, pr + 1))
            pm = pm.at[bidx].set(row)
            return A.items[bidx, row[pr]], (px, pn, pm)

        return lax.cond(shortcut, do_shortcut, do_full, perm)

    def _straw2_choose(self, A, x, bidx, r, position):
        """Masked-argmax straw2 (mapper.c:339-362) with choose_args
        weight/id substitution (mapper.c:287-304) pre-baked per bucket."""
        sz = A.size[bidx]
        hsh = A.bhash[bidx]
        if self.st.has_choose_args:
            pos = min(position, self.st.max_positions - 1) \
                if isinstance(position, int) \
                else jnp.minimum(position, self.st.max_positions - 1)
            wts = A.arg_weights[bidx, pos]
            ids = A.arg_ids[bidx]
        else:
            wts = A.weights[bidx]
            ids = A.items[bidx]
        u = _h3(hsh, x, ids, r)
        lane = jnp.arange(self.S, dtype=I32)
        if self.use_table_key:
            rec = self.recip_aw[bidx, pos] if self.st.has_choose_args \
                else self.recip_w[bidx]
            keys = straw2_key(u, wts, rec, xp=jnp, ln_tab=self.ln16)
            keys = jnp.where(lane < sz, keys,
                             jnp.uint64(0xFFFFFFFFFFFFFFFF))
            # argmin/argmax return the x64 index dtype (int64); the
            # gather index lanes are int32 by contract (jaxcheck)
            return A.items[bidx, jnp.argmin(keys).astype(I32)]
        draws = straw2_draw(u & jnp.uint32(0xFFFF), wts, xp=jnp,
                            tables=self.tabs)
        draws = jnp.where(lane < sz, draws, jnp.int64(C.S64_MIN))
        return A.items[bidx, jnp.argmax(draws).astype(I32)]

    def _straw_choose(self, A, x, bidx, r):
        """Legacy straw (mapper.c:205-223)."""
        sz = A.size[bidx]
        hsh = A.bhash[bidx]
        u = _h3(hsh, x, A.items[bidx], r) & jnp.uint32(0xFFFF)
        draws = u.astype(jnp.uint64) * A.straws[bidx].astype(jnp.uint64)
        lane = jnp.arange(self.S, dtype=I32)
        draws = jnp.where(lane < sz, draws, jnp.uint64(0))
        return A.items[bidx, jnp.argmax(draws).astype(I32)]

    def _list_choose(self, A, x, bidx, r):
        """Tail-to-head probabilistic descent (mapper.c:119-142): the C
        loop returns the *largest* index whose draw lands under its
        weight, falling back to items[0]."""
        sz = A.size[bidx]
        hsh = A.bhash[bidx]
        bid = A.bid[bidx]
        h = _h4(hsh, x, A.items[bidx], r, bid) & jnp.uint32(0xFFFF)
        w = (h.astype(jnp.uint64)
             * A.sum_weights[bidx].astype(jnp.uint64)) >> jnp.uint64(16)
        hit = w < A.weights[bidx].astype(jnp.uint64)
        lane = jnp.arange(self.S, dtype=I32)
        cand = jnp.where(hit & (lane < sz), lane, -1)
        j = jnp.max(cand)
        return A.items[bidx, jnp.maximum(j, 0)]

    def _tree_choose(self, A, x, bidx, r):
        """Weighted binary tree descent (mapper.c:145-200).

        Under vmap, lax.switch executes every branch for every lane, so
        this must terminate even when ``bidx`` is a non-tree bucket
        (nnodes=0, where n would get stuck at 0): clamp the start node
        to 1 (odd → immediate exit) and bound the loop by the static
        tree depth as a belt-and-braces guard."""
        hsh = A.bhash[bidx]
        bid = A.bid[bidx]
        n0 = jnp.maximum((A.nnodes[bidx] >> 1).astype(I32), 1)
        max_depth = max(1, int(self.st.max_nodes).bit_length())

        def cond(st):
            n, d = st
            return ((n & 1) == 0) & (d < max_depth)

        def body(st):
            n, d = st
            w = A.node_weights[bidx, n]
            t = (_h4(hsh, x, n, r, bid).astype(jnp.uint64)
                 * w.astype(jnp.uint64)) >> jnp.uint64(32)
            half = ((n & -n) >> 1).astype(I32)
            left = n - half
            lw = A.node_weights[bidx, left].astype(jnp.uint64)
            return jnp.where(t < lw, left, n + half), d + 1

        n, _ = lax.while_loop(cond, body, (n0, jnp.int32(0)))
        return A.items[bidx, n >> 1]

    def bucket_choose(self, A, perm, x, bidx, r, position):
        """crush_bucket_choose dispatch (mapper.c:365-396).  Only the
        algorithms actually present in the map get branches."""
        algs = self.st.algs_present
        if len(algs) == 1 and algs[0] != C.CRUSH_BUCKET_UNIFORM:
            return self._fixed_alg(algs[0], A, x, bidx, r, position), perm

        branches = []
        for alg in algs:
            if alg == C.CRUSH_BUCKET_UNIFORM:
                branches.append(
                    lambda op, a=alg: self._perm_choose(
                        op[0], op[1], op[2], op[3], op[4]))
            else:
                branches.append(
                    lambda op, a=alg: (
                        self._fixed_alg(a, op[0], op[2], op[3], op[4],
                                        position), op[1]))
        table = np.zeros(6, np.int32)
        for i, alg in enumerate(algs):
            table[alg] = i
        br = jnp.asarray(table)[jnp.clip(A.alg[bidx], 0, 5)]
        return lax.switch(br, branches, (A, perm, x, bidx, r))

    def _fixed_alg(self, alg, A, x, bidx, r, position):
        if alg == C.CRUSH_BUCKET_STRAW2:
            return self._straw2_choose(A, x, bidx, r, position)
        if alg == C.CRUSH_BUCKET_STRAW:
            return self._straw_choose(A, x, bidx, r)
        if alg == C.CRUSH_BUCKET_LIST:
            return self._list_choose(A, x, bidx, r)
        if alg == C.CRUSH_BUCKET_TREE:
            return self._tree_choose(A, x, bidx, r)
        raise AssertionError(f"alg {alg} needs perm state")

    # -- device rejection ---------------------------------------------
    def is_out(self, weight, item, x):
        """Weight-based rejection (mapper.c:402-416); item is a valid
        device id when this is called.  The weight vector is the
        caller's runtime array and its length is the C ``weight_max``
        bound: items at or past it are out (mapper.c:406), never a
        clamped gather into the last slot."""
        wmax = weight.shape[0]
        w = weight[jnp.clip(item, 0, wmax - 1)]
        h = _h2(jnp.int32(C.CRUSH_HASH_RJENKINS1), x, item) \
            & jnp.uint32(0xFFFF)
        return jnp.where(item >= wmax, True,
                         jnp.where(w >= 0x10000, False,
                                   jnp.where(w == 0, True, h >= w)))

    # -- child bucket classification ----------------------------------
    def classify(self, A, item):
        """Returns (itemtype, child_idx, valid_child).  itemtype is -1
        for a negative id with no bucket behind it (the C code skips
        before ever reading a type there)."""
        is_neg = item < 0
        cidx = jnp.clip(-1 - item, 0, self.B - 1)
        exists = is_neg & ((-1 - item) < self.B) & (A.alg[cidx] != 0)
        itemtype = jnp.where(
            is_neg, jnp.where(exists, A.btype[cidx], -1), 0)
        return itemtype, cidx, exists


def _seg_any_eq(vec, lo, hi, value):
    """any(vec[i] == value for i in [lo, hi)) without dynamic slicing."""
    idx = jnp.arange(vec.shape[0], dtype=I32)
    return jnp.any((idx >= lo) & (idx < hi) & (vec == value))


def make_choose_firstn(rc: _RuleCompiler, *, numrep: int, type_: int,
                       tries: int, recurse_tries: int, local_retries: int,
                       fallback_retries: int, recurse_to_leaf: bool,
                       vary_r: int, stable: int, single_rep: bool):
    """Builds crush_choose_firstn (mapper.c:438-626) for one static
    configuration.  When ``single_rep`` (the chooseleaf recursion), the
    rep loop collapses to the one position the parent is filling."""
    R = rc.R

    if recurse_to_leaf:
        inner = make_choose_firstn(
            rc, numrep=1, type_=0, tries=recurse_tries, recurse_tries=0,
            local_retries=local_retries, fallback_retries=fallback_retries,
            recurse_to_leaf=False, vary_r=vary_r, stable=stable,
            single_rep=True)

    def run(A, weight, x, root, out, base, outpos0, count0,
            out2, base2, parent_r, perm):
        """Returns (outpos, out, out2, perm)."""

        def attempt_loop(rep, outpos, count, out, out2, perm):
            def cond(st):
                return ~st[0]

            def body(st):
                (done, placed, skip, in_b, flocal, ftotal, item,
                 out2, perm) = st
                r = (rep + parent_r + ftotal).astype(I32)
                sz = A.size[in_b]
                empty = sz == 0

                if fallback_retries > 0:
                    use_pc = (flocal >= (sz >> 1)) \
                        & (flocal > fallback_retries)
                    nitem, perm = lax.cond(
                        use_pc & ~empty,
                        lambda op: rc._perm_choose(A, op[0], x, in_b, r),
                        lambda op: rc.bucket_choose(
                            A, op[0], x, in_b, r, outpos_pos),
                        (perm,))
                else:
                    nitem, perm = rc.bucket_choose(
                        A, perm, x, in_b, r, outpos_pos)
                item = jnp.where(empty, item, nitem)

                over = (~empty) & (item >= rc.st.max_devices)
                itemtype, cidx, exists = rc.classify(A, item)
                want = itemtype == type_
                descend = (~empty) & (~over) & (~want) & exists
                badterm = (~empty) & (~over) & (~want) & (~exists)
                live = (~empty) & (~over) & want

                collide = live & _seg_any_eq(out, base, base + outpos, item)
                reject = empty

                if recurse_to_leaf:
                    do_rec = live & ~collide
                    rec_neg = do_rec & (item < 0)
                    sub_r = (r >> (vary_r - 1)) if vary_r else jnp.int32(0)

                    def rec(op):
                        o2, pm = op
                        got, o2, _, pm = inner(
                            A, weight, x, cidx, o2, base2, outpos, count,
                            None, jnp.int32(0), sub_r, pm)
                        return got, o2, pm

                    def norec(op):
                        return outpos, op[0], op[1]

                    got, out2, perm = lax.cond(
                        rec_neg, rec, norec, (out2, perm))
                    reject = reject | (rec_neg & (got <= outpos))
                    dev_leaf = do_rec & (item >= 0)
                    out2 = jnp.where(
                        dev_leaf,
                        out2.at[jnp.clip(base2 + outpos, 0, R - 1)]
                        .set(item), out2)

                check = live & ~collide & ~reject & (itemtype == 0)
                reject = reject | (check & rc.is_out(weight, item, x))

                fail = (reject | collide) & ~over & ~badterm & ~descend
                nftotal = ftotal + fail.astype(I32)
                nflocal = flocal + fail.astype(I32)
                retry_b = fail & (
                    (collide & (nflocal <= local_retries))
                    | ((fallback_retries > 0)
                       & (nflocal <= sz + fallback_retries)))
                retry_d = fail & ~retry_b & (nftotal < tries)
                give_up = fail & ~retry_b & ~retry_d

                success = live & ~collide & ~reject
                ndone = over | badterm | give_up | success
                nskip = over | badterm | give_up
                nplaced = success
                n_in_b = jnp.where(descend, cidx,
                                   jnp.where(retry_d, root, in_b))
                nflocal = jnp.where(retry_d, 0, nflocal)
                return (ndone, nplaced, nskip, n_in_b, nflocal, nftotal,
                        item, out2, perm)

            outpos_pos = outpos  # the C `outpos` passed to choose_args
            st = (jnp.bool_(False), jnp.bool_(False), jnp.bool_(False),
                  root, jnp.int32(0), jnp.int32(0), jnp.int32(0),
                  out2, perm)
            st = lax.while_loop(cond, body, st)
            _, placed, _, _, _, _, item, out2, perm = st
            out = jnp.where(
                placed,
                out.at[jnp.clip(base + outpos, 0, R - 1)].set(item), out)
            outpos = outpos + placed.astype(I32)
            count = count - placed.astype(I32)
            return outpos, count, out, out2, perm

        if single_rep:
            rep = jnp.int32(0) if stable else outpos0
            outpos, count, out, out2, perm = attempt_loop(
                rep, outpos0, count0, out, out2, perm)
            return outpos, out, out2, perm

        def outer_cond(st):
            rep, outpos, count = st[0], st[1], st[2]
            return (rep < numrep) & (count > 0)

        def outer_body(st):
            rep, outpos, count, out, out2, perm = st
            outpos, count, out, out2, perm = attempt_loop(
                rep, outpos, count, out, out2, perm)
            return rep + 1, outpos, count, out, out2, perm

        st = (jnp.int32(0), outpos0, count0, out, out2, perm)
        _, outpos, _, out, out2, perm = lax.while_loop(
            outer_cond, outer_body, st)
        return outpos, out, out2, perm

    return run


def make_choose_indep(rc: _RuleCompiler, *, numrep: int, type_: int,
                      tries: int, recurse_tries: int,
                      recurse_to_leaf: bool, single_rep: bool):
    """Builds crush_choose_indep (mapper.c:633-821): breadth-first rounds,
    positionally stable, UNDEF backfilled to NONE."""
    R = rc.R

    if recurse_to_leaf:
        inner = make_choose_indep(
            rc, numrep=numrep, type_=0, tries=recurse_tries,
            recurse_tries=0, recurse_to_leaf=False, single_rep=True)

    def run(A, weight, x, root, left0, out, base, outpos0,
            out2, base2, parent_r, perm):
        """Returns (out, out2, perm)."""
        idx = jnp.arange(R, dtype=I32)
        endpos = outpos0 + left0
        seg = (idx >= base + outpos0) & (idx < base + endpos)
        out = jnp.where(seg, UNDEF, out)
        has2 = out2 is not None
        if has2:
            seg2 = (idx >= base2 + outpos0) & (idx < base2 + endpos)
            out2 = jnp.where(seg2, UNDEF, out2)
        else:
            out2 = jnp.zeros((), I32)  # placeholder carried through

        def fill_rep(rep, ftotal, left, out, out2, perm):
            """One descent attempt for one result slot (one round)."""

            def dcond(st):
                return ~st[0]

            def dbody(st):
                done, in_b, left, out, out2, perm = st
                alg_u = (A.alg[in_b] == C.CRUSH_BUCKET_UNIFORM) \
                    & (jnp.remainder(A.size[in_b], numrep) == 0)
                r = rep + parent_r \
                    + jnp.where(alg_u, (numrep + 1) * ftotal,
                                numrep * ftotal)
                r = r.astype(I32)
                sz = A.size[in_b]
                empty = sz == 0

                item, perm = rc.bucket_choose(A, perm, x, in_b, r,
                                              outpos_pos)
                over = (~empty) & (item >= rc.st.max_devices)
                itemtype, cidx, exists = rc.classify(A, item)
                want = itemtype == type_
                descend = (~empty) & (~over) & (~want) & exists
                badterm = ((~empty) & (~over) & (~want) & (~exists)) | over
                live = (~empty) & (~badterm) & want & ~descend

                collide = live & _seg_any_eq(
                    out, base + outpos0, base + endpos, item)
                ok = live & ~collide

                if recurse_to_leaf:
                    rec_neg = ok & (item < 0)

                    def rec(op):
                        o2, pm = op
                        o2, _, pm = inner(
                            A, weight, x, cidx, jnp.int32(1), o2, base2,
                            rep, None, jnp.int32(0), r, pm)
                        return o2, pm

                    out2, perm = lax.cond(
                        rec_neg, rec, lambda op: op, (out2, perm))
                    leaf_fail = rec_neg & (
                        out2[jnp.clip(base2 + rep, 0, R - 1)] == NONE)
                    dev_leaf = ok & (item >= 0)
                    out2 = jnp.where(
                        dev_leaf,
                        out2.at[jnp.clip(base2 + rep, 0, R - 1)]
                        .set(item), out2)
                    ok = ok & ~leaf_fail

                ok = ok & ~((itemtype == 0) & rc.is_out(weight, item, x))

                # terminal NONE (out-of-range item / unresolvable child)
                out = jnp.where(
                    badterm,
                    out.at[jnp.clip(base + rep, 0, R - 1)].set(NONE), out)
                if recurse_to_leaf:
                    out2 = jnp.where(
                        badterm,
                        out2.at[jnp.clip(base2 + rep, 0, R - 1)]
                        .set(NONE), out2)
                out = jnp.where(
                    ok, out.at[jnp.clip(base + rep, 0, R - 1)].set(item),
                    out)
                left = left - (badterm | ok).astype(I32)
                ndone = ~descend
                n_in_b = jnp.where(descend, cidx, in_b)
                return ndone, n_in_b, left, out, out2, perm

            # choose_args position: the C code passes the function's
            # `outpos` parameter (mapper.c:701), not the slot index
            outpos_pos = outpos0
            slot_open = out[jnp.clip(base + rep, 0, R - 1)] == UNDEF
            active = (rep >= outpos0) & (rep < endpos) & slot_open

            def go(op):
                st = (jnp.bool_(False), root) + op
                st = lax.while_loop(dcond, dbody, st)
                return st[2:]

            left, out, out2, perm = lax.cond(
                active, go, lambda op: op, (left, out, out2, perm))
            return left, out, out2, perm

        def round_cond(st):
            ftotal, left = st[0], st[1]
            return (left > 0) & (ftotal < tries)

        def round_body(st):
            ftotal, left, out, out2, perm = st
            if single_rep:
                left, out, out2, perm = fill_rep(
                    outpos0, ftotal, left, out, out2, perm)
            else:
                for rep_i in range(numrep):
                    left, out, out2, perm = fill_rep(
                        outpos0 + rep_i, ftotal, left, out, out2, perm)
            return ftotal + 1, left, out, out2, perm

        st = (jnp.int32(0), left0, out, out2, perm)
        _, _, out, out2, perm = lax.while_loop(round_cond, round_body, st)

        out = jnp.where(seg & (out == UNDEF), NONE, out)
        if has2:
            out2 = jnp.where(seg2 & (out2 == UNDEF), NONE, out2)
            return out, out2, perm
        return out, None, perm

    return run


def make_single_fn(cmap: CrushMap, ruleno: int, result_max: int,
                   choose_args: Optional[ChooseArgMap] = None,
                   encoded=None):
    """The unjitted single-x rule program: ``single(arrays, weight, x)
    -> (result i32[R], len i32)``.  Compose/fuse it into larger programs
    (the OSDMap pipeline) before vmap+jit.  Returns
    ``(single, static, arrays_np)``.

    ``encoded``: a pre-computed ``encode_map`` result, so callers
    compiling many rules over one map pay the host-side encode once.
    """
    static, arrays_np = encoded if encoded is not None \
        else encode_map(cmap, choose_args)
    rule = cmap.rules[ruleno]
    (local_tries, fallback_tries, total_tries, descend_once,
     vary_r0, stable0) = static.tunables

    # Walk the steps once to know whether perm state can ever be touched:
    # uniform buckets present, or a fallback-tries setting > 0 in force.
    fb = fallback_tries
    max_fb = fb
    for s in rule.steps:
        if s.op == C.CRUSH_RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES \
                and s.arg1 >= 0:
            fb = s.arg1
            max_fb = max(max_fb, fb)
    needs_perm = static.has_uniform or max_fb > 0

    rc = _RuleCompiler(static, result_max, needs_perm)
    R = result_max
    B = static.max_buckets

    def single(A, weight, x):
        if rc.use_table_key:
            if static.has_choose_args:
                rc.recip_aw = recip64(A.arg_weights, xp=jnp)
            else:
                rc.recip_w = recip64(A.weights, xp=jnp)
        try:
            return _single_body(A, weight, x)
        finally:
            # the recips are TRACERS while jit traces this function;
            # rc outlives the trace (the closure keeps it), so leaving
            # them set leaks the dead tracer — jax.checking_leaks
            # (the kernel-test gate) rejects the program
            rc.recip_w = rc.recip_aw = None

    def _single_body(A, weight, x):
        choose_tries = total_tries + 1  # mapper.c:906 off-by-one heritage
        choose_leaf_tries = 0
        local_retries = local_tries
        local_fb = fallback_tries
        vary_r = vary_r0
        stable = stable0

        w = jnp.zeros(R, I32)
        result = jnp.full(R, NONE, I32)
        rlen = jnp.int32(0)
        wsize = jnp.int32(0)
        wbound = 0
        perm = rc.perm_init()
        idx = jnp.arange(R, dtype=I32)

        for step in rule.steps:
            op, arg1, arg2 = step.op, step.arg1, step.arg2
            if op == C.CRUSH_RULE_TAKE:
                ok = (0 <= arg1 < cmap.max_devices) or \
                    (arg1 < 0 and cmap.bucket_by_id(arg1) is not None)
                if ok:
                    w = w.at[0].set(arg1)
                    wsize = jnp.int32(1)
                    wbound = 1
            elif op == C.CRUSH_RULE_SET_CHOOSE_TRIES:
                if arg1 > 0:
                    choose_tries = arg1
            elif op == C.CRUSH_RULE_SET_CHOOSELEAF_TRIES:
                if arg1 > 0:
                    choose_leaf_tries = arg1
            elif op == C.CRUSH_RULE_SET_CHOOSE_LOCAL_TRIES:
                if arg1 >= 0:
                    local_retries = arg1
            elif op == C.CRUSH_RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES:
                if arg1 >= 0:
                    local_fb = arg1
            elif op == C.CRUSH_RULE_SET_CHOOSELEAF_VARY_R:
                if arg1 >= 0:
                    vary_r = arg1
            elif op == C.CRUSH_RULE_SET_CHOOSELEAF_STABLE:
                if arg1 >= 0:
                    stable = arg1
            elif op in (C.CRUSH_RULE_CHOOSELEAF_FIRSTN,
                        C.CRUSH_RULE_CHOOSE_FIRSTN,
                        C.CRUSH_RULE_CHOOSELEAF_INDEP,
                        C.CRUSH_RULE_CHOOSE_INDEP):
                if wbound == 0:
                    continue
                numrep = arg1
                if numrep <= 0:
                    numrep += result_max
                    if numrep <= 0:
                        continue
                firstn = op in (C.CRUSH_RULE_CHOOSELEAF_FIRSTN,
                                C.CRUSH_RULE_CHOOSE_FIRSTN)
                leafy = op in (C.CRUSH_RULE_CHOOSELEAF_FIRSTN,
                               C.CRUSH_RULE_CHOOSELEAF_INDEP)
                if firstn:
                    if choose_leaf_tries:
                        recurse_tries = choose_leaf_tries
                    elif descend_once:
                        recurse_tries = 1
                    else:
                        recurse_tries = choose_tries
                    fn = make_choose_firstn(
                        rc, numrep=numrep, type_=arg2, tries=choose_tries,
                        recurse_tries=recurse_tries,
                        local_retries=local_retries,
                        fallback_retries=local_fb, recurse_to_leaf=leafy,
                        vary_r=vary_r, stable=stable, single_rep=False)
                else:
                    fn = make_choose_indep(
                        rc, numrep=numrep, type_=arg2, tries=choose_tries,
                        recurse_tries=(choose_leaf_tries
                                       if choose_leaf_tries else 1),
                        recurse_to_leaf=leafy, single_rep=False)

                o = jnp.zeros(R, I32)
                cvec = jnp.zeros(R, I32)
                osize = jnp.int32(0)
                for i in range(wbound):
                    src = w[i]
                    sidx = jnp.clip(-1 - src, 0, B - 1)
                    run = (jnp.int32(i) < wsize) & (src < 0) \
                        & ((-1 - src) < B) & (A.alg[sidx] != 0)
                    if firstn:
                        def go_f(op_):
                            o, cvec, perm = op_
                            got, o, cvec, perm = fn(
                                A, weight, x, sidx, o, osize,
                                jnp.int32(0), jnp.int32(R) - osize,
                                cvec, osize, jnp.int32(0), perm)
                            return got, o, cvec, perm

                        got, o, cvec, perm = lax.cond(
                            run, go_f,
                            lambda op_: (jnp.int32(0),) + op_,
                            (o, cvec, perm))
                        osize = osize + got
                    else:
                        out_size = jnp.minimum(
                            jnp.int32(numrep), jnp.int32(R) - osize)

                        def go_i(op_):
                            o, cvec, perm = op_
                            o, cvec, perm = fn(
                                A, weight, x, sidx, out_size, o, osize,
                                jnp.int32(0), cvec, osize, jnp.int32(0),
                                perm)
                            return o, cvec, perm

                        o, cvec, perm = lax.cond(
                            run, go_i, lambda op_: op_, (o, cvec, perm))
                        osize = osize + jnp.where(run, out_size, 0)
                if leafy:
                    o = jnp.where(idx < osize, cvec, o)
                w = o
                wsize = osize
                wbound = min(R, wbound * numrep)
            elif op == C.CRUSH_RULE_EMIT:
                src_i = idx - rlen
                take = (src_i >= 0) & (src_i < wsize)
                gathered = w[jnp.clip(src_i, 0, R - 1)]
                result = jnp.where(take, gathered, result)
                rlen = jnp.minimum(rlen + wsize, R)
                wsize = jnp.int32(0)
                wbound = 0
        return result, rlen

    return single, static, arrays_np


def build_rule_fn(cmap: CrushMap, ruleno: int, result_max: int,
                  choose_args: Optional[ChooseArgMap] = None,
                  encoded=None):
    """Compile one rule into a batched mapper.

    Returns ``(fn, static, arrays)`` where ``fn(arrays, weight_u32[D],
    xs_u32[N]) -> (results i32[N, result_max], lens i32[N])`` is jitted;
    pass updated ``arrays``/``weight`` freely — only shape changes
    recompile.  This is the TPU replacement for the reference hot loop at
    CrushTester.cc:573 / OSDMapMapping.h:18.
    """
    single, static, arrays_np = make_single_fn(
        cmap, ruleno, result_max, choose_args, encoded)
    batched = jax.jit(jax.vmap(single, in_axes=(None, None, 0)))
    return batched, static, arrays_np

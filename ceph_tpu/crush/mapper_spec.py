"""Speculative straw2 firstn mapper — the divergence-tolerant fast path.

The general batched mapper (``mapper_jax.py``) reproduces the reference's
retry descent (crush_choose_firstn, src/crush/mapper.c:438-626) as a
per-lane ``lax.while_loop``.  Under ``vmap`` that loop runs until the
*slowest* lane finishes and every iteration does only one small descent
step, so the program the TPU sees is long, serial, and narrow — the exact
shape the MXU hates.

This module compiles the *common case* — straw2-only hierarchies mapped by
a ``take / chooseleaf firstn / emit`` rule under modern tunables
(choose_local_tries=0, choose_local_fallback_tries=0) — into a dense
speculative program instead:

- One "try" of the reference's retry loop is a pure descent from the take
  root (r = rep + ftotal, mapper.c:497) whose depth is bounded by the
  static hierarchy depth.  Nothing about try ``ftotal`` depends on try
  ``ftotal-1`` *except* which one is selected, so K tries are evaluated
  at once as (K, fanout)-shaped straw2 draws and the reference's retry
  semantics collapse to "first non-failing try wins" (masked argmax).
- The chooseleaf recursion (mapper.c:548-572: numrep=1, its own retry
  budget ``recurse_tries``, r' = (stable ? 0 : outpos) + (vary_r ?
  r >> (vary_r-1) : 0) + ftotal') is unrolled the same way: with
  chooseleaf_descend_once (tunables since firefly) it is a single pure
  descent per outer try.
- The per-rep round loop remains a ``lax.while_loop``, but its body now
  retires K tries per iteration and virtually always exits after one.
- "Virtually always" is not "always": under ``vmap`` the loop runs every
  lane until the slowest is done.  :func:`map_stragglers` therefore runs
  one round over the whole batch and the loops only over the lanes that
  need another round, compacted into chunks a fraction of its size.
- ``choose(leaf) indep`` rules, and the two-step ``choose indep N type
  T / chooseleaf indep M type U`` of Ceph's locality-aware EC rules (the
  LRC plugin's ``crush-locality``), run the reference's breadth-first
  rounds directly: every open slot's descent is one lane of a batch.

Bit-exactness contract: identical (result, len) to ``mapper_ref.py`` /
``mapper_jax.py`` for every eligible (map, rule, tunables) combination —
asserted for all golden maps in ``tests/test_mapper_spec.py``.  Eligible
rules are detected by :func:`analyze`; ineligible ones raise
:class:`Ineligible`, and ``lowering.lower_rule`` lowers them onto the
general rule VM instead.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax

if not jax.config.jax_enable_x64:
    jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402

from jax import lax  # noqa: E402

from . import constants as C  # noqa: E402
from . import hash as H  # noqa: E402
from .ln import ln16_table, recip64, straw2_key  # noqa: E402
from .map import ChooseArgMap, CrushMap  # noqa: E402
from .map_arrays import encode_map  # noqa: E402

I32 = jnp.int32
U32 = jnp.uint32
NONE = C.CRUSH_ITEM_NONE
UNDEF = C.CRUSH_ITEM_UNDEF

# per-k try status codes
_DESC = 0     # still descending
_OK = 1       # reached an item of the wanted type (device for inner)
_FAIL = 2     # reject/collide/empty — costs one ftotal, retry from root
_SKIP = 3     # terminal: give up this rep (over / unresolvable child)


class Ineligible(ValueError):
    """The (map, rule, tunables) combination needs the general mapper."""


@dataclass(frozen=True)
class Plan:
    """Static facts the speculative compiler needs (all trace-time).

    A one-step rule is its ``choose`` step; a two-step rule (``choose
    indep`` of buckets, then ``chooseleaf indep`` below each) is its
    second step, below the ``pre_numrep`` buckets of ``pre_type`` that
    the first picks (``pre_numrep`` 0: one step)."""

    root_idx: int        # bucket index of the take root
    numrep: int
    type_: int           # target type of the choose step
    leafy: bool          # chooseleaf (recurse to device) vs choose type 0
    firstn: bool         # firstn (compacting) vs indep (positional)
    tries: int           # outer retry budget (choose_total_tries + 1 rule)
    recurse_tries: int   # inner retry budget (1 under descend_once)
    vary_r: int
    stable: int
    depth_outer: int     # levels from the root (or a pre_type bucket)
                         # to the choose's type
    depth_inner: int     # levels from a type_ bucket to the devices
    pre_numrep: int = 0  # two-step: buckets the first step picks
    pre_type: int = 0    # two-step: their type
    pre_tries: int = 0   # two-step: the first step's retry budget
    pre_depth: int = 0   # two-step: descent levels root -> pre_type
    first_rounds: int = 1  # indep rounds the one-round pass unrolls
    levels_cut: int = 0  # one-step: levels below type_ that a descent
                         # from the root to the devices would add


def _depth_to(cmap: CrushMap, idx: int, type_: int, _seen=()) -> int:
    """Levels a descent from bucket index ``idx`` looking for
    ``type_`` can take: it stops at an item of that type, a device or
    a missing bucket, and goes on into any other bucket.  A descent
    draws once per level, so this bounds every descent that
    terminates; a cycle would not terminate in the C descent either."""
    if idx in _seen:
        raise Ineligible("bucket graph has a cycle")
    best = 1
    for it in cmap.buckets[idx].items:
        b = cmap.buckets.get(-1 - it) if it < 0 else None
        if b is not None and b.type != type_:
            best = max(best, 1 + _depth_to(cmap, -1 - it, type_,
                                           _seen + (idx,)))
    return best


# inner retry budgets unrolled in one try of the outer loop: firstn
# unrolls all of them in the one-round pass; indep tries the first
# there and flags a lane that needs more (Ceph's EC rules set 5)
MAX_RECURSE_TRIES = {True: 4, False: 5}


def analyze(cmap: CrushMap, ruleno: int, result_max: int) -> Plan:
    """Decide eligibility and extract the static plan.

    Eligible iff: every bucket is straw2; the rule is one ``take``,
    then either one ``choose(leaf) firstn|indep`` or ``choose indep N
    type T`` (a bucket type) followed by ``chooseleaf indep M type U``
    (U a bucket type, N·M <= result_max), then ``emit`` (SET_* tunable
    steps allowed); the effective local retry knobs of a firstn rule
    are 0 (modern tunables — mapper.c:444-449 never takes the
    retry_bucket or perm-fallback paths then); the inner budget
    unrolls (:data:`MAX_RECURSE_TRIES`); and numrep fits result_max.
    """
    for b in cmap.buckets.values():
        if b.alg != C.CRUSH_BUCKET_STRAW2:
            raise Ineligible(f"bucket alg {b.alg} != straw2")
    t = cmap.tunables
    rule = cmap.rules[ruleno]

    choose_tries = t.choose_total_tries + 1  # mapper.c:906 heritage
    choose_leaf_tries = 0
    local_retries = t.choose_local_tries
    local_fb = t.choose_local_fallback_tries
    vary_r = t.chooseleaf_vary_r
    stable = t.chooseleaf_stable

    root = None
    chooses = []     # (numrep, type, leafy, firstn, tries, leaf_tries)
    emitted = False
    for step in rule.steps:
        op, arg1, arg2 = step.op, step.arg1, step.arg2
        if emitted:
            raise Ineligible("steps after emit")
        if op == C.CRUSH_RULE_SET_CHOOSE_TRIES:
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
        elif op == C.CRUSH_RULE_TAKE:
            if root is not None or chooses:
                raise Ineligible("multiple takes")
            if arg1 >= 0 or cmap.bucket_by_id(arg1) is None:
                raise Ineligible("take target is not an existing bucket")
            root = -1 - arg1
        elif op in (C.CRUSH_RULE_CHOOSELEAF_FIRSTN,
                    C.CRUSH_RULE_CHOOSE_FIRSTN,
                    C.CRUSH_RULE_CHOOSELEAF_INDEP,
                    C.CRUSH_RULE_CHOOSE_INDEP):
            if root is None:
                raise Ineligible("choose without take")
            if len(chooses) == 2:
                raise Ineligible("more than two chooses")
            leafy = op in (C.CRUSH_RULE_CHOOSELEAF_FIRSTN,
                           C.CRUSH_RULE_CHOOSELEAF_INDEP)
            firstn = op in (C.CRUSH_RULE_CHOOSELEAF_FIRSTN,
                            C.CRUSH_RULE_CHOOSE_FIRSTN)
            numrep = arg1
            if numrep <= 0:
                numrep += result_max
            if not (0 < numrep <= result_max):
                raise Ineligible("numrep outside [1, result_max]")
            if numrep > 16:
                raise Ineligible("numrep unroll bound exceeded")
            if not firstn and leafy and arg2 == 0:
                # the reference writes the candidate device into out2
                # BEFORE the is_out check here (mapper.c:772-776), so
                # an all-rejected slot leaks its last rejected device
                # into the result; reproducing that quirk isn't worth
                # the complexity — fall back to the general VM
                raise Ineligible("chooseleaf indep of type 0 "
                                 "(out2 pre-is_out leak quirk)")
            chooses.append((numrep, arg2, leafy, firstn, choose_tries,
                            choose_leaf_tries))
        elif op == C.CRUSH_RULE_EMIT:
            if not chooses:
                raise Ineligible("emit without choose")
            emitted = True
        else:
            raise Ineligible(f"unsupported step op {op}")
    if not emitted:
        raise Ineligible("rule never emits")
    pre = None
    if len(chooses) == 2:
        pre = chooses[0]
        n1, t1, leafy1, firstn1 = pre[:4]
        n2, t2, leafy2, firstn2 = chooses[1][:4]
        if firstn1 or leafy1 or t1 == 0 or firstn2 or not leafy2:
            raise Ineligible("two chooses other than choose indep of a "
                             "bucket type, then chooseleaf indep")
        if n1 * n2 > result_max:
            raise Ineligible("two-step numreps exceed result_max")
    numrep, type_, leafy, firstn, tries, leaf_tries = chooses[-1]
    if not leafy and type_ != 0 and pre is None:
        raise Ineligible("choose of a non-device type")
    if firstn and (local_retries != 0 or local_fb != 0):
        # indep has no local-retry paths at all (mapper.c:633-821),
        # so legacy local tunables only disqualify firstn rules
        raise Ineligible("legacy local retry tunables in force")
    if leafy:
        if leaf_tries:
            recurse_tries = leaf_tries
        elif firstn and t.chooseleaf_descend_once:
            recurse_tries = 1
        elif firstn:
            recurse_tries = tries
        else:
            recurse_tries = 1  # indep default (mapper_jax:692)
    else:
        recurse_tries = 1
    if recurse_tries > MAX_RECURSE_TRIES[firstn]:
        raise Ineligible(f"recurse_tries {recurse_tries} unroll bound")

    # every descent is bounded by the levels to its wanted type, not
    # by the tree's depth: a level after every lane has stopped is a
    # draw whose result is thrown away
    depth_inner = 1
    if leafy and type_ > 0:
        depth_inner = max([_depth_to(cmap, i, 0)
                           for i, b in cmap.buckets.items()
                           if b.type == type_] or [1])
    if pre is None:
        depth_outer = _depth_to(cmap, root, type_)
        return Plan(root_idx=root, numrep=numrep, type_=type_,
                    leafy=leafy, firstn=firstn, tries=tries,
                    recurse_tries=recurse_tries, vary_r=vary_r,
                    stable=stable, depth_outer=depth_outer,
                    depth_inner=depth_inner,
                    levels_cut=max(0, _depth_to(cmap, root, 0) - depth_outer))
    mids = [i for i, b in cmap.buckets.items() if b.type == pre[1]]
    return Plan(root_idx=root, numrep=numrep, type_=type_, leafy=True,
                firstn=False, tries=tries, recurse_tries=recurse_tries,
                vary_r=vary_r, stable=stable,
                depth_outer=max([_depth_to(cmap, i, type_)
                                 for i in mids] or [1]),
                depth_inner=depth_inner, pre_numrep=pre[0],
                pre_type=pre[1], pre_tries=pre[4],
                pre_depth=_depth_to(cmap, root, pre[1]),
                # a segment draws its M slots from one bucket (4 of a
                # rack's 25 hosts in crush10k_lrc), so its first round
                # collides far more often than a draw over the whole
                # tree: after one round 42% of that pool's PGs are left
                # to re-run, after two 5% (11% for the EC 8+3 rule)
                first_rounds=2)


def make_single_spec(cmap: CrushMap, ruleno: int, result_max: int,
                     choose_args: Optional[ChooseArgMap] = None,
                     encoded=None, k_tries: int = 8):
    """The unjitted single-x speculative program:
    ``single(arrays, weight, x) -> (result i32[R], len i32)``, and its
    one-round variant ``one_round(arrays, weight, x) -> (result, len,
    more bool)``: every retry loop runs its first round only (a
    two-step rule's, its first two), an indep inner recursion its first
    try, and ``more`` says some loop would have run further.  Where ``more`` is
    false the variant's answer is ``single``'s.

    Raises :class:`Ineligible` when the rule needs the general mapper.
    Returns ``(single, one_round, static, arrays_np)``.
    """
    plan = analyze(cmap, ruleno, result_max)
    static, arrays_np = encoded if encoded is not None \
        else encode_map(cmap, choose_args)
    # the straw2 key from the LN16 table and weight reciprocals: no
    # divide, and best in this flat-shaped program on every backend
    ln16 = jnp.asarray(ln16_table())
    S = static.max_size
    B = static.max_buckets
    R = result_max
    K = max(1, min(k_tries, plan.tries))
    maxdev = static.max_devices
    U64MAX = jnp.uint64(0xFFFFFFFFFFFFFFFF)

    def rounds(cond, body, st, one_round, x, first=1):
        """A retry loop: ``lax.while_loop`` in the full program; in the
        one-round variant its ``first`` rounds unrolled (the first
        always runs, a later one only where the loop would), and
        whether the loop would go on."""
        if one_round:
            st = body(st)
            for _ in range(first - 1):
                go = cond(st)
                st = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(go, a, b), body(st), st)
            return st, cond(st)
        # every carry starts as a value of x's lane: vmap then batches
        # the loop in one pass of its body, not in one more per carry
        # it finds per-lane (over a third of the program's trace time)
        lane = x == x
        st = jax.tree_util.tree_map(lambda v: jnp.where(lane, v, v), st)
        return lax.while_loop(cond, body, st), jnp.bool_(False)

    def straw2_k(A, rw, x, cur, r, pos):
        """straw2 choose (mapper.c:287-362) over a (K,) vector of bucket
        indices; ``pos`` is the choose_args position (the C outpos) and
        ``rw`` the precomputed weight reciprocals (division-free key)."""
        if static.has_choose_args:
            p = jnp.minimum(pos, static.max_positions - 1)
            wts = A.arg_weights[cur, p]
            rec = rw[cur, p]
            ids = A.arg_ids[cur]
        else:
            wts = A.weights[cur]
            rec = rw[cur]
            ids = A.items[cur]
        h = H.crush_hash32_3(jnp.uint32(x), ids.astype(U32),
                             r[:, None].astype(U32))
        h = jnp.where(A.bhash[cur][:, None] == C.CRUSH_HASH_RJENKINS1,
                      h, jnp.uint32(0))
        lane = jnp.arange(S, dtype=I32)
        in_bucket = lane[None, :] < A.size[cur][:, None]
        keys = straw2_key(h, wts, rec, xp=jnp, ln_tab=ln16)
        keys = jnp.where(in_bucket, keys, U64MAX)
        return A.items[cur, jnp.argmin(keys, axis=1)]

    def classify(A, item):
        is_neg = item < 0
        cidx = jnp.clip(-1 - item, 0, B - 1)
        exists = is_neg & ((-1 - item) < B) & (A.alg[cidx] != 0)
        itemtype = jnp.where(is_neg, jnp.where(exists, A.btype[cidx], -1),
                             0)
        return itemtype, cidx, exists

    def is_out(weight, item, x):
        """mapper.c:402-416 over a (K,) item vector."""
        wmax = weight.shape[0]
        w = weight[jnp.clip(item, 0, wmax - 1)]
        h = H.crush_hash32_2(jnp.uint32(x), item.astype(U32)) \
            & jnp.uint32(0xFFFF)
        return jnp.where(item >= wmax, True,
                         jnp.where(w >= 0x10000, False,
                                   jnp.where(w == 0, True, h >= w)))

    def seg_any_eq(vec, n, item):
        """any(vec[i] == item_k for i < n) -> bool (K,)."""
        idx = jnp.arange(vec.shape[0], dtype=I32)
        return jnp.any((idx[None, :] < n) & (vec[None, :] == item[:, None]),
                       axis=1)

    def descend(A, rw, x, start, r, pos, want_type, levels):
        """Lane-parallel pure descents: from bucket indices ``start``
        choose with rank ``r`` per level until an item of ``want_type``
        appears (mapper.c:497-546 minus the retry paths analyze()
        ruled out).  Lane count = len(start) — K speculative tries for
        firstn, numrep slots for indep.
        Returns (status, item, item_bidx), each start-shaped."""
        cur = start
        status = jnp.zeros_like(start)
        fitem = jnp.zeros_like(start)
        fcidx = jnp.zeros_like(start)
        for _ in range(levels):
            item = straw2_k(A, rw, x, cur, r, pos)
            empty = A.size[cur] == 0
            over = item >= maxdev
            itemtype, cidx, exists = classify(A, item)
            want = itemtype == want_type
            new = jnp.where(empty, _FAIL,
                            jnp.where(over, _SKIP,
                                      jnp.where(want, _OK,
                                                jnp.where(exists, _DESC,
                                                          _SKIP))))
            act = status == _DESC
            fitem = jnp.where(act & (new == _OK), item, fitem)
            fcidx = jnp.where(act & (new == _OK), cidx, fcidx)
            cur = jnp.where(act & (new == _DESC), cidx, cur)
            status = jnp.where(act, new, status)
        # levels bounds every terminating descent; anything still
        # descending would not terminate under the C semantics either
        status = jnp.where(status == _DESC, _FAIL, status)
        return status, fitem, fcidx

    def leaf_try(A, rw, weight, x, host_idx, r_in, pos, out2, outpos):
        """One inner try (mapper.c:548-572 recursion, numrep=1): descent
        host->device plus the device checks.  Returns (status, dev)."""
        st, dev, _ = descend(A, rw, x, host_idx, r_in, pos, 0,
                             plan.depth_inner)
        ok = st == _OK
        bad = ok & (seg_any_eq(out2, outpos, dev)
                    | is_out(weight, dev, x))
        return jnp.where(bad, _FAIL, st), dev

    def indep(A, weight, x, rw, start, valid, numrep, type_, leafy,
              tries, levels, one_round):
        """crush_choose_indep (mapper.c:633-821) as dense rounds, over
        segments of ``numrep`` slots: segment s descends from bucket
        index ``start[s]``, and is left out where ``valid[s]`` is
        false.  A segment is one C call, which crush_do_rule hands the
        out pointer ``o+osize`` and outpos 0: its ranks count from its
        first slot, its collisions stay inside it, and its choose_args
        position is 0.  Segments share nothing, so their rounds run side
        by side.  The breadth-first structure is already a batch — every
        open slot's descent vectorizes, with a sequential unrolled
        commit pass that reproduces the reference's in-round collision
        ordering (slot j sees slots < j placed this round).
        Positional: failed slots stay NONE.  Returns ``(out, out2,
        more)``, out (items) and out2 (devices) i32[segments, numrep]."""
        NS, NR = start.shape[0], numrep
        js = jnp.tile(jnp.arange(NR, dtype=I32), NS)
        starts = jnp.repeat(start, NR)
        out = jnp.broadcast_to(
            jnp.where(valid, I32(UNDEF), I32(NONE))[:, None], (NS, NR))
        pos0 = jnp.int32(0)
        none1 = jnp.zeros((1,), I32)
        # the one-round pass tries the inner descent once, and flags a
        # slot that would try again
        inner = 1 if one_round else plan.recurse_tries

        def round_cond(st):
            ftotal, left, out, out2, cut = st
            return jnp.any(left > 0) & (ftotal < tries)

        def round_body(st):
            ftotal, left, out, out2, cut = st
            # straw2-only: no uniform buckets, so the rank multiplier
            # is always numrep (mapper.c:653-660)
            r = (js + numrep * ftotal).astype(I32)
            ost, host, hidx = descend(A, rw, x, starts, r, pos0, type_,
                                      levels)
            found = ost == _OK
            if leafy:
                # inner: rep=slot, parent_r=r, its own rounds under the
                # recurse budget (r_in = slot + r + numrep*ft_in)
                dev = jnp.zeros_like(host)
                got = jnp.zeros(js.shape, bool)
                dead = jnp.zeros(js.shape, bool)
                for t_in in range(inner):
                    # the inner's choose_args position is the SLOT
                    # index (the recursion's outpos param in
                    # mapper_jax.make_choose_indep), vectorized per
                    # lane; no device dedup: the inner indep's collide
                    # segment is its own single slot (there too)
                    ist, d = leaf_try(
                        A, rw, weight, x, hidx,
                        (js + r + numrep * t_in).astype(I32), js, none1,
                        jnp.int32(0))
                    take = found & ~got & ~dead & (ist == _OK)
                    dev = jnp.where(take, d, dev)
                    got = got | take
                    dead = dead | (~got & (ist == _SKIP))
                cand = found & got
                if inner < plan.recurse_tries:
                    cut = cut | jnp.any(found & ~got & ~dead &
                                        (out.reshape(-1) == UNDEF))
            elif type_ > 0:
                # a bucket: no is_out (mapper.c:806 checks devices only)
                dev, cand = host, found
            else:
                dev = host
                cand = found & ~is_out(weight, host, x)

            ost, host, dev, cand = (v.reshape(NS, NR)
                                    for v in (ost, host, dev, cand))
            # sequential commit: the C fills slots in order, so slot
            # j's collision check sees this round's earlier placements
            for j in range(NR):
                slot_open = out[:, j] == UNDEF
                collide = jnp.any(out == host[:, j:j + 1], axis=1)
                place = cand[:, j] & slot_open & ~collide
                term = (ost[:, j] == _SKIP) & slot_open
                out = out.at[:, j].set(jnp.where(
                    place, host[:, j], jnp.where(term, NONE, out[:, j])))
                out2 = out2.at[:, j].set(jnp.where(
                    place, dev[:, j], jnp.where(term, NONE, out2[:, j])))
                left = left - (place | term).astype(I32)
            return ftotal + 1, left, out, out2, cut

        st = (jnp.int32(0), jnp.where(valid, NR, 0).astype(I32), out, out,
              jnp.bool_(False))
        (_, _, out, out2, cut), more = rounds(round_cond, round_body, st,
                                              one_round, x,
                                              plan.first_rounds)
        return (jnp.where(out == UNDEF, NONE, out),
                jnp.where(out2 == UNDEF, NONE, out2), more | cut)

    def single_indep(A, weight, x, rw, one_round):
        """An indep rule: one segment from the take root."""
        out, out2, more = indep(
            A, weight, x, rw, jnp.full((1,), plan.root_idx, I32),
            jnp.ones((1,), bool), plan.numrep, plan.type_, plan.leafy,
            plan.tries, plan.depth_outer, one_round)
        # analyze() guarantees numrep <= result_max
        result = jnp.full(R, NONE, I32).at[:plan.numrep].set(
            (out2 if plan.leafy else out)[0])
        return result, jnp.int32(plan.numrep), more

    def two_step(A, weight, x, rw, one_round):
        """``choose indep N type T`` from the take root, then
        ``chooseleaf indep M`` below each bucket it picked, in order
        (crush_do_rule, mapper.c:967-1040)."""
        mids, _, more = indep(
            A, weight, x, rw, jnp.full((1,), plan.root_idx, I32),
            jnp.ones((1,), bool), plan.pre_numrep, plan.pre_type, False,
            plan.pre_tries, plan.pre_depth, one_round)
        # a hole (NONE; UNDEF where an unfinished first round left a
        # slot, a lane the pass flags) is no bucket: it is skipped
        valid = mids[0] < 0
        _, devs, more2 = indep(
            A, weight, x, rw, jnp.clip(-1 - mids[0], 0, B - 1), valid,
            plan.numrep, plan.type_, True, plan.tries, plan.depth_outer,
            one_round)
        # ... without advancing osize: the next bucket's slots close up
        NR = plan.numrep
        nvalid = valid.astype(I32)
        before = jnp.cumsum(nvalid, dtype=I32) - nvalid
        idx = jnp.arange(R, dtype=I32)
        result = jnp.full(R, NONE, I32)
        for s in range(plan.pre_numrep):
            p = idx - before[s] * NR
            take = valid[s] & (p >= 0) & (p < NR)
            result = jnp.where(take, devs[s, jnp.clip(p, 0, NR - 1)],
                               result)
        return result, jnp.sum(nvalid, dtype=I32) * NR, more | more2

    def program(A, weight, x, one_round):
        # weight reciprocals: unbatched under vmap (depend only on A), so
        # they are computed once per launch, not per lane
        rw = recip64(A.arg_weights, xp=jnp) if static.has_choose_args \
            else recip64(A.weights, xp=jnp)
        if plan.pre_numrep:
            return two_step(A, weight, x, rw, one_round)
        if not plan.firstn:
            return single_indep(A, weight, x, rw, one_round)
        more = jnp.bool_(False)
        out = jnp.full(R, NONE, I32)
        out2 = jnp.full(R, NONE, I32)
        outpos = jnp.int32(0)
        ks = jnp.arange(K, dtype=I32)

        for rep in range(plan.numrep):
            def round_body(st, rep=rep):
                ftotal, done, succ, hostv, devv = st
                r = (rep + ftotal + ks).astype(I32)
                ost, host, hidx = descend(A, rw, x,
                                          jnp.full((K,), plan.root_idx,
                                                   I32),
                                          r, outpos, plan.type_,
                                          plan.depth_outer)
                found = ost == _OK
                collide = found & seg_any_eq(out, outpos, host)

                if plan.leafy and plan.type_ > 0:
                    # chooseleaf recursion, unrolled over its try budget
                    sub_r = (r >> (plan.vary_r - 1)) if plan.vary_r \
                        else jnp.zeros((K,), I32)
                    rep_in = jnp.int32(0) if plan.stable else outpos
                    dev = jnp.zeros((K,), I32)
                    got = jnp.zeros((K,), bool)
                    dead = jnp.zeros((K,), bool)
                    for j in range(plan.recurse_tries):
                        ist, d = leaf_try(A, rw, weight, x, hidx,
                                          (rep_in + sub_r + j).astype(I32),
                                          outpos, out2, outpos)
                        take = found & ~got & ~dead & (ist == _OK)
                        dev = jnp.where(take, d, dev)
                        got = got | take
                        dead = dead | (~got & (ist == _SKIP))
                    live = found & ~collide & got
                else:
                    # direct device choose (type 0): out-check the item
                    dev = host
                    live = found & ~collide & ~is_out(weight, host, x)

                eff = jnp.where(found & ~live, _FAIL, ost)
                # tries beyond the rep's remaining budget read as give-up
                eff = jnp.where(ftotal + ks < plan.tries, eff, _SKIP)
                pick = jnp.argmax(eff != _FAIL)
                any_pick = jnp.any(eff != _FAIL)
                win = any_pick & (eff[pick] == _OK)
                return (ftotal + K, any_pick, succ | win,
                        jnp.where(win, host[pick], hostv),
                        jnp.where(win, dev[pick], devv))

            def round_cond(st):
                return (~st[1]) & (st[0] < plan.tries)

            st = (jnp.int32(0), jnp.bool_(False), jnp.bool_(False),
                  jnp.int32(0), jnp.int32(0))
            (_, _, succ, host, dev), rep_more = rounds(
                round_cond, round_body, st, one_round, x)
            more = more | rep_more
            slot = jnp.clip(outpos, 0, R - 1)
            out = jnp.where(succ, out.at[slot].set(host), out)
            out2 = jnp.where(succ, out2.at[slot].set(dev), out2)
            outpos = outpos + succ.astype(I32)

        result = out2 if plan.leafy else out
        idx = jnp.arange(R, dtype=I32)
        result = jnp.where(idx < outpos, result, NONE)
        return result, outpos, more

    def single(A, weight, x):
        return program(A, weight, x, False)[:2]

    def one_round(A, weight, x):
        return program(A, weight, x, True)

    return single, one_round, static, arrays_np


# stragglers re-run in chunks of N // STRAGGLER_CHUNKS lanes; batches
# under STRAGGLER_MIN_LANES run the plain loops, since chunks under 64
# lanes save little and the second copy of the program doubles the
# compile
STRAGGLER_CHUNKS = 64
STRAGGLER_MIN_LANES = 64 * STRAGGLER_CHUNKS


def map_stragglers(single, one_round, A, weight, xs):
    """Map a batch ``xs`` (u32[N]) with the speculative program, for one
    round of retries per lane and then the full retry loops on the
    stragglers alone.

    Under ``vmap`` a retry ``while_loop`` runs every lane for as many
    rounds as the slowest lane needs.  Here every lane runs
    ``one_round``; the lanes it flags are gathered, in order, into
    chunks of ``N // STRAGGLER_CHUNKS`` lanes, ``single`` re-runs each
    chunk with its loops (for as many rounds as that chunk's slowest
    lane needs), and the answers are scattered back.  One chunk size
    keeps one copy of ``single`` in the program; any count of
    stragglers, up to all ``N``, stays exact.

    Returns ``(result i32[N,R], len i32[N], stats i32[2])``, ``stats``
    being the stragglers and the chunks re-run, or None where ``N`` is
    under :data:`STRAGGLER_MIN_LANES` and the plain loops ran.
    """
    n = xs.shape[0]
    full = jax.vmap(single, in_axes=(None, None, 0))
    if n < STRAGGLER_MIN_LANES:
        return full(A, weight, xs) + (None,)
    cap = n // STRAGGLER_CHUNKS
    res, lens, more = jax.vmap(one_round, in_axes=(None, None, 0))(
        A, weight, xs)
    flagged = jnp.sum(more, dtype=I32)
    # the stragglers' lanes first, in order (a stable sort: the TPU
    # compiler cannot fit jnp.nonzero's cumsum over 2^18 lanes)
    order = jnp.argsort(~more, stable=True).astype(I32)
    order = jnp.pad(order, (0, -n % cap))

    def chunk(st):
        c, res, lens = st
        lane = lax.dynamic_slice(order, (c * cap,), (cap,))
        pad = c * cap + jnp.arange(cap, dtype=I32) >= flagged
        # pad lanes repeat the chunk's first straggler, so they add no
        # round, and write nothing back
        r, ln = full(A, weight, xs[jnp.where(pad, lane[0], lane)])
        idx = jnp.where(pad, n, lane)
        return (c + 1, res.at[idx].set(r, mode="drop"),
                lens.at[idx].set(ln, mode="drop"))

    chunks, res, lens = lax.while_loop(
        lambda st: st[0] * cap < flagged, chunk, (jnp.int32(0), res, lens))
    return res, lens, jnp.stack([flagged, chunks])


def build_spec_rule_fn(cmap: CrushMap, ruleno: int, result_max: int,
                       choose_args: Optional[ChooseArgMap] = None,
                       encoded=None, k_tries: int = 8):
    """Compile one eligible rule into a jitted batched speculative mapper
    (:func:`map_stragglers`) with the same signature as
    ``mapper_jax.build_rule_fn``."""
    single, one_round, static, arrays_np = make_single_spec(
        cmap, ruleno, result_max, choose_args, encoded, k_tries)

    def map_batch(A, weight, xs):
        return map_stragglers(single, one_round, A, weight, xs)[:2]

    return jax.jit(map_batch), static, arrays_np


"""Plain CRUSH: the placement a cell's answers are compared with.

A scalar transcription of Ceph's ``src/crush/mapper.c``
(``crush_do_rule``, ``crush_choose_firstn``, ``crush_choose_indep``,
``bucket_straw2_choose``, ``is_out``), ``src/crush/hash.c``
(rjenkins1) and ``crush_ln``.  It knows straw2 buckets only, the one
bucket kind the benchmark's maps use, and refuses any other.  Nothing
here imports the system under test; the log tables are the published
``crush_ln_table.h`` constants, kept as data beside this file.
"""

from __future__ import annotations

import json
import pathlib
from typing import Dict, List, Sequence

ITEM_NONE = 0x7FFFFFFF
ITEM_UNDEF = 0x7FFFFFFE
ALG_STRAW2 = 5

OP_TAKE = 1
OP_CHOOSE_FIRSTN = 2
OP_CHOOSE_INDEP = 3
OP_EMIT = 4
OP_CHOOSELEAF_FIRSTN = 6
OP_CHOOSELEAF_INDEP = 7

M32 = 0xFFFFFFFF
HASH_SEED = 1315423911

_TABLES = json.loads((pathlib.Path(__file__).with_name(
    "crush_ln_tables.json")).read_text())
RH_LH = _TABLES["RH_LH_tbl"]
LL = _TABLES["LL_tbl"]


def _mix(a, b, c):
    a = (a - b - c) & M32
    a ^= c >> 13
    b = (b - c - a) & M32
    b ^= (a << 8) & M32
    c = (c - a - b) & M32
    c ^= b >> 13
    a = (a - b - c) & M32
    a ^= c >> 12
    b = (b - c - a) & M32
    b ^= (a << 16) & M32
    c = (c - a - b) & M32
    c ^= b >> 5
    a = (a - b - c) & M32
    a ^= c >> 3
    b = (b - c - a) & M32
    b ^= (a << 10) & M32
    c = (c - a - b) & M32
    c ^= b >> 15
    return a, b, c


def hash32_2(a: int, b: int) -> int:
    """crush_hash32_rjenkins1_2."""
    a &= M32
    b &= M32
    h = HASH_SEED ^ a ^ b
    x, y = 231232, 1232
    a, b, h = _mix(a, b, h)
    x, a, h = _mix(x, a, h)
    b, y, h = _mix(b, y, h)
    return h


def hash32_3(a: int, b: int, c: int) -> int:
    """crush_hash32_rjenkins1_3."""
    a &= M32
    b &= M32
    c &= M32
    h = HASH_SEED ^ a ^ b ^ c
    x, y = 231232, 1232
    a, b, h = _mix(a, b, h)
    c, x, h = _mix(c, x, h)
    y, a, h = _mix(y, a, h)
    b, x, h = _mix(b, x, h)
    y, c, h = _mix(y, c, h)
    return h


def crush_ln(xin: int) -> int:
    """2^44 * log2(xin + 1), fixed point, as mapper.c computes it."""
    x = xin + 1
    iexpon = 15
    if not x & 0x18000:
        bits = 16 - (x & 0x1FFFF).bit_length()
        x <<= bits
        iexpon = 15 - bits
    index1 = (x >> 8) << 1
    rh = RH_LH[index1 - 256]
    lh = RH_LH[index1 + 1 - 256]
    xl64 = (x * rh) >> 48
    result = iexpon << 44
    lh += LL[xl64 & 0xFF]
    return result + (lh >> 4)


_LN: List[int] = []


def _ln_minus_one(u: int) -> int:
    """crush_ln(u) - 2^48 for a 16-bit u (the straw2 draw's numerator),
    tabulated on first use."""
    if not _LN:
        _LN.extend(crush_ln(v) - 0x1000000000000 for v in range(1 << 16))
    return _LN[u]


class Map:
    """A CRUSH map in the dictionary form of ``crushtool`` JSON."""

    def __init__(self, d: Dict):
        self.max_devices = int(d["max_devices"])
        self.max_buckets = int(d["max_buckets"])
        t = d["tunables"]
        if t.get("choose_local_tries", 0) or \
                t.get("choose_local_fallback_tries", 0):
            raise ValueError("legacy local retries are not transcribed")
        self.total_tries = int(t["choose_total_tries"])
        self.descend_once = int(t["chooseleaf_descend_once"])
        self.vary_r = int(t["chooseleaf_vary_r"])
        self.stable = int(t["chooseleaf_stable"])
        self.buckets: Dict[int, Dict] = {}
        for b in d["buckets"]:
            if b["alg"] != ALG_STRAW2 or b.get("hash", 0) != 0:
                raise ValueError(f"bucket {b['id']}: only straw2 with "
                                 f"rjenkins1 is transcribed")
            self.buckets[int(b["id"])] = {
                "type": int(b["type"]),
                "items": [int(i) for i in b["items"]],
                "weights": [int(w) for w in b["item_weights"]]}
        self.rules = {int(r["ruleno"]): [tuple(s) for s in r["steps"]]
                      for r in d["rules"]}

    # -- mapper.c -------------------------------------------------------
    def straw2(self, bid: int, x: int, r: int) -> int:
        b = self.buckets[bid]
        items, weights = b["items"], b["weights"]
        high, high_draw = 0, 0
        for i, item in enumerate(items):
            w = weights[i]
            if w:
                ln = _ln_minus_one(hash32_3(x, item, r) & 0xFFFF)
                draw = -((-ln) // w)      # C division truncates to 0
            else:
                draw = -(1 << 63)
            if i == 0 or draw > high_draw:
                high, high_draw = i, draw
        return items[high]

    def _type_of(self, item: int) -> int:
        return self.buckets[item]["type"] if item < 0 else 0

    @staticmethod
    def is_out(weight: Sequence[int], item: int, x: int) -> bool:
        if item >= len(weight):
            return True
        w = weight[item]
        if w >= 0x10000:
            return False
        if w == 0:
            return True
        return (hash32_2(x, item) & 0xFFFF) >= w

    def choose_firstn(self, bucket: int, weight, x: int, numrep: int,
                      type_: int, out: List[int], outpos: int,
                      out_size: int, tries: int, recurse_tries: int,
                      recurse_to_leaf: bool, out2, parent_r: int) -> int:
        count = out_size
        rep = 0 if self.stable else outpos
        while rep < numrep and count > 0:
            ftotal = 0
            skip_rep = False
            item = 0
            while True:                              # retry descent
                retry_descent = False
                inb = bucket
                flocal = 0
                while True:                          # retry bucket
                    collide = False
                    retry_bucket = False
                    r = rep + parent_r + ftotal
                    if not self.buckets[inb]["items"]:
                        reject = True
                    else:
                        item = self.straw2(inb, x, r)
                        if item >= self.max_devices:
                            skip_rep = True
                            break
                        itemtype = self._type_of(item)
                        if itemtype != type_:
                            if item >= 0 or item not in self.buckets:
                                skip_rep = True
                                break
                            inb = item
                            retry_bucket = True
                            continue
                        collide = item in out[:outpos]
                        reject = False
                        if not collide and recurse_to_leaf:
                            if item < 0:
                                sub_r = (r >> (self.vary_r - 1)
                                         if self.vary_r else 0)
                                got = self.choose_firstn(
                                    item, weight, x,
                                    1 if self.stable else outpos + 1, 0,
                                    out2, outpos, count, recurse_tries,
                                    0, False, None, sub_r)
                                if got <= outpos:
                                    reject = True
                            else:
                                out2[outpos] = item
                        if not reject and not collide and itemtype == 0:
                            reject = self.is_out(weight, item, x)
                    if reject or collide:
                        ftotal += 1
                        flocal += 1
                        if ftotal < tries:
                            retry_descent = True
                        else:
                            skip_rep = True
                    if not retry_bucket:
                        break
                if not retry_descent:
                    break
            if not skip_rep:
                out[outpos] = item
                outpos += 1
                count -= 1
            rep += 1
        return outpos

    def choose_indep(self, bucket: int, weight, x: int, left: int,
                     numrep: int, type_: int, out: List[int],
                     outpos: int, tries: int, recurse_tries: int,
                     recurse_to_leaf: bool, out2, parent_r: int) -> None:
        endpos = outpos + left
        for rep in range(outpos, endpos):
            out[rep] = ITEM_UNDEF
            if out2 is not None:
                out2[rep] = ITEM_UNDEF
        ftotal = 0
        while left > 0 and ftotal < tries:
            for rep in range(outpos, endpos):
                if out[rep] != ITEM_UNDEF:
                    continue
                inb = bucket
                while True:
                    r = rep + parent_r + numrep * ftotal
                    if not self.buckets[inb]["items"]:
                        break
                    item = self.straw2(inb, x, r)
                    if item >= self.max_devices:
                        out[rep] = ITEM_NONE
                        if out2 is not None:
                            out2[rep] = ITEM_NONE
                        left -= 1
                        break
                    itemtype = self._type_of(item)
                    if itemtype != type_:
                        if item >= 0 or item not in self.buckets:
                            out[rep] = ITEM_NONE
                            if out2 is not None:
                                out2[rep] = ITEM_NONE
                            left -= 1
                            break
                        inb = item
                        continue
                    if item in out[outpos:endpos]:
                        break
                    if recurse_to_leaf:
                        if item < 0:
                            self.choose_indep(item, weight, x, 1, numrep,
                                              0, out2, rep, recurse_tries,
                                              0, False, None, r)
                            if out2[rep] == ITEM_NONE:
                                break
                        else:
                            out2[rep] = item
                    if itemtype == 0 and self.is_out(weight, item, x):
                        break
                    out[rep] = item
                    left -= 1
                    break
            ftotal += 1
        for rep in range(outpos, endpos):
            if out[rep] == ITEM_UNDEF:
                out[rep] = ITEM_NONE
            if out2 is not None and out2[rep] == ITEM_UNDEF:
                out2[rep] = ITEM_NONE

    def do_rule(self, ruleno: int, x: int, result_max: int,
                weight: Sequence[int]) -> List[int]:
        """crush_do_rule: the OSDs for input ``x``."""
        x &= M32
        if x & 0x80000000:                 # the C mapper takes int x
            x -= 1 << 32
        choose_tries = self.total_tries + 1
        w: List[int] = []
        result: List[int] = []
        for op, arg1, arg2 in self.rules[ruleno]:
            if op == OP_TAKE:
                if 0 <= arg1 < self.max_devices or arg1 in self.buckets:
                    w = [arg1]
            elif op in (OP_CHOOSE_FIRSTN, OP_CHOOSE_INDEP,
                        OP_CHOOSELEAF_FIRSTN, OP_CHOOSELEAF_INDEP):
                firstn = op in (OP_CHOOSE_FIRSTN, OP_CHOOSELEAF_FIRSTN)
                leaf = op in (OP_CHOOSELEAF_FIRSTN, OP_CHOOSELEAF_INDEP)
                o = [0] * result_max
                c = [0] * result_max
                osize = 0
                for wi in w:
                    numrep = arg1
                    if numrep <= 0:
                        numrep += result_max
                        if numrep <= 0:
                            continue
                    if wi not in self.buckets:
                        continue
                    if firstn:
                        recurse_tries = 1 if self.descend_once \
                            else choose_tries
                        osize = self.choose_firstn(
                            wi, weight, x, numrep, arg2, o, osize,
                            result_max - osize, choose_tries,
                            recurse_tries, leaf, c, 0)
                    else:
                        out_size = min(numrep, result_max - osize)
                        self.choose_indep(wi, weight, x, out_size, numrep,
                                          arg2, o, osize, choose_tries, 1,
                                          leaf, c, 0)
                        osize += out_size
                w = (c if leaf else o)[:osize]
            elif op == OP_EMIT:
                for item in w:
                    if len(result) < result_max:
                        result.append(item)
                w = []
            else:
                raise ValueError(f"rule step {op} is not transcribed")
        return result

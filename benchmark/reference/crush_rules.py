"""Plain CRUSH for rules with retry-budget steps and more than one
choose step: the placement the LRC cell's answers are compared with.

``crush.Map`` transcribes ``crush_do_rule`` for one ``take / choose /
emit`` block.  This ``Map`` keeps its buckets, hashes and choose
functions and transcribes the rest of Ceph's ``src/crush/mapper.c``
``crush_do_rule`` (:878-1083) that such rules reach:

- ``set_choose_tries`` and ``set_chooseleaf_tries`` steps, and the
  inner budget an indep choose then hands its recursion
  (``choose_leaf_tries`` where set, else 1);
- a choose step over several buckets of the working vector: each call
  gets the out pointers ``o+osize`` and ``c+osize`` with outpos 0, so
  its ranks count from its own first slot and its collisions stay in
  its own slots; a working-vector entry that is no bucket (a hole,
  ``ITEM_NONE``) is skipped without advancing ``osize``.

The other ``set_*`` steps are not transcribed and are refused.  Nothing
here imports the system under test.
"""

from __future__ import annotations

from typing import List, Sequence

from . import crush as _crush
from .crush import (M32, OP_CHOOSE_FIRSTN, OP_CHOOSE_INDEP,
                    OP_CHOOSELEAF_FIRSTN, OP_CHOOSELEAF_INDEP, OP_EMIT,
                    OP_TAKE)

OP_SET_CHOOSE_TRIES = 8
OP_SET_CHOOSELEAF_TRIES = 9

CHOOSES = (OP_CHOOSE_FIRSTN, OP_CHOOSE_INDEP, OP_CHOOSELEAF_FIRSTN,
           OP_CHOOSELEAF_INDEP)


class Map(_crush.Map):
    def do_rule(self, ruleno: int, x: int, result_max: int,
                weight: Sequence[int]) -> List[int]:
        """crush_do_rule: the OSDs for input ``x``."""
        x &= M32
        if x & 0x80000000:                 # the C mapper takes int x
            x -= 1 << 32
        choose_tries = self.total_tries + 1
        choose_leaf_tries = 0
        w: List[int] = []
        result: List[int] = []
        for op, arg1, arg2 in self.rules[ruleno]:
            if op == OP_TAKE:
                if 0 <= arg1 < self.max_devices or arg1 in self.buckets:
                    w = [arg1]
            elif op == OP_SET_CHOOSE_TRIES:
                if arg1 > 0:
                    choose_tries = arg1
            elif op == OP_SET_CHOOSELEAF_TRIES:
                if arg1 > 0:
                    choose_leaf_tries = arg1
            elif op in CHOOSES:
                firstn = op in (OP_CHOOSE_FIRSTN, OP_CHOOSELEAF_FIRSTN)
                leaf = op in (OP_CHOOSELEAF_FIRSTN, OP_CHOOSELEAF_INDEP)
                o: List[int] = []
                c: List[int] = []
                for wi in w:
                    numrep = arg1 if arg1 > 0 else arg1 + result_max
                    if numrep <= 0 or wi not in self.buckets:
                        continue
                    room = result_max - len(o)
                    seg, seg2 = [0] * room, [0] * room
                    if firstn:
                        if choose_leaf_tries:
                            recurse_tries = choose_leaf_tries
                        elif self.descend_once:
                            recurse_tries = 1
                        else:
                            recurse_tries = choose_tries
                        n = self.choose_firstn(
                            wi, weight, x, numrep, arg2, seg, 0, room,
                            choose_tries, recurse_tries, leaf, seg2, 0)
                    else:
                        n = min(numrep, room)
                        self.choose_indep(
                            wi, weight, x, n, numrep, arg2, seg, 0,
                            choose_tries, choose_leaf_tries or 1, leaf,
                            seg2, 0)
                    o += seg[:n]
                    c += seg2[:n]
                w = c if leaf else o
            elif op == OP_EMIT:
                for item in w:
                    if len(result) < result_max:
                        result.append(item)
                w = []
            else:
                raise ValueError(f"rule step {op} is not transcribed")
        return result

"""Builds a configuration's CRUSH map whose rules set retry budgets.

``crushmap.build_map`` reads the take, choose and emit steps; this
reads ``["set_choose_tries", n]`` and ``["set_chooseleaf_tries", n]``
too, the steps Ceph's erasure-code plugins put before the take.
"""

from __future__ import annotations

from typing import Dict

from .crushmap import OPS, TYPES, build_map

SET_OPS = {"set_choose_tries": 8, "set_chooseleaf_tries": 9}


def build_map_with_steps(spec: Dict) -> Dict:
    """``spec``: the ``crush`` group of a configuration file."""
    d = build_map(dict(spec, rules=[]))
    root = d["buckets"][-1]["id"]
    rules = []
    for ruleno, steps in enumerate(spec["rules"]):
        out = []
        for step in steps:
            op = step[0]
            if op in SET_OPS:
                out.append([SET_OPS[op], int(step[1]), 0])
            elif op == "take":
                out.append([OPS[op], root, 0])
            elif op == "emit":
                out.append([OPS[op], 0, 0])
            else:
                out.append([OPS[op], int(step[1]), TYPES[step[2]]])
        rules.append({"ruleno": ruleno, "steps": out})
    return dict(d, rules=rules, max_rules=len(rules))

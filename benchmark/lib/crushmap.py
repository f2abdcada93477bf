"""Builds a configuration's CRUSH map from its sizes.

A three-level straw2 hierarchy (root, racks, hosts, OSDs), laid out the
way ``crushtool --build`` numbers it: each rack's hosts first, then the
rack, then the root.  The result is the dictionary form that both the
system under test (``CrushMap.from_dict``) and the plain reference
(``benchmark.reference.crush.Map``) read.
"""

from __future__ import annotations

from typing import Dict

ALG = {"straw2": 5}
TYPES = {"osd": 0, "host": 1, "rack": 2, "root": 3}
OPS = {"take": 1, "choose_firstn": 2, "choose_indep": 3, "emit": 4,
       "chooseleaf_firstn": 6, "chooseleaf_indep": 7}


def build_map(spec: Dict) -> Dict:
    """``spec``: the ``crush`` group of a configuration file."""
    alg = ALG[spec["alg"]]
    per_host = int(spec["osds_per_host"])
    per_rack = int(spec["hosts_per_rack"])
    racks = int(spec["racks"])
    w_osd = int(spec["osd_weight"])
    buckets = []
    next_id = -1
    osd = 0
    rack_ids = []

    def bucket(bid, type_, items, weights):
        return {"id": bid, "alg": alg, "hash": 0, "type": type_,
                "weight": sum(weights), "size": len(items),
                "items": items, "item_weights": weights}

    for _r in range(racks):
        hosts = []
        for _h in range(per_rack):
            items = list(range(osd, osd + per_host))
            osd += per_host
            buckets.append(bucket(next_id, TYPES["host"], items,
                                  [w_osd] * per_host))
            hosts.append(next_id)
            next_id -= 1
        buckets.append(bucket(next_id, TYPES["rack"], hosts,
                              [w_osd * per_host] * per_rack))
        rack_ids.append(next_id)
        next_id -= 1
    root = next_id
    buckets.append(bucket(root, TYPES["root"], rack_ids,
                          [w_osd * per_host * per_rack] * racks))
    rules = []
    for ruleno, steps in enumerate(spec["rules"]):
        out = []
        for step in steps:
            op = step[0]
            if op == "take":
                out.append([OPS[op], root, 0])
            elif op == "emit":
                out.append([OPS[op], 0, 0])
            else:
                out.append([OPS[op], int(step[1]), TYPES[step[2]]])
        rules.append({"ruleno": ruleno, "steps": out})
    return {"max_devices": osd, "max_buckets": int(spec["max_buckets"]),
            "max_rules": len(rules), "tunables": dict(spec["tunables"]),
            "buckets": buckets, "rules": rules}

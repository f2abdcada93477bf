"""Plain ``OSDMap::pg_to_up_acting_osds`` for pools without exception
tables: pps seed, CRUSH, nonexistent and down OSDs removed (replicated
pools compact, erasure pools keep positions with holes), first
remaining OSD as primary, acting = up.  Ceph ``src/osd/OSDMap.cc``
(``_pg_to_raw_osds``, ``_remove_nonexistent_osds``,
``_raw_to_up_osds``, ``_pick_primary``) and ``src/osd/osd_types.cc``
(``pg_pool_t::raw_pg_to_pps``, FLAG_HASHPSPOOL set)."""

from __future__ import annotations

from typing import List, Sequence, Tuple

from .crush import ITEM_NONE, Map, hash32_2

EXISTS = 1
UP = 2


def calc_mask(n: int) -> int:
    return (1 << (n - 1).bit_length()) - 1 if n > 1 else 0


def stable_mod(x: int, b: int, bmask: int) -> int:
    return x & bmask if (x & bmask) < b else x & (bmask >> 1)


def pps(pool_id: int, ps: int, pgp_num: int) -> int:
    return hash32_2(stable_mod(ps, pgp_num, calc_mask(pgp_num)), pool_id)


def up_acting(cmap: Map, pool: dict, ps: int, weight: Sequence[int],
              state: Sequence[int]) -> Tuple[List[int], int,
                                             List[int], int]:
    """(up, up_primary, acting, acting_primary) of PG ``ps``.
    ``pool``: id, type ("replicated" or "erasure"), size, pg_num,
    crush_rule."""
    n = len(state)

    def has(o: int, bits: int) -> bool:
        return 0 <= o < n and (state[o] & bits) == bits

    x = pps(pool["id"], ps, pool["pg_num"])
    raw = cmap.do_rule(pool["crush_rule"], x, pool["size"], weight)
    if pool["type"] == "replicated":
        up = [o for o in raw if has(o, EXISTS | UP)]
    else:
        up = [o if has(o, EXISTS | UP) else ITEM_NONE for o in raw]
    primary = next((o for o in up if o != ITEM_NONE), -1)
    return up, primary, list(up), primary

"""Critical-path stage fold of a request's span tree.

Copied from the program's ``ceph_tpu/common/attribution.py``
(``stage_of``, ``fold_tree``, ``fold_spans``) so that later changes to
the program cannot move the yardstick: every instant of a client op's
root span is charged to the stage of the deepest span covering it, and
the dispatch-queue wait tagged ``q_wait`` on ``handle:*`` spans is
carved out of the messenger stage.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

STAGES: Tuple[str, ...] = ("client", "messenger", "dispatch",
                           "osd_op", "encode", "wal", "fanout",
                           "unattributed")

UNATTRIBUTED = "unattributed"


def stage_of(name: Optional[str]) -> Optional[str]:
    """Stage for one span name; None when the table cannot place it
    (the fold then charges ``unattributed``)."""
    if not name:
        return None
    if name.startswith("client."):
        return "client"
    if name == "call:shard_write":
        return "fanout"
    if name == "ec.encode":
        return "encode"
    if name == "store.commit":
        return "wal"
    if name.startswith(("call:", "send:")):
        return "messenger"
    if name.startswith("handle:"):
        return "osd_op"
    return None


def _interval(span: Dict) -> Optional[Tuple[float, float]]:
    start = span.get("start")
    dur = span.get("duration")
    if not isinstance(start, (int, float)) or \
            not isinstance(dur, (int, float)) or dur < 0:
        return None
    return float(start), float(start) + float(dur)


def fold_tree(root: Dict) -> Optional[Dict]:
    """Fold one reassembled trace tree (a ``telemetry.trace_tree``
    node: span dict + ``children`` list) into a per-stage breakdown.

    Returns ``{"trace_id", "root", "total", "stages": {stage: s}}``
    with ``sum(stages.values()) == total`` (to float rounding), or
    None for a root with no usable timing."""
    ri = _interval(root)
    if ri is None or not root.get("finished", True):
        return None
    r0, r1 = ri
    total = r1 - r0
    stages: Dict[str, float] = {s: 0.0 for s in STAGES}
    if total <= 0:
        return {"trace_id": root.get("trace_id"),
                "root": root.get("name"), "total": 0.0,
                "stages": stages}

    # flatten to (depth, clip0, clip1, span); clipping to the root
    # interval bounds cross-daemon clock skew
    flat: List[Tuple[int, float, float, Dict]] = []

    def walk(node: Dict, depth: int) -> None:
        iv = _interval(node)
        if iv is not None:
            a, b = max(iv[0], r0), min(iv[1], r1)
            if b > a:
                flat.append((depth, a, b, node))
        for child in node.get("children", []):
            walk(child, depth + 1)

    walk(root, 0)

    # elementary segments between all span boundaries: each is charged
    # to the DEEPEST covering span (ties break toward the later
    # start — parallel siblings at equal depth share a stage anyway)
    bounds = sorted({t for _d, a, b, _s in flat for t in (a, b)})
    q_wait_total = 0.0
    for seg0, seg1 in zip(bounds, bounds[1:]):
        mid = (seg0 + seg1) / 2
        best = None
        for depth, a, b, span in flat:
            if a <= mid < b and (best is None or depth >= best[0]):
                best = (depth, span)
        st = stage_of(best[1].get("name")) if best else None
        stages[st if st in STAGES else UNATTRIBUTED] += seg1 - seg0

    # the dispatch-queue carve: handle spans tag the frame-receipt ->
    # handler-start wait (q_wait), which wall-clock-wise sits inside
    # the caller's messenger time.  Move it (bounded by what the
    # messenger stage actually holds — parallel fan-out q_waits can
    # overlap) so queueing is visible as its own stage.
    for _d, _a, _b, span in flat:
        name = span.get("name") or ""
        if name.startswith("handle:"):
            qw = (span.get("tags") or {}).get("q_wait")
            if isinstance(qw, (int, float)) and qw > 0:
                q_wait_total += float(qw)
    moved = min(q_wait_total, stages["messenger"])
    stages["messenger"] -= moved
    stages["dispatch"] += moved

    # float-rounding residual (the charge loop covers the root
    # interval exactly, so this is noise-scale) lands explicit
    residual = total - sum(stages.values())
    if residual > 0:
        stages[UNATTRIBUTED] += residual
    return {"trace_id": root.get("trace_id"),
            "root": root.get("name"), "total": total,
            "stages": stages}


def fold_spans(spans: Iterable[Dict],
               root_prefix: str = "client.") -> List[Dict]:
    """Group a flat span list (any number of daemons) by trace, parent
    into trees, and fold every finished root whose name matches
    ``root_prefix``.  Self-contained (no telemetry import) so the
    bench worker can fold in-process."""
    by_trace: Dict[str, List[Dict]] = {}
    for s in spans:
        tid = s.get("trace_id")
        if tid:
            by_trace.setdefault(tid, []).append(s)
    out: List[Dict] = []
    for tid, mine in by_trace.items():
        index: Dict[str, Dict] = {}
        for s in mine:
            index.setdefault(s["span_id"], dict(s, children=[]))
        roots: List[Dict] = []
        for node in index.values():
            parent = node.get("parent_id")
            if parent and parent in index:
                index[parent]["children"].append(node)
            else:
                roots.append(node)
        for root in roots:
            name = root.get("name") or ""
            if not name.startswith(root_prefix):
                continue
            if not root.get("finished", True):
                continue
            fold = fold_tree(root)
            if fold is not None:
                out.append(fold)
    return out

"""The EC write's shard pushes, split into five parts, from the
program's own spans.

The PG primary sends each of an object's shards but its own to the OSD
that holds it, one ``call:shard_write`` span C per push, on the
primary.  C logs the event ``sent`` as the encoded request frame is
handed to the socket, before the write itself; the holder's ``handle:shard_write`` span H is C's child
(``H.parent_id == C.span_id``) and carries ``q_wait``, the seconds from
the frame's receipt to the handler's start.  Every sampled span stamps
``t0_ns``/``t1_ns`` on ``time.perf_counter_ns()``, the clock of the
benchmark's window.  Each push then splits into parts that sum to C's
duration:

    send      C start -> sent: session lock, frame encode, wait for
              the socket's writer
    transit   sent -> receipt (H start - q_wait): socket write, the
              holder's reader thread
    queue     q_wait: the peer's dispatch queue
    handler   H start -> H end: op scheduler, PG lock, store commit
    reply     H end -> C end: reply frame, waking the waiter

The spans are read from the process's span rings (the program's
``ceph_tpu.common.tracing.rings``), which outlive the cluster's
daemons.  A program without them, or without the ns stamps, reads as
nothing.
"""

from __future__ import annotations

from typing import Dict, List, Optional

PARTS = ("send", "transit", "queue", "handler", "reply")

PUSH = "call:shard_write"
HANDLER = "handle:shard_write"


def _spans() -> Optional[List[Dict]]:
    """The finished push and handler spans in the process's rings; None
    when the program keeps no such rings or any ring evicted spans."""
    try:
        from ceph_tpu.common.tracing import rings
    except ImportError:
        return None
    found = rings()
    if any(evicted for _svc, _ring, evicted in found):
        return None
    return [s.dump() for _svc, ring, _ev in found for s in list(ring)
            if s.name in (PUSH, HANDLER)]


def split(run) -> Optional[Dict[str, float]]:
    """Nanoseconds of each part, summed over every push that started in
    the window (its start to the last acknowledgement).  None when the
    window holds no push or a push cannot be split."""
    done = run.window.done()
    spans = _spans()
    if not done or spans is None:
        return None
    lo = run.window.t0 * 1e9
    hi = max(o.t_done for o in done) * 1e9
    handlers = {s["parent_id"]: s for s in spans if s["name"] == HANDLER}
    parts = dict.fromkeys(PARTS, 0.0)
    pushes = 0
    for c in spans:
        if c["name"] != PUSH or c.get("t0_ns") is None or \
                not lo <= c["t0_ns"] <= hi:
            continue
        h = handlers.get(c["span_id"])
        sent = [e["t_ns"] for e in c["events"] if e["event"] == "sent"]
        if h is None or not sent or h.get("t0_ns") is None or \
                "q_wait" not in h["tags"]:
            return None
        # a frame written again (a reconnect) logs ``sent`` again: the
        # last write is the one the holder answered
        receipt = h["t0_ns"] - h["tags"]["q_wait"] * 1e9
        parts["send"] += sent[-1] - c["t0_ns"]
        parts["transit"] += receipt - sent[-1]
        parts["queue"] += h["t0_ns"] - receipt
        parts["handler"] += h["t1_ns"] - h["t0_ns"]
        parts["reply"] += c["t1_ns"] - h["t1_ns"]
        pushes += 1
    return parts if pushes else None


def share(run, part: str) -> Optional[float]:
    """Percent of the window's summed push time spent in ``part``."""
    parts = split(run)
    if parts is None:
        return None
    total = sum(parts.values())
    return 100.0 * parts[part] / total if total > 0 else None

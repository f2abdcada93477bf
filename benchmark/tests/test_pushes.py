"""The split of the EC write's shard pushes (``lib/pushes.py``): on
synthetic span rings, the five shares sum to 100, pushes outside the
window are left out, and a push that cannot be split, an evicted ring
or an empty window reads as nothing; one tiny traced write run on the
CPU reads all five."""

import json
import time

import pytest

from benchmark.lib import harness, pushes
from benchmark.lib.spec import BENCH, Cell
from benchmark.lib.stats import Op, Window

METRICS = {"send": "msgr.push_send_share.write",
           "transit": "msgr.push_transit_share.write",
           "queue": "msgr.push_queue_share.write",
           "handler": "osd.push_handler_share.write",
           "reply": "msgr.push_reply_share.write"}

T0 = 1_000.0            # the window's start, seconds on perf_counter


class FakeSpan:
    def __init__(self, name, span_id, parent_id=None, t0_ns=None,
                 t1_ns=None, events=(), tags=None):
        self.name = name
        self._dump = {"name": name, "span_id": span_id,
                      "parent_id": parent_id, "t0_ns": t0_ns,
                      "t1_ns": t1_ns, "tags": dict(tags or {}),
                      "events": [{"t_ns": t, "event": e}
                                 for t, e in events]}

    def dump(self):
        return self._dump


def push(i, start_s, parts_ns):
    """A push C and its handler H whose five parts last ``parts_ns``."""
    send, transit, queue, handler, reply = parts_ns
    c0 = int(start_s * 1e9)
    sent = c0 + send
    h0 = sent + transit + queue
    h1 = h0 + handler
    c = FakeSpan(pushes.PUSH, f"c{i}", t0_ns=c0, t1_ns=h1 + reply,
                 events=[(sent, "sent")])
    h = FakeSpan(pushes.HANDLER, f"h{i}", parent_id=f"c{i}", t0_ns=h0,
                 t1_ns=h1, tags={"q_wait": queue / 1e9})
    return c, h


def run_with(monkeypatch, rings, acked_at=(T0 + 5.0,)):
    from ceph_tpu.common import tracing

    monkeypatch.setattr(tracing, "rings", lambda: rings)
    win = Window(t0=T0, ops=[Op(key=i, units=1, t_submit=T0, t_done=t,
                                ok=True) for i, t in enumerate(acked_at)])
    return harness.Run(window=win, setup_s=0.0, device_kind="cpu")


def shares(run):
    return {p: pushes.share(run, p) for p in pushes.PARTS}


def test_shares_sum_to_100_and_follow_the_parts(monkeypatch):
    a = push(0, T0 + 1.0, (1000, 2000, 3000, 4000, 5000))
    b = push(1, T0 + 2.0, (3000, 2000, 1000, 6000, 3000))
    run = run_with(monkeypatch, [("osd.0", [a[0], b[1]], 0),
                                 ("osd.1", [b[0], a[1]], 0)])
    got = shares(run)
    assert sum(got.values()) == pytest.approx(100.0)
    assert got == pytest.approx({"send": 13.333333, "transit": 13.333333,
                                 "queue": 13.333333, "handler": 33.333333,
                                 "reply": 26.666667})


def test_pushes_outside_the_window_are_left_out(monkeypatch):
    inside = push(0, T0 + 1.0, (1000, 1000, 1000, 1000, 1000))
    before = push(1, T0 - 1.0, (9000, 0, 0, 0, 0))
    after = push(2, T0 + 6.0, (9000, 0, 0, 0, 0))
    run = run_with(monkeypatch, [("osd.0", [*inside, *before, *after], 0)])
    assert shares(run) == pytest.approx(dict.fromkeys(pushes.PARTS, 20.0))


def _no_handler():
    c, _h = push(0, T0 + 1.0, (1, 1, 1, 1, 1))
    return [("osd.0", [c], 0)], (T0 + 5.0,)


def _evicted():
    return [("osd.0", list(push(0, T0 + 1.0, (1, 1, 1, 1, 1))), 0),
            ("mon.a", [], 3)], (T0 + 5.0,)


def _no_push_in_window():
    return [("osd.0", list(push(0, T0 - 1.0, (1, 1, 1, 1, 1))), 0)], \
        (T0 + 5.0,)


def _no_acknowledgement():
    return [("osd.0", list(push(0, T0 + 1.0, (1, 1, 1, 1, 1))), 0)], ()


@pytest.mark.parametrize("case", [_no_handler, _evicted, _no_push_in_window,
                                  _no_acknowledgement])
def test_what_cannot_be_split_reads_as_nothing(monkeypatch, case):
    rings, acked = case()
    run = run_with(monkeypatch, rings, acked)
    assert shares(run) == dict.fromkeys(pushes.PARTS)


def test_a_program_without_span_rings_reads_as_nothing(monkeypatch):
    from ceph_tpu.common import tracing

    monkeypatch.delattr(tracing, "rings")
    run = harness.Run(window=Window(t0=T0, ops=[
        Op(key=0, units=1, t_submit=T0, t_done=T0 + 1, ok=True)]),
        setup_s=0.0, device_kind="cpu")
    assert shares(run) == dict.fromkeys(pushes.PARTS)


def test_a_tiny_traced_write_run_reads_all_five():
    config = json.loads((BENCH / "tests" / "data" /
                         "tiny_rados.json").read_text())
    traffic = json.loads((BENCH / "traffic" / "write_4m.json").read_text())
    traffic.update(object_bytes=1 << 16, in_flight=4, check_objects=8,
                   warm_batches=[1, 2, 4])
    cell = Cell(name="tiny.write_4m", chips=1, config=config,
                traffic=traffic, end_to_end=[],
                per_layer=[{"name": n, "unit": "%"}
                           for n in METRICS.values()])
    line = harness.run_cell(cell, 2 ** 31 + 41, 1.5, True,
                            time.monotonic(), require_tpu=False)
    assert line["correct"], line["check"]
    got = {n: line["metrics"].get(n, {}).get("value")
           for n in METRICS.values()}
    assert None not in got.values(), got
    assert sum(got.values()) == pytest.approx(100.0)

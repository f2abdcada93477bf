"""Unit tests for the tracing plane (common/tracing.py) and the
observability satellites: log2 latency histograms, strict perf-counter
type checks, idempotent TrackedOp.finish."""

import gc
import threading
import time

import pytest

from ceph_tpu.common import tracing
from ceph_tpu.common.op_tracker import OpTracker
from ceph_tpu.common.perf_counters import PerfCounters
from ceph_tpu.common.tracing import NOOP_SPAN, Tracer
from ceph_tpu.msg.messenger import Messenger


# -- spans ------------------------------------------------------------------

def test_span_basics_and_dump():
    t = Tracer("svc")
    with t.start_span("op", tags={"pool": 1}) as sp:
        sp.log("phase-1")
        sp.set_tag("oid", "x")
        assert t.current() is sp
    assert t.current() is None
    d = t.dump()
    assert d["service"] == "svc"
    (s,) = d["spans"]
    assert s["name"] == "op" and s["parent_id"] is None
    assert s["tags"] == {"pool": 1, "oid": "x"}
    assert s["events"][0]["event"] == "phase-1"
    assert s["finished"] and s["duration"] >= 0


def test_thread_local_parenting_and_trace_id():
    t = Tracer("svc")
    with t.start_span("root") as root:
        with t.start_span("child") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
            assert t.current() is child
        assert t.current() is root
    # siblings from another thread do NOT inherit this thread's stack
    seen = {}

    def other():
        with t.start_span("elsewhere") as sp:
            seen["parent"] = sp.parent_id

    th = threading.Thread(target=other)
    th.start()
    th.join()
    assert seen["parent"] is None


def test_wire_carrier_round_trip():
    a, b = Tracer("client"), Tracer("osd")
    with a.start_span("put") as sp:
        carrier = Tracer.inject(sp)
    assert carrier["trace_id"] == sp.trace_id
    with b.start_span("handle", child_of=carrier) as remote:
        assert remote.trace_id == sp.trace_id
        assert remote.parent_id == sp.span_id
        assert remote.sampled


def test_require_parent_noop_and_inject_none():
    t = Tracer("svc")
    sp = t.start_span("orphan", require_parent=True)
    assert sp is NOOP_SPAN
    assert Tracer.inject(sp) is None
    with sp:  # context manager is a no-op, records nothing
        sp.log("ignored")
    assert t.dump()["spans"] == []
    # with a live parent the same call makes a real child
    with t.start_span("root") as root:
        with t.start_span("child", require_parent=True) as child:
            assert child.trace_id == root.trace_id


def test_sampling_decided_at_root_and_inherited():
    t = Tracer("svc", sample_rate=0.0)
    with t.start_span("root") as root:
        assert not root.sampled
        carrier = Tracer.inject(root)
        assert carrier["sampled"] is False
    # never recorded, but counted
    assert t.dump()["spans"] == []
    assert t.sampled_out == 1
    # a remote child inherits the unsampled decision even on a
    # sample-everything tracer
    t2 = Tracer("peer", sample_rate=1.0)
    with t2.start_span("handle", child_of=carrier):
        pass
    assert t2.dump()["spans"] == []


def test_ring_bound_and_trace_filter():
    t = Tracer("svc", ring_size=4)
    ids = []
    for i in range(8):
        with t.start_span(f"op{i}") as sp:
            ids.append(sp.trace_id)
    d = t.dump()
    assert [s["name"] for s in d["spans"]] == \
        ["op4", "op5", "op6", "op7"]
    only = t.dump(trace_id=ids[-1])
    assert [s["name"] for s in only["spans"]] == ["op7"]


def test_span_finish_idempotent_and_error_tag():
    t = Tracer("svc")
    with pytest.raises(ValueError):
        with t.start_span("boom") as sp:
            sp.finish()  # explicit finish inside the with
            raise ValueError("x")
    d = t.dump()
    assert len(d["spans"]) == 1  # not double-recorded
    assert t.finished == 1
    # the error raised AFTER finish is still not lost silently: the
    # context manager only tags spans it finishes itself
    with pytest.raises(RuntimeError):
        with t.start_span("tagged"):
            raise RuntimeError("y")
    tagged = t.dump()["spans"][-1]
    assert "RuntimeError" in tagged["tags"]["error"]


def test_scope_adopts_span_on_another_thread():
    t = Tracer("svc")
    got = {}
    with t.start_span("fanout-root") as root:
        def worker():
            with t.scope(root):
                with t.start_span("pushed") as sp:
                    got["parent"] = sp.parent_id
            got["after"] = t.current()

        th = threading.Thread(target=worker)
        th.start()
        th.join()
    assert got["parent"] == root.span_id
    assert got["after"] is None


def test_active_spans_and_abandon():
    t = Tracer("svc")
    sp = t.start_span("leaky")
    assert any(s is sp for _svc, s in tracing.active_spans())
    leaked = t.abandon_active()
    assert leaked == [sp]
    assert not any(s is sp for _svc, s in tracing.active_spans())
    # a later finish of an abandoned span must not blow up
    sp.finish()


def _record_annotations(monkeypatch):
    """Replace jax's TraceAnnotation with a recorder of opens/closes."""
    import jax.profiler

    log = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("open", self.name))

        def __exit__(self, *exc):
            log.append(("close", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return log


def test_unsampled_span_does_no_new_work(monkeypatch):
    annotations = _record_annotations(monkeypatch)
    t = Tracer("svc", sample_rate=0.0)
    with t.start_span("op") as sp:
        sp.log("ignored")
    assert not sp.sampled
    assert sp.t0_ns is None and sp.t1_ns is None and sp.events == []
    assert annotations == []
    assert t.dump()["spans"] == [] and t._ring.evicted == 0


def test_sampled_span_stamps_one_ns_clock_and_annotates(monkeypatch):
    annotations = _record_annotations(monkeypatch)
    t = Tracer("svc")
    before = time.perf_counter_ns()
    with t.start_span("op") as sp:
        assert annotations == [("open", "op")]
        sp.log("mid")
    after = time.perf_counter_ns()
    assert annotations == [("open", "op"), ("close", "op")]
    assert before <= sp.t0_ns <= sp.events[0][0] <= sp.t1_ns <= after
    (d,) = t.dump()["spans"]
    assert (d["t0_ns"], d["t1_ns"]) == (sp.t0_ns, sp.t1_ns)
    assert d["events"] == [{"t_ns": sp.events[0][0], "event": "mid"}]


def test_ring_outlives_its_tracer_until_the_service_restarts():
    def listed():
        return {svc: (ring, ev) for svc, ring, ev in tracing.rings()}

    t = Tracer("flight-recorder", ring_size=2)
    for i in range(5):
        with t.start_span(f"op{i}"):
            pass
    assert t.dump()["evicted"] == 3
    del t
    gc.collect()
    ring, evicted = listed()["flight-recorder"]
    assert [s.name for s in ring] == ["op3", "op4"] and evicted == 3
    t2 = Tracer("flight-recorder")
    ring2, evicted2 = listed()["flight-recorder"]
    assert ring2 is t2._ring and ring2 is not ring and evicted2 == 0


def test_call_marks_sent_between_its_start_and_the_peers_receipt():
    server = Messenger("trace-server", lossless=True,
                       tracer=Tracer("trace-server"))
    client = Messenger("trace-client", lossless=True,
                       tracer=Tracer("trace-client"))
    server.start()
    client.start()
    try:
        server.register("op", lambda m: {"ok": True})
        # the session's first frame goes out in its set-up replay, and
        # logs ``sent`` only after it
        assert client.call(server.addr, {"type": "op"})["ok"]
        first = {s["span_id"] for s in client.tracer.dump()["spans"]}
        for _ in range(3):
            assert client.call(server.addr, {"type": "op"})["ok"]
    finally:
        client.shutdown()
        server.shutdown()
    calls = [s for s in client.tracer.dump()["spans"]
             if s["name"] == "call:op" and s["span_id"] not in first]
    handlers = {s["parent_id"]: s for s in server.tracer.dump()["spans"]
                if s["name"] == "handle:op"}
    assert len(calls) == 3
    for c in calls:
        h = handlers[c["span_id"]]
        (sent,) = [e["t_ns"] for e in c["events"] if e["event"] == "sent"]
        receipt = h["t0_ns"] - h["tags"]["q_wait"] * 1e9
        assert c["t0_ns"] <= sent <= receipt <= h["t1_ns"] <= c["t1_ns"]


def test_sampled_span_lies_on_the_host_plane_of_a_profile(tmp_path):
    import jax
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        with Tracer("svc").start_span("probe.sampled"):
            pass
        with Tracer("svc", sample_rate=0.0).start_span("probe.unsampled"):
            pass
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = {ev.name
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events}
    assert "probe.sampled" in names
    assert "probe.unsampled" not in names


# -- perf-counter satellites -------------------------------------------------

def test_hist_log2_bucketing_resolves_subsecond():
    pc = PerfCounters("x")
    pc.add_histogram("lat", buckets=32)  # min 1 µs
    for v in (5e-7, 2e-6, 1e-3, 0.5):
        pc.hist_add("lat", v)
    buckets = pc.dump()["lat"]["buckets"]
    assert pc.dump()["lat"]["min"] == 1e-6
    assert buckets[0] == 1               # <= 1 µs floor
    assert buckets[2] == 1               # 2 µs -> [2, 4) µs
    assert buckets[10] == 1              # 1 ms -> [512, 1024) µs
    assert buckets[19] == 1              # 0.5 s -> [0.26, 0.52) s
    # four distinct sub-second samples, four distinct buckets — the
    # old int(value).bit_length() collapsed all of these into bucket 0
    assert sum(buckets) == 4
    # clamping at the top
    pc.hist_add("lat", 1e12)
    assert pc.dump()["lat"]["buckets"][-1] == 1


def test_hist_custom_min_value():
    pc = PerfCounters("x")
    pc.add_histogram("sz", buckets=8, min_value=1)
    pc.hist_add("sz", 1)
    pc.hist_add("sz", 3)
    pc.hist_add("sz", 1024)
    b = pc.dump()["sz"]["buckets"]
    assert b[0] == 1 and b[2] == 1 and b[-1] == 1


def test_strict_type_checks_on_updates():
    pc = PerfCounters("x")
    pc.add_u64_counter("ops")
    pc.add_u64("gauge")
    pc.add_histogram("hist")
    pc.add_u64_avg("avg")
    with pytest.raises(AssertionError, match="no key"):
        pc.inc("tpyo")
    with pytest.raises(AssertionError, match="no key"):
        pc.set("tpyo", 1)
    with pytest.raises(AssertionError):
        pc.inc("hist")  # histograms take hist_add, not inc
    with pytest.raises(AssertionError):
        pc.set("avg", 2)
    with pytest.raises(AssertionError):
        pc.hist_add("ops", 1)
    pc.inc("ops")
    pc.set("gauge", 7)
    assert pc.dump()["ops"] == 1 and pc.dump()["gauge"] == 7


# -- op tracker satellite ----------------------------------------------------

def test_tracked_op_finish_idempotent():
    tr = OpTracker()
    op = tr.create("osd_op", "write x")
    op.finish()
    served = tr.dump_historic_ops()["served_total"]
    events = len(op.events)
    op.finish()  # double finish: no-op
    assert tr.dump_historic_ops()["served_total"] == served == 1
    assert len(op.events) == events
    assert sum(1 for e in op.events if e[1] == "done") == 1
    assert len(tr.dump_historic_ops()["ops"]) == 1
    # the context-manager path double-finishes by design (explicit +
    # __exit__): still one history entry
    with tr.create("osd_op", "read y") as op2:
        op2.finish()
    assert tr.dump_historic_ops()["served_total"] == 2

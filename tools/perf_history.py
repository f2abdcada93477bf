#!/usr/bin/env python
"""perf_history — the bench trajectory table with regression deltas.

Every committed ``BENCH_rNN.json`` records one driver bench run
(headline JSON under ``parsed``, the run's stderr under ``tail``).
Until now comparing runs was archaeology: open two files, grep the
tails, eyeball the numbers.  This tool ingests the whole series and
renders it as a trajectory table — one row per run, one column per
metric, with per-metric percentage deltas vs the previous run that
recorded the metric — and turns regressions into a red check:

    python tools/perf_history.py              # table, r01 -> rNN
    python tools/perf_history.py --check      # exit 1 if the LATEST
                                              # run regressed any
                                              # throughput metric
                                              # beyond --threshold
    python tools/perf_history.py --json       # rows as JSON

Metrics come from two places: the structured headline (``parsed``:
crush mappings/s, vs_baseline, and — from this PR on — the ``slo``
block), and the stderr tail (cluster IOPS, EC GB/s, batched-encode
speedup, and the staged lane's backend-init outcome: ``init_probe_s``
is how long the run waited before it gave up on an accelerator
backend that never initialized).

Regression policy: throughput metrics (higher is better) flag when
they drop more than ``--threshold`` (default 25%) vs the previous
recorded value; ``init_probe_s`` (lower is better) flags when it
grows past the fail-fast deadline band.  SLO blocks recorded by the
bench itself flag directly when ``pass`` is false.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional

# (metric, higher_is_better) — column order of the table
METRICS = [
    ("crush_mappings_s", True),
    ("vs_baseline", True),
    ("cluster_wr_iops", True),
    ("cluster_seq_iops", True),
    ("ec_encode_gbps", True),
    ("ec_batch_speedup", True),
    ("mc_crush_ndev_s", True),
    ("mc_crush_eff", True),
    ("mc_ec_eff", True),
    ("mc_dry_crush_eff", True),
    ("mc_dry_ec_eff", True),
    ("init_probe_s", False),
    ("chaos_ops", True),
    ("chaos_converge_s", False),
    ("balance_rounds", False),
    ("balance_final_stddev", False),
    ("balance_sweep_mappings_s", True),
    ("drill_recovery_mbs", True),
    ("drill_speedup", True),
    ("drill_p99_ms", False),
    ("netsplit_false_markdowns", False),
    ("netsplit_detect_s", False),
    ("netsplit_epoch_churn", False),
    ("race_violations", False),
    ("race_overhead_pct", False),
    ("async_violations", False),
    ("async_overhead_pct", False),
    ("attr_unattr_pct", False),
    ("copy_bytes_per_op", False),
    ("prof_overhead_pct", False),
    ("net.send_stall_share", False),
    ("net.dispatch_p99_ms", False),
]

_TAIL_PATTERNS = {
    "cluster_wr_iops": re.compile(
        r"# cluster [^:]*: write ([\d.]+) IOPS"),
    "cluster_seq_iops": re.compile(r"; seq ([\d.]+) IOPS"),
    "ec_encode_gbps": re.compile(
        r"# ec k=8,m=3: encode ([\d.]+) GB/s"),
    "ec_batch_speedup": re.compile(
        r"# ec batched encode .*\(([\d.]+)x\)"),
}
_INIT_KILL = re.compile(
    r"# staged/default: killed \((?:no init line|deadline)[^)]*\) "
    r"at t=([\d.]+)s")
_INIT_HANG_LEGACY = re.compile(
    r"backend never initialized within ([\d.]+)s")
# the multichip scaling block: BENCH tails carry the bench lane's
# stage JSON ("# multichip json: {...}"), MULTICHIP dryrun tails carry
# the dryrun-sized twin ("multichip scaling: {...}")
_MC_JSON = re.compile(r"multichip (?:json|scaling): (\{.*\})")
# the cluster lane's stage JSON ("# cluster json: {...}") — from the
# profiling-plane PR on it carries the attribution / copy-ledger /
# profiler blocks alongside the IOPS headline
_CL_JSON = re.compile(r"# cluster json: (\{.*\})")

# zero-copy buffer-plane goal (ROADMAP item 2): r13 measured the
# baseline at 191,329.9 copied bytes per acked op; the buffer plane
# landed in r14 with a >=40% reduction acceptance bar.  Any run after
# the baseline that books more than 0.6x the baseline is a red check
# regardless of run-over-run drift — the goal is absolute.
_COPY_BASELINE_RUN = 13
_COPY_BASELINE = 191330.0
_COPY_GOAL = 0.6 * _COPY_BASELINE


def _multichip_metrics(tail: str,
                       dryrun: bool = False) -> Dict[str, float]:
    """Scaling metrics from a tail's multichip JSON block: the
    N-device CRUSH throughput and the scaling-efficiency figures
    (N-device throughput / (N x 1-device)) for CRUSH and EC encode —
    the ROADMAP item 1 acceptance numbers, red-checked like any other
    trajectory metric when they drop more than the threshold.

    Dryrun (MULTICHIP_r*) records measure a deliberately smaller
    workload than the bench lane, so their efficiency lands in its
    own ``mc_dry_*`` columns — each series deltas like-for-like —
    and their absolute rate (small-map, incomparable) is dropped."""
    m = _MC_JSON.search(tail)
    if not m:
        return {}
    try:
        d = json.loads(m.group(1))
    except ValueError:
        return {}
    pre = "mc_dry_" if dryrun else "mc_"
    keys = [("crush_scaling_efficiency", pre + "crush_eff"),
            ("ec_scaling_efficiency", pre + "ec_eff")]
    if not dryrun:
        keys.append(("crush_ndev_mappings_per_sec",
                     "mc_crush_ndev_s"))
    out: Dict[str, float] = {}
    for key, name in keys:
        if isinstance(d.get(key), (int, float)):
            out[name] = float(d[key])
    return out


def _profiling_metrics(tail: str) -> Dict[str, float]:
    """Profiling-plane metrics from a tail's cluster JSON block —
    all lower-is-better: the share of the client critical path the
    attribution fold could not name (``attr_unattr_pct``), the bytes
    the hot write path copies per acked op (``copy_bytes_per_op``),
    and the IOPS tax of running the wallclock sampler at its default
    rate (``prof_overhead_pct``).  Growth past the threshold is a red
    check: unattributed share creeping up means a new untagged span
    on the critical path; bytes/op creeping up means a new copy."""
    m = _CL_JSON.search(tail)
    if not m:
        return {}
    try:
        d = json.loads(m.group(1))
    except ValueError:
        return {}
    out: Dict[str, float] = {}
    attr = d.get("attribution") or {}
    if isinstance(attr.get("unattr_pct"), (int, float)):
        out["attr_unattr_pct"] = float(attr["unattr_pct"])
    copyb = d.get("copy") or {}
    if isinstance(copyb.get("bytes_per_op"), (int, float)):
        out["copy_bytes_per_op"] = float(copyb["bytes_per_op"])
    prof = d.get("profiler") or {}
    if isinstance(prof.get("overhead_pct"), (int, float)):
        out["prof_overhead_pct"] = float(prof["overhead_pct"])
    # saturation plane (PR 17): whole-run messenger backpressure —
    # stall share creeping up means the send path is blocking on the
    # wire; dispatch p99 creeping up means frames are sitting in the
    # handler pool queue before any handler runs
    net = d.get("net") or {}
    if isinstance(net.get("send_stall_share"), (int, float)):
        out["net.send_stall_share"] = float(net["send_stall_share"])
    if isinstance(net.get("dispatch_p99_ms"), (int, float)):
        out["net.dispatch_p99_ms"] = float(net["dispatch_p99_ms"])
    return out


def load_run(path: str) -> Optional[Dict]:
    try:
        raw = json.load(open(path))
    except (OSError, ValueError) as e:
        print(f"# {path}: unreadable ({e})", file=sys.stderr)
        return None
    parsed = raw.get("parsed") or {}
    tail = raw.get("tail") or ""
    row: Dict = {
        "run": f"r{int(raw.get('n', 0)):02d}",
        "n": int(raw.get("n", 0)),
        "path": os.path.basename(path),
        "rc": raw.get("rc"),
        "platform": parsed.get("platform"),
        "metrics": {},
        "slo_fail": [],
    }
    if isinstance(parsed.get("value"), (int, float)):
        row["metrics"]["crush_mappings_s"] = float(parsed["value"])
    if isinstance(parsed.get("vs_baseline"), (int, float)):
        row["metrics"]["vs_baseline"] = float(parsed["vs_baseline"])
    for metric, pat in _TAIL_PATTERNS.items():
        m = pat.search(tail)
        if m:
            row["metrics"][metric] = float(m.group(1))
    row["metrics"].update(_multichip_metrics(tail))
    row["metrics"].update(_profiling_metrics(tail))
    # how long the staged lane burned before the accelerator verdict:
    # the backend-init fail-fast probe should cap this at ~60 s (the
    # r05 run burned 300 s; the probe landed after that measurement)
    m = _INIT_KILL.search(tail) or _INIT_HANG_LEGACY.search(tail)
    if m:
        row["metrics"]["init_probe_s"] = float(m.group(1))
    elif parsed.get("backend_init_failed"):
        row["metrics"]["init_probe_s"] = float(
            os.environ.get("CEPH_TPU_BENCH_INIT_DEADLINE", 60))
    slo = parsed.get("slo")
    if isinstance(slo, dict) and slo.get("pass") is False:
        row["slo_fail"].append(slo.get("metric", "headline"))
    for m_ in re.finditer(r"# slo (\S+): .*-> FAIL", tail):
        row["slo_fail"].append(m_.group(1))
    return row


def load_multichip(path: str) -> Optional[Dict]:
    """One MULTICHIP_rNN.json dryrun record: run number + the scaling
    metrics parsed from its tail (absent on records that predate the
    scaling block)."""
    try:
        raw = json.load(open(path))
    except (OSError, ValueError) as e:
        print(f"# {path}: unreadable ({e})", file=sys.stderr)
        return None
    return {"ok": raw.get("ok"),
            "metrics": _multichip_metrics(raw.get("tail") or "",
                                          dryrun=True)}


def load_chaos(path: str) -> Optional[Dict]:
    """One CHAOS_rNN.json thrasher-soak record (tools/thrasher.py):
    acked-op volume and HEALTH_OK convergence time become trajectory
    metrics; lost acked writes or a failed soak (``ok`` false) are
    regressions outright — there is no acceptable drift on
    durability."""
    try:
        raw = json.load(open(path))
    except (OSError, ValueError) as e:
        print(f"# {path}: unreadable ({e})", file=sys.stderr)
        return None
    metrics: Dict[str, float] = {}
    if isinstance(raw.get("ops"), (int, float)):
        metrics["chaos_ops"] = float(raw["ops"])
    if isinstance(raw.get("health_converge_s"), (int, float)):
        metrics["chaos_converge_s"] = float(raw["health_converge_s"])
    fail: List[str] = []
    if raw.get("lost"):
        fail.append(f"chaos_lost_writes={raw['lost']}")
    if raw.get("ok") is False:
        fail.append("chaos_soak_failed")
    return {"metrics": metrics, "fail": fail}


def load_balance(path: str) -> Optional[Dict]:
    """One BALANCE_rNN.json balancer-convergence record (bench.py
    --worker balancer over ceph_tpu/mgr/run_offline): rounds to
    converge, final deviation stddev, sweep throughput.  A run that
    exits without converging is a red check outright."""
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    metrics: Dict[str, float] = {}
    if isinstance(raw.get("rounds"), (int, float)):
        metrics["balance_rounds"] = float(raw["rounds"])
    if isinstance(raw.get("final_stddev"), (int, float)):
        metrics["balance_final_stddev"] = float(raw["final_stddev"])
    if isinstance(raw.get("sweep_mappings_per_sec"), (int, float)):
        metrics["balance_sweep_mappings_s"] = float(
            raw["sweep_mappings_per_sec"])
    fail: List[str] = []
    if raw.get("converged") is False:
        fail.append("balance_not_converged")
    return {"metrics": metrics, "fail": fail}


def load_drill(path: str) -> Optional[Dict]:
    """One DRILL_rNN.json whole-host-failure record (tools/thrasher.py
    --host-kill): pipelined recovery MB/s, the speedup over the serial
    per-object baseline, and the degraded-read soak p99 become
    trajectory metrics.  Lost acked writes, a failed reconvergence, a
    failed SLO, or a speedup under the 1.5x pipeline gate are
    regressions outright."""
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, ValueError) as e:
        print(f"# {path}: unreadable ({e})", file=sys.stderr)
        return None
    metrics: Dict[str, float] = {}
    if isinstance(raw.get("recovery_mbps"), (int, float)):
        metrics["drill_recovery_mbs"] = float(raw["recovery_mbps"])
    if isinstance(raw.get("pipeline_speedup"), (int, float)):
        metrics["drill_speedup"] = float(raw["pipeline_speedup"])
    soak = raw.get("soak") or {}
    if isinstance(soak.get("p99_ms"), (int, float)):
        metrics["drill_p99_ms"] = float(soak["p99_ms"])
    fail: List[str] = []
    if raw.get("lost"):
        fail.append(f"drill_lost_writes={raw['lost']}")
    if raw.get("converge_s") is None:
        fail.append("drill_not_converged")
    slo = soak.get("slo")
    if isinstance(slo, dict) and slo.get("pass") is False:
        fail.append(f"drill_slo_fail:{slo.get('metric')}")
    speedup = raw.get("pipeline_speedup")
    if not isinstance(speedup, (int, float)) or speedup <= 1.5:
        fail.append("drill_speedup_below_1.5x")
    if raw.get("ok") is False:
        fail.append("drill_failed")
    return {"metrics": metrics, "fail": fail}


def load_netsplit(path: str) -> Optional[Dict]:
    """One NETSPLIT_rNN.json partition-drill record (tools/thrasher.py
    --netsplit): false markdowns under a mon-link cut, true-isolation
    detection latency, and flap-drill epoch churn become trajectory
    metrics.  ANY false markdown, lost acked write, or failed drill
    verdict is a regression outright — partition tolerance has no
    acceptable drift."""
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, ValueError) as e:
        print(f"# {path}: unreadable ({e})", file=sys.stderr)
        return None
    metrics: Dict[str, float] = {}
    if isinstance(raw.get("false_markdowns"), (int, float)):
        metrics["netsplit_false_markdowns"] = float(
            raw["false_markdowns"])
    if isinstance(raw.get("detect_s"), (int, float)):
        metrics["netsplit_detect_s"] = float(raw["detect_s"])
    if isinstance(raw.get("epoch_churn"), (int, float)):
        metrics["netsplit_epoch_churn"] = float(raw["epoch_churn"])
    fail: List[str] = []
    if raw.get("false_markdowns"):
        fail.append(
            f"netsplit_false_markdowns={raw['false_markdowns']}")
    if raw.get("lost"):
        fail.append(f"netsplit_lost_writes={raw['lost']}")
    if raw.get("ok") is False:
        fail.append("netsplit_drill_failed")
    return {"metrics": metrics, "fail": fail}


def load_race(path: str) -> Optional[Dict]:
    """One RACE_rNN.json data-race-audit record (tools/thrasher.py
    --race-audit): the violation count and checker-overhead metrics
    join the trajectory, and the gate is absolute — ANY recorded
    lockset/confinement violation, any acked-write loss under the
    drills, a failed audit verdict, or checker overhead at/over 10%
    is a regression outright (a data race has no acceptable drift)."""
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, ValueError) as e:
        print(f"# {path}: unreadable ({e})", file=sys.stderr)
        return None
    metrics: Dict[str, float] = {}
    if isinstance(raw.get("violations"), (int, float)):
        metrics["race_violations"] = float(raw["violations"])
    if isinstance(raw.get("overhead_pct"), (int, float)):
        metrics["race_overhead_pct"] = float(raw["overhead_pct"])
    fail: List[str] = []
    if raw.get("violations"):
        fail.append(f"race_violations={raw['violations']}")
    if raw.get("lost"):
        fail.append(f"race_lost_writes={raw['lost']}")
    ov = raw.get("overhead_pct")
    if not isinstance(ov, (int, float)) or ov >= 10.0:
        fail.append(f"race_checker_overhead={ov}")
    if raw.get("ok") is False:
        fail.append("race_audit_failed")
    return {"metrics": metrics, "fail": fail}


def load_async(path: str) -> Optional[Dict]:
    """One ASYNC_rNN.json loop-stall record (tools/thrasher.py
    --loop-stall): the static-violation count and enforcement
    overhead join the trajectory, and the gate is absolute — ANY
    unsuppressed BLOCK001 reachability violation, any acked-write
    loss, an unnamed victim callback, a cluster that failed to heal,
    a failed drill verdict, or enforcement overhead at/over 5% is a
    regression outright (a blocking dispatch loop has no acceptable
    drift)."""
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, ValueError) as e:
        print(f"# {path}: unreadable ({e})", file=sys.stderr)
        return None
    metrics: Dict[str, float] = {}
    if isinstance(raw.get("static_violations"), (int, float)):
        metrics["async_violations"] = \
            float(raw["static_violations"])
    if isinstance(raw.get("overhead_pct"), (int, float)):
        metrics["async_overhead_pct"] = float(raw["overhead_pct"])
    fail: List[str] = []
    if raw.get("static_violations"):
        fail.append(
            f"async_violations={raw['static_violations']}")
    if raw.get("lost"):
        fail.append(f"async_lost_writes={raw['lost']}")
    if not raw.get("victim_named"):
        fail.append("async_victim_unnamed")
    if not raw.get("cleared"):
        fail.append("async_not_healed")
    ov = raw.get("overhead_pct")
    if not isinstance(ov, (int, float)) or ov >= 5.0:
        fail.append(f"async_enforcer_overhead={ov}")
    if raw.get("ok") is False:
        fail.append("loop_stall_drill_failed")
    return {"metrics": metrics, "fail": fail}


def load_all(directory: str) -> List[Dict]:
    rows = []
    for path in sorted(glob.glob(os.path.join(directory,
                                              "BENCH_r*.json"))):
        row = load_run(path)
        if row is not None:
            rows.append(row)
    by_n = {r["n"]: r for r in rows}
    # MULTICHIP_rNN dryrun records ride the same trajectory: their
    # scaling metrics merge into the same-numbered bench row (the
    # driver emits both per run), creating a standalone row when no
    # bench run shares the number.  Bench-measured values win — the
    # dryrun twin is smaller-scale.
    for path in sorted(glob.glob(os.path.join(directory,
                                              "MULTICHIP_r*.json"))):
        m = re.search(r"MULTICHIP_r(\d+)\.json$", path)
        mc = load_multichip(path)
        if mc is None or m is None or not mc["metrics"]:
            continue
        n = int(m.group(1))
        row = by_n.get(n)
        if row is None:
            row = {"run": f"r{n:02d}", "n": n,
                   "path": os.path.basename(path), "rc": None,
                   "platform": None, "metrics": {}, "slo_fail": []}
            by_n[n] = row
            rows.append(row)
        for k, v in mc["metrics"].items():
            row["metrics"].setdefault(k, v)
    # CHAOS_rNN thrasher records merge the same way: chaos metrics
    # land on the same-numbered bench row (or a standalone row), and
    # their hard failures ride slo_fail into the regression check
    for path in sorted(glob.glob(os.path.join(directory,
                                              "CHAOS_r*.json"))):
        m = re.search(r"CHAOS_r(\d+)\.json$", path)
        ch = load_chaos(path)
        if ch is None or m is None or \
                not (ch["metrics"] or ch["fail"]):
            continue
        n = int(m.group(1))
        row = by_n.get(n)
        if row is None:
            row = {"run": f"r{n:02d}", "n": n,
                   "path": os.path.basename(path), "rc": None,
                   "platform": None, "metrics": {}, "slo_fail": []}
            by_n[n] = row
            rows.append(row)
        for k, v in ch["metrics"].items():
            row["metrics"].setdefault(k, v)
        row["slo_fail"].extend(ch["fail"])
    # BALANCE_rNN balancer-convergence records: placement-quality
    # metrics merge onto the same-numbered row; a non-converged run
    # rides slo_fail into the regression check
    for path in sorted(glob.glob(os.path.join(directory,
                                              "BALANCE_r*.json"))):
        m = re.search(r"BALANCE_r(\d+)\.json$", path)
        bal = load_balance(path)
        if bal is None or m is None or \
                not (bal["metrics"] or bal["fail"]):
            continue
        n = int(m.group(1))
        row = by_n.get(n)
        if row is None:
            row = {"run": f"r{n:02d}", "n": n,
                   "path": os.path.basename(path), "rc": None,
                   "platform": None, "metrics": {}, "slo_fail": []}
            by_n[n] = row
            rows.append(row)
        for k, v in bal["metrics"].items():
            row["metrics"].setdefault(k, v)
        row["slo_fail"].extend(bal["fail"])
    # DRILL_rNN whole-host-failure records: recovery-throughput and
    # degraded-read-latency metrics merge onto the same-numbered row;
    # durability / SLO / pipeline-gate failures ride slo_fail into
    # the regression check
    for path in sorted(glob.glob(os.path.join(directory,
                                              "DRILL_r*.json"))):
        m = re.search(r"DRILL_r(\d+)\.json$", path)
        dr = load_drill(path)
        if dr is None or m is None or \
                not (dr["metrics"] or dr["fail"]):
            continue
        n = int(m.group(1))
        row = by_n.get(n)
        if row is None:
            row = {"run": f"r{n:02d}", "n": n,
                   "path": os.path.basename(path), "rc": None,
                   "platform": None, "metrics": {}, "slo_fail": []}
            by_n[n] = row
            rows.append(row)
        for k, v in dr["metrics"].items():
            row["metrics"].setdefault(k, v)
        row["slo_fail"].extend(dr["fail"])
    # NETSPLIT_rNN partition-drill records: detection-latency and
    # churn metrics merge onto the same-numbered row; false markdowns
    # and lost writes ride slo_fail into the regression check
    for path in sorted(glob.glob(os.path.join(directory,
                                              "NETSPLIT_r*.json"))):
        m = re.search(r"NETSPLIT_r(\d+)\.json$", path)
        ns = load_netsplit(path)
        if ns is None or m is None or \
                not (ns["metrics"] or ns["fail"]):
            continue
        n = int(m.group(1))
        row = by_n.get(n)
        if row is None:
            row = {"run": f"r{n:02d}", "n": n,
                   "path": os.path.basename(path), "rc": None,
                   "platform": None, "metrics": {}, "slo_fail": []}
            by_n[n] = row
            rows.append(row)
        for k, v in ns["metrics"].items():
            row["metrics"].setdefault(k, v)
        row["slo_fail"].extend(ns["fail"])
    # RACE_rNN data-race-audit records: violation count and checker
    # overhead merge onto the same-numbered row; any violation, lost
    # write or overhead breach rides slo_fail into the regression
    # check
    for path in sorted(glob.glob(os.path.join(directory,
                                              "RACE_r*.json"))):
        m = re.search(r"RACE_r(\d+)\.json$", path)
        rc_ = load_race(path)
        if rc_ is None or m is None or \
                not (rc_["metrics"] or rc_["fail"]):
            continue
        n = int(m.group(1))
        row = by_n.get(n)
        if row is None:
            row = {"run": f"r{n:02d}", "n": n,
                   "path": os.path.basename(path), "rc": None,
                   "platform": None, "metrics": {}, "slo_fail": []}
            by_n[n] = row
            rows.append(row)
        for k, v in rc_["metrics"].items():
            row["metrics"].setdefault(k, v)
        row["slo_fail"].extend(rc_["fail"])
    # ASYNC_rNN loop-stall records: static-violation count and
    # enforcement overhead merge onto the same-numbered row; any
    # violation, lost write, unnamed victim, failed heal or overhead
    # breach rides slo_fail into the regression check
    for path in sorted(glob.glob(os.path.join(directory,
                                              "ASYNC_r*.json"))):
        m = re.search(r"ASYNC_r(\d+)\.json$", path)
        ac = load_async(path)
        if ac is None or m is None or \
                not (ac["metrics"] or ac["fail"]):
            continue
        n = int(m.group(1))
        row = by_n.get(n)
        if row is None:
            row = {"run": f"r{n:02d}", "n": n,
                   "path": os.path.basename(path), "rc": None,
                   "platform": None, "metrics": {}, "slo_fail": []}
            by_n[n] = row
            rows.append(row)
        for k, v in ac["metrics"].items():
            row["metrics"].setdefault(k, v)
        row["slo_fail"].extend(ac["fail"])
    rows.sort(key=lambda r: r["n"])
    return rows


def compute_deltas(rows: List[Dict],
                   threshold: float = 0.25) -> None:
    """Annotate each row with per-metric % delta vs the previous run
    that recorded the metric, and a ``regressions`` list for drops
    (or, for lower-is-better metrics, growth) beyond the threshold."""
    last_seen: Dict[str, float] = {}
    for row in rows:
        row["deltas"] = {}
        row["regressions"] = list(row["slo_fail"])
        for metric, higher_better in METRICS:
            val = row["metrics"].get(metric)
            if val is None:
                continue
            prev = last_seen.get(metric)
            if prev not in (None, 0):
                pct = (val - prev) / abs(prev)
                row["deltas"][metric] = pct
                regressed = (pct < -threshold) if higher_better \
                    else (pct > threshold)
                if regressed:
                    row["regressions"].append(
                        f"{metric} {prev:g} -> {val:g} "
                        f"({pct * 100:+.0f}%)")
            last_seen[metric] = val
        cbpo = row["metrics"].get("copy_bytes_per_op")
        if cbpo is not None and row["n"] > _COPY_BASELINE_RUN \
                and cbpo > _COPY_GOAL:
            row["regressions"].append(
                f"copy_bytes_per_op {cbpo:g} above the zero-copy "
                f"goal {_COPY_GOAL:g} (0.6 x r{_COPY_BASELINE_RUN}'s "
                f"{_COPY_BASELINE:g})")


def render(rows: List[Dict]) -> str:
    headers = ["run"] + [m for m, _ in METRICS] + ["flags"]
    widths = [max(len(h), 14) for h in headers]
    widths[0] = 5

    def cell(row: Dict, metric: str) -> str:
        val = row["metrics"].get(metric)
        if val is None:
            return "-"
        pct = row["deltas"].get(metric)
        s = f"{val:g}"
        if pct is not None:
            s += f" ({pct * 100:+.0f}%)"
        return s

    lines = ["".join(h.ljust(w + 1) for h, w in zip(headers,
                                                    widths))]
    for row in rows:
        flags = "REGRESSED" if row["regressions"] else "ok"
        cells = [row["run"]] + [cell(row, m) for m, _ in METRICS] \
            + [flags]
        lines.append("".join(c.ljust(w + 1)
                             for c, w in zip(cells, widths)))
    for row in rows:
        for reg in row["regressions"]:
            lines.append(f"  ! {row['run']}: {reg}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="perf_history")
    ap.add_argument("directory", nargs="?",
                    default=os.path.dirname(os.path.dirname(
                        os.path.abspath(__file__))),
                    help="directory holding BENCH_r*.json "
                         "(default: repo root)")
    ap.add_argument("--threshold", type=float, default=0.25,
                    help="fractional drop that counts as a "
                         "regression (default 0.25)")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 when the LATEST run regressed")
    ap.add_argument("--json", action="store_true",
                    help="emit rows as JSON instead of the table")
    args = ap.parse_args(argv)

    rows = load_all(args.directory)
    if not rows:
        print(f"no BENCH_r*.json under {args.directory}",
              file=sys.stderr)
        return 2
    compute_deltas(rows, threshold=args.threshold)
    if args.json:
        print(json.dumps(rows, indent=1))
    else:
        print(render(rows))
    if args.check and rows[-1]["regressions"]:
        print(f"REGRESSION in {rows[-1]['run']}: "
              f"{rows[-1]['regressions']}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

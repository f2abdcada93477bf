"""The harness's contract: the result line's shape, its refusal to run
without a TPU, and a new cell, traffic mix, configuration and metric
added as files and entries with no existing file edited."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap
import time

import pytest

from benchmark.lib import harness
from benchmark.lib.spec import BENCH, ROOT, Cell, load_cell

CPU = dict(os.environ, JAX_PLATFORMS="cpu")


def tiny_placement_cell(chips=1):
    config = json.loads((BENCH / "tests" / "data" /
                         "tiny_crush.json").read_text())
    traffic = dict(json.loads((BENCH / "traffic" /
                               "remap_rep3.json").read_text()),
                   check_uniform=8)
    return Cell(name="tiny.remap_rep3", chips=chips, config=config,
                traffic=traffic,
                end_to_end=[{"name": "placements_per_s", "unit": "PGs/s"},
                            {"name": "setup_s", "unit": "s"}],
                per_layer=[{"name": "placement.device_ns_per_pg",
                            "unit": "ns"}])


def test_result_line_shape():
    line = harness.run_cell(tiny_placement_cell(), 5, 0.3, False,
                            time.monotonic(), require_tpu=False)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "check"]
    assert line["correct"] is True
    assert set(line["metrics"]) == {"placements_per_s", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for c in line["check"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(line)


def test_traced_line_has_busy_window_and_breakdown_keys_when_traced():
    line = harness.run_cell(tiny_placement_cell(), 6, 0.3, True,
                            time.monotonic(), require_tpu=False)
    assert list(line)[-1] == "check"
    # on the CPU there is no device plane: the per-layer reader finds
    # nothing, and says nothing rather than 0
    assert line["metrics"] == {}
    assert line["device"]["busy_s"] == 0.0 and line["device"]["window_s"] > 0


def test_a_run_refuses_without_a_tpu():
    with pytest.raises(harness.NoChip):
        harness.run_cell(tiny_placement_cell(), 1, 0.1, False,
                         time.monotonic())
    with pytest.raises(harness.NoChip):
        harness.run_cell(tiny_placement_cell(chips=4), 1, 0.1, False,
                         time.monotonic(), require_tpu=False)


def test_command_exits_non_zero_with_no_result_without_a_tpu():
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "crush10k.remap_rep3", "--seed", str(2 ** 31 + 3), "--seconds",
         "1", "--trace", "0"], cwd=ROOT, env=CPU, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not 'tpu'" in p.stderr


def test_every_cell_of_the_benchmark_resolves():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = load_cell(w["name"])
        assert (BENCH / "generators" /
                f"{cell.traffic['generator']}.py").is_file()
        for m in cell.end_to_end + cell.per_layer:
            assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer


DUMMY_GENERATOR = '''
    """A dummy mix: squares a seeded vector on the device each step."""
    import time

    import numpy as np

    from benchmark.lib.stats import Op, Window


    class Generator:
        def __init__(self, config, traffic, seed, trace):
            self.n = traffic["n"] * config["scale"]
            self.x = np.random.default_rng(seed).integers(0, 100, self.n)

        def setup(self):
            import jax
            import jax.numpy as jnp

            self.f = jax.jit(lambda v: v * v)
            self.last = np.asarray(self.f(jnp.asarray(self.x)))

        def window(self, seconds):
            win = Window(t0=time.perf_counter())
            while time.perf_counter() < win.t0 + seconds:
                op = Op(key=0, units=self.n, t_submit=time.perf_counter())
                self.last = np.asarray(self.f(self.x))
                op.t_done, op.ok = time.perf_counter(), True
                win.ops.append(op)
            return win

        def counters(self):
            return {}

        def facts(self):
            return {"n": self.n}

        def release(self):
            pass

        def check(self):
            return {"mismatches": (int((self.last != self.x ** 2).sum()), 0)}

        def close(self):
            pass
'''


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_cell_mix_config_and_metric_are_files_and_entries(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = _digests(tmp_path / "benchmark")
    b = tmp_path / "benchmark"
    (b / "generators" / "dummy_square.py").write_text(
        textwrap.dedent(DUMMY_GENERATOR))
    (b / "traffic" / "square_small.json").write_text(
        json.dumps({"generator": "dummy_square", "n": 64}))
    (b / "configs" / "dummy.json").write_text(json.dumps({"scale": 2}))
    (b / "metrics" / "squares_per_s.py").write_text(
        "from benchmark.lib.stats import rate\n\n\n"
        "def read(run):\n    return rate(run.window)\n")
    (b / "metrics" / "dummy.elements.py").write_text(
        "def read(run):\n    return float(run.facts['n'])\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "dummy", "source": "https://example.org",
                            "file": "benchmark/configs/dummy.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "dummy.square_small",
                              "config": "dummy", "traffic": "square_small",
                              "chips": 1, "why": "a test"})
    spec["end_to_end"].insert(0, {
        "name": "squares_per_s", "unit": "1/s", "better": "higher",
        "bound": 0.05, "source": "host_clock",
        "workloads": ["dummy.square_small"]})
    spec["per_layer"].append({
        "name": "dummy.elements", "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "dummy",
        "moves": "squares_per_s", "workloads": ["dummy.square_small"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    code = textwrap.dedent("""
        import json, sys, time
        sys.path.insert(0, ".")
        from benchmark.lib import harness
        from benchmark.lib.spec import load_cell
        cell = load_cell("dummy.square_small")
        for trace in (False, True):
            print(json.dumps(harness.run_cell(
                cell, 9, 0.2, trace, time.monotonic(), require_tpu=False)))
    """)
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=CPU,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    plain, traced = (json.loads(s) for s in p.stdout.strip().splitlines())
    assert plain["correct"] and traced["correct"]
    assert set(plain["metrics"]) == {"squares_per_s", "setup_s"}
    assert traced["metrics"] == {"dummy.elements":
                                 {"value": 128.0, "unit": "1"}}
    after = _digests(tmp_path / "benchmark")
    assert all(after[f] == d for f, d in before.items())

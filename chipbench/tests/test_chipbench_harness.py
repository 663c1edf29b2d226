"""The harness finds a cell's files by name and refuses to run without a
TPU, on too few chips, or on a device kind its peaks table lacks."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import harness

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


class FakeDevice:
    def __init__(self, platform, kind):
        self.platform = platform
        self.device_kind = kind


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_finds_its_files_by_name():
    for w in bench()["workloads"]:
        cell = harness.load_cell(ROOT, w["name"])
        assert cell["chips"] == w["chips"]
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "drivers", cell["workload"]["driver"] + ".py"))
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "traffic", cell["mix"]["kind"] + ".py"))
        assert cell["end_to_end"] and cell["per_layer"]
        assert "setup_s" in [m["name"] for m in cell["end_to_end"]]


def test_every_metric_has_a_reader():
    b = bench()
    for m in b["end_to_end"] + b["per_layer"]:
        assert callable(harness.reader(m["name"]))


def test_unknown_workload_is_refused():
    with pytest.raises(harness.BenchError, match="unknown workload"):
        harness.load_cell(ROOT, "no-such-cell")


def test_devices_are_checked():
    cell = {"name": "c", "chips": 4}
    tpu = [FakeDevice("tpu", "TPU v5 lite")] * 4
    dev = harness.check_devices(cell, tpu)
    assert dev.peaks["bf16_flops_per_s"] == 197e12
    assert dev.peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError, match="needs a TPU"):
        harness.check_devices(cell, [FakeDevice("cpu", "cpu")])
    with pytest.raises(harness.BenchError, match="needs 4 chips"):
        harness.check_devices(cell, tpu[:1])
    with pytest.raises(harness.BenchError, match="not in peaks.json"):
        harness.check_devices(cell, [FakeDevice("tpu", "TPU v9 huge")] * 4)


def test_peaks_table_names_its_source():
    with open(os.path.join(ROOT, "chipbench", "peaks.json")) as f:
        table = json.load(f)
    assert "TPU v5e" in table["source"]


def test_run_without_a_tpu_fails_and_prints_no_result():
    name = bench()["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "chipbench",
                                                     "run.py"),
                        "--workload", name, "--seed", "1", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True,
                       env=env, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_result_line_puts_the_checks_last():
    dev = harness.Device("tpu", "TPU v5 lite", 1, {}, [])
    line = harness.result_line({
        "device": dev, "memory_peak_bytes": 5, "correct": True,
        "attempted": 3, "failed": 0, "metrics": {"setup_s": {
            "value": 1.5, "unit": "s"}},
        "checks": [("gap", 0.1, 0.5)]})
    assert list(line)[-1] == "checks"
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert line["checks"]["gap"] == {"value": 0.1, "limit": 0.5}


def test_benchmark_json_shape():
    import re
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    assert 1 <= b["run_seconds"] <= 51
    configs = {c["name"] for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert all(name.match(k) for k in c["reduced"])
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["end_to_end"] + b["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        cells = e2e[m["moves"]].get("workloads",
                                    [w["name"] for w in b["workloads"]])
        assert set(m.get("workloads", cells)) <= set(cells)

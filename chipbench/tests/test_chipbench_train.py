"""The training driver at a tiny size on one CPU device (the check for a
chip skipped): the profiler's start and stop, which take seconds on a
chip, stay out of the window that the rate and the MFU are read over."""
import json
import os
import time

import jax
import pytest

from chipbench import flops, harness, trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
PAUSE = 1.5           # seconds the stand-in profiler takes to start and stop
PEAK = 1e12


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def run(tmp_path, trace):
    config = load(HERE, "data", "tiny-train.json")
    config["train"]["mesh"] = {"data": 1, "model": 1}
    cell = {"name": "tiny-train", "chips": 1, "root": str(tmp_path),
            "end_to_end": [{"name": "train_tokens_per_s",
                            "unit": "tokens/s"}],
            "per_layer": [{"name": "train_mfu", "unit": "%"}],
            "workload": load(ROOT, "chipbench", "workloads",
                             "sc2-train-fsdp4.json"),
            "config": config,
            "mix": load(HERE, "data", "tiny-batches.json")}
    dev = harness.Device("cpu", "cpu", 1, {"bf16_flops_per_s": PEAK},
                         jax.devices()[:1])
    return harness.run_cell(cell, dev, seed=2**34 + 9, seconds=0.4,
                            trace=trace, t_start=time.monotonic())


def test_profiler_time_stays_out_of_the_window(tmp_path, monkeypatch):
    calls = []

    def slow(what):
        def call(*args):
            calls.append(what)
            time.sleep(PAUSE)
        return call

    monkeypatch.setattr(jax.profiler, "start_trace", slow("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace", slow("stop"))
    monkeypatch.setattr(T, "find_xplane", lambda d: d)
    monkeypatch.setattr(T, "load", lambda path: None)
    monkeypatch.setattr(T, "summarize", lambda tr, n: {
        "busy_s": 1.0, "window_s": 1.0, "breakdown": {}})

    plain = run(tmp_path, trace=False)
    assert calls == []
    traced = run(tmp_path, trace=True)
    assert calls == ["start", "stop"]
    assert plain["correct"] and traced["correct"]

    lo, hi = traced["window"]
    assert hi - lo < PAUSE
    tr = traced["train"]
    trace_steps = int(load(ROOT, "chipbench", "workloads",
                           "sc2-train-fsdp4.json")["trace_steps"])
    assert traced["attempted"] == tr["steps"] + trace_steps
    want = 100.0 * tr["window_tokens"] / (hi - lo) * \
        flops.train_flops_per_token(traced["sizes"], tr["seq_len"]) / PEAK
    assert traced["metrics"]["train_mfu"]["value"] == pytest.approx(want)
    # a window that held the profiler's 3 s would read a fraction of this
    ratio = traced["metrics"]["train_mfu"]["value"] / (
        plain["metrics"]["train_tokens_per_s"]["value"]
        * flops.train_flops_per_token(plain["sizes"], tr["seq_len"])
        / PEAK * 100.0)
    assert ratio > 0.5

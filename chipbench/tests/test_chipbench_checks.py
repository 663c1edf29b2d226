"""The correctness comparison: train readings against the reference's,
and a tiny run with the timed step broken underneath."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import check_train

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def test_train_numbers():
    prog = {"losses": [2.0, 1.9], "grad": {"a": 1.0, "b": 0.5, "c": 1e-9},
            "update": {"a": 0.1, "b": 0.2, "c": 5.0}}
    ref = {"losses": [2.002, 1.9], "grad": {"a": 1.01, "b": 0.5, "c": 0.0},
           "update": {"a": 0.1, "b": 0.22, "c": 0.0}}
    n = check_train.compare(prog, ref)
    assert n["loss_rel_gap"] == pytest.approx(0.002 / 2.002)
    assert n["grad_leaf_gap"] == pytest.approx(0.01 / 1.01)
    # leaf c: no reference gradient, left out of the change
    assert n["update_leaf_gap"] == pytest.approx(0.02 / 0.22)
    assert check_train.schedule(
        {"kind": "cosine", "warmup_steps": 200, "total_steps": 1000,
         "min_ratio": 0.1}, 0) == pytest.approx(1 / 200)


def test_train_faults_come_out_incorrect():
    """A tiny training run on four host CPU devices, checked against the
    cell's limits: sound, then with the step returning its state
    unchanged, with half of the batch left out, with the exchange
    between chips left out, and with the control (the reference in
    float8) in the step's place."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-m", "chipbench.tests.fault_run"],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = {r["case"]: r for r in map(json.loads,
                                      p.stdout.strip().splitlines())}
    assert got["sound"]["correct"], got["sound"]
    for case in ("state_unchanged", "half_batch", "no_exchange",
                 "control"):
        assert not got[case]["correct"], got[case]

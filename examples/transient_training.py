"""End-to-end transient-cluster training — the paper's scenario on the
elastic runtime (a ≥100-step end-to-end run). Membership is
data: the runtime feeds the one train step row weights and an LR scale.

A 4-slot sparse-mapping cluster trains a ~25M-param reduced starcoder2
for 300 steps while the cluster lives through the paper's full event
repertoire:

  step   0: 2 workers active
  step  60: slot 2 joins (dynamic scale-up; LR rescales adaptively)
  step 119: slot 0 gets the 30 s revocation WARNING -> fast checkpoint
  step 120: slot 0 revoked (training continues on survivors; C3)
  step 180: slot 3 joins
  crash at step 240 -> restart from the newest valid checkpoint, finish.

    PYTHONPATH=src python examples/transient_training.py
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.config import (OptimizerConfig, ScheduleConfig, TrainConfig,
                          get_config)
from repro.core import (CheckpointManager, ElasticRuntime, RevocationEvent,
                        SparseCluster)
from repro.data.pipeline import ShardedDataset
from repro.models.builder import build_model
from repro.train.step import init_state

STEPS = 300


EVENTS = [
    RevocationEvent(step=60, slot=2, kind="join"),
    RevocationEvent(step=119, slot=0, kind="warn"),
    RevocationEvent(step=120, slot=0, kind="revoke"),
    RevocationEvent(step=180, slot=3, kind="join"),
]


def make_runtime(model, tcfg, ds, ckpt, upto_step=0):
    cluster = SparseCluster(max_slots=4)
    cluster.fill_and_activate(0, 0)
    cluster.fill_and_activate(1, 0)
    # a restart must replay membership changes up to the restore point
    # (in production this state lives in the cluster manager; here the
    # deterministic trace IS the manager)
    for e in EVENTS:
        if e.step < upto_step and e.kind == "join":
            cluster.fill_and_activate(e.slot, e.step)
        elif e.step < upto_step and e.kind == "revoke":
            cluster.revoke(e.slot, e.step)
    rt = ElasticRuntime(model, tcfg, ds, cluster, ckpt)
    rt.add_events([e for e in EVENTS if e.step >= upto_step])
    return rt


def main():
    cfg = get_config("starcoder2-3b", reduced=True)
    model = build_model(cfg)
    tcfg = TrainConfig(
        optimizer=OptimizerConfig(name="adamw", lr=1e-3, adaptive_lr=True,
                                  base_workers=2),
        schedule=ScheduleConfig(kind="cosine", warmup_steps=30,
                                total_steps=STEPS),
        checkpoint_every=60)
    ds = ShardedDataset(cfg, global_batch=16, seq_len=64)

    with tempfile.TemporaryDirectory() as d:
        ckpt = CheckpointManager(d, replicas=2)
        rt = make_runtime(model, tcfg, ds, ckpt, 0)
        state = init_state(model, tcfg, jax.random.key(0))

        print(f"phase 1: steps 0..239 (events: join@60, warn@119, "
              f"revoke@120, join@180)")
        state = rt.run(state, 240)
        print(f"  fast saves taken: {rt.fast_saves}")
        for m in rt.metrics_log[::40]:
            print(f"  step {m['step']:>4d}  active={m['active']}  "
                  f"lr {m['lr']:.2e}  loss {m['loss']:.4f}")

        print("phase 2: simulated crash at step 240 -> restore + finish")
        got = ckpt.restore_latest()
        assert got is not None
        step0, restored, _ = got
        print(f"  restored step {step0} "
              f"(<= 240; deterministic pipeline replays the gap)")
        rt2 = make_runtime(model, tcfg, ds, ckpt, upto_step=step0)
        state = rt2.run(restored, STEPS - step0, start_step=step0)
        last = rt2.metrics_log[-1]
        print(f"  finished: step {last['step']}  active={last['active']}  "
              f"loss {last['loss']:.4f}")
        first = rt.metrics_log[0]
        print(f"loss {first['loss']:.3f} -> {last['loss']:.3f} over "
              f"{STEPS} steps through 1 revocation + 2 joins + 1 restart")


if __name__ == "__main__":
    main()

"""``Trainer.fit`` keeps the held-expert layer's step metrics as gauges."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.config import OptimizerConfig, ScheduleConfig, TrainConfig, \
    get_config
from repro.data.pipeline import ShardedDataset
from repro.models.builder import build_model
from repro.models.ffn import HELD_AUX
from repro.train.step import init_state, make_train_step
from repro.train.trainer import Trainer

TCFG = TrainConfig(
    optimizer=OptimizerConfig(name="momentum", lr=1e-2, base_workers=1),
    schedule=ScheduleConfig(kind="constant", warmup_steps=1, total_steps=10),
    checkpoint_every=0)


@pytest.mark.parametrize("held", [0, 4])
def test_fit_records_the_expert_gauges(held):
    cfg = get_config("moonlight-16b-a3b", reduced=True).replace(
        experts_held=held)
    rec = obs.Recorder()
    tr = Trainer(build_model(cfg), TCFG,
                 ShardedDataset(cfg, global_batch=2, seq_len=16),
                 recorder=rec)
    seen = []
    tr.fit(tr.init_or_restore(), 2,
           on_step=lambda step, m: seen.append({k: float(m[k])
                                                for k in HELD_AUX}))
    stats = rec.metrics.to_stats()
    # the gauges hold the last step's readings
    assert {k: stats[k] for k in HELD_AUX} == seen[-1]
    share, load = seen[-1]["moe_held_share"], seen[-1]["moe_load_max"]
    if held:
        # 4 of 16 experts held: some, not all, of the assignments
        assert 0 < share < 1
    else:
        assert share == 1.0
    assert load >= 1.0


def test_dense_model_records_no_expert_gauges():
    cfg = get_config("starcoder2-3b", reduced=True)
    rec = obs.Recorder()
    tr = Trainer(build_model(cfg), TCFG,
                 ShardedDataset(cfg, global_batch=2, seq_len=16),
                 recorder=rec)
    tr.fit(tr.init_or_restore(), 1)
    assert not set(HELD_AUX) & set(rec.metrics.to_stats())


def test_microbatches_merge_the_expert_metrics():
    """Over microbatches the step's loss and held share are the mean of
    each microbatch's, and ``moe_load_max`` is their max."""
    cfg = get_config("moonlight-16b-a3b", reduced=True).replace(
        experts_held=4)
    model = build_model(cfg)
    state = init_state(model, TCFG, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (4, 16), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    one = jax.jit(make_train_step(model, TCFG))
    two = jax.jit(make_train_step(
        model, dataclasses.replace(TCFG, microbatches=2)))
    halves = [one(state, {k: v[2 * i:2 * i + 2] for k, v in batch.items()})[1]
              for i in range(2)]
    _, m = two(state, batch)
    loads = [float(h["moe_load_max"]) for h in halves]
    assert loads[0] != loads[1]
    assert float(m["moe_load_max"]) == max(loads)
    for k in ("loss", "moe_held_share"):
        assert float(m[k]) == pytest.approx(
            sum(float(h[k]) for h in halves) / 2, rel=1e-5)

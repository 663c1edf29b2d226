"""Trainer — checkpointed, restartable training loop.

The thin orchestration layer over ``make_train_step``: restore-on-start
(master-less checkpoint scan), periodic saves, revocation-warning fast
saves, and observability via an ``obs.Recorder`` (per-step spans plus the
``steps_total``/``step_latency_ms`` series, and the held-expert layer's
``moe_held_share``/``moe_load_max`` gauges; the legacy ``metrics_log``
list is kept as a plain-Python view of the same numbers). Elastic
membership is layered on top by ``core.elastic.ElasticRuntime``; this
class is the static-cluster loop the paper starts from (1/2/4/8 fixed
workers) and the restart harness both paths share.

Restart contract (paper C3): the data pipeline is pure in (step, shard,
num_shards), and ``step`` rides inside the checkpoint payload, so a
revocation + restore replays from the exact next batch — at most one
global batch of work is lost, bounded by checkpoint cadence for the
parameters themselves.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.config import TrainConfig
from repro.core.checkpoint import CheckpointManager
from repro.data.pipeline import ShardedDataset
from repro.models.builder import Model
from repro.models.ffn import HELD_AUX
from repro.train.step import TrainState, init_state, make_train_step

PyTree = Any


def evaluate_accuracy(model: Model, params: PyTree,
                      batch: Dict[str, jax.Array]) -> float:
    """Held-out top-1 accuracy of ``params`` on one eval batch.

    Classification accuracy for the resnet family, next-token accuracy
    for sequence families — the gym's eval metric and the quantity the
    sim-vs-train monotonicity contract is stated over.
    """
    logits, _aux = model.apply(params, batch)
    pred = jnp.argmax(logits, axis=-1)
    return float((pred == batch["labels"]).mean())


@dataclasses.dataclass
class Trainer:
    model: Model
    tcfg: TrainConfig
    dataset: ShardedDataset
    ckpt: Optional[CheckpointManager] = None
    log_every: int = 50
    recorder: Optional[obs.Recorder] = None

    def __post_init__(self):
        self.step_fn = jax.jit(make_train_step(self.model, self.tcfg))
        self.metrics_log: List[Dict[str, float]] = []
        self.rec = self.recorder if self.recorder is not None else obs.NULL

    # -- lifecycle ----------------------------------------------------------
    def init_or_restore(self, key: Optional[jax.Array] = None) -> TrainState:
        if self.ckpt is not None:
            got = self.ckpt.restore_latest()
            if got is not None:
                step, state, _extra = got
                return state
        key = key if key is not None else jax.random.key(self.tcfg.seed)
        return init_state(self.model, self.tcfg, key)

    def fit(self, state: TrainState, num_steps: int,
            lr_scale: float = 1.0,
            on_step: Optional[Callable[[int, Dict], None]] = None
            ) -> TrainState:
        start = int(state.step)
        rec = self.rec
        t0 = time.monotonic()
        for step in range(start, start + num_steps):
            ts = rec.now()
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                batch = self.dataset.global_batch_at(step)
                state, m = self.step_fn(state, batch, jnp.float32(lr_scale))
            if rec.enabled:
                dt = rec.now() - ts
                rec.span_at(obs.EV_STEP, cat=obs.CAT_TRAIN, t_wall=ts,
                            dur_wall=dt, sim_t=float(step), dur_sim=1.0,
                            loss=float(m["loss"]), mode="static")
                rec.metrics.counter("steps_total", mode="static").inc()
                rec.metrics.histogram("step_latency_ms").observe(dt * 1e3)
                for name in HELD_AUX:
                    if name in m:
                        rec.metrics.gauge(name).set(float(m[name]))
            if on_step is not None:
                on_step(step, m)
            if (step + 1) % self.log_every == 0 or step == start:
                self.metrics_log.append({
                    "step": step, "loss": float(m["loss"]),
                    "grad_norm": float(m["grad_norm"]), "lr": float(m["lr"]),
                    "wall_s": time.monotonic() - t0,
                })
            if (self.ckpt is not None and self.tcfg.checkpoint_every
                    and (step + 1) % self.tcfg.checkpoint_every == 0):
                self.ckpt.save(step + 1, state)
        return state

    # revocation-warning hook (GCE: 30 s). One replica, fsync'd, returns.
    def on_revocation_warning(self, state: TrainState) -> None:
        if self.ckpt is not None:
            self.ckpt.save(int(state.step), state, fast=True,
                           extra={"reason": "revocation_warning"})

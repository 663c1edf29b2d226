"""Trainer — checkpointed, restartable training loop.

The one loop over ``make_train_step``. ``run_step`` is every step of it:
the jitted step under the profiler's ``train`` step annotation, the step
span and ``steps_total``/``step_latency_ms`` series (and the held-expert
gauges) on an ``obs.Recorder``, and the periodic save. ``fit`` drives it
over the static cluster the paper starts from (1/2/4/8 fixed workers),
with restore-on-start and the plain-Python ``metrics_log``;
``core.elastic.ElasticRuntime`` drives it through membership events.

Restart contract (paper C3): the data pipeline is pure in (step, shard,
num_shards), and ``step`` rides inside the checkpoint payload, so a
revocation + restore replays from the exact next batch — at most one
global batch of work is lost, bounded by checkpoint cadence for the
parameters themselves.
"""
from __future__ import annotations

import dataclasses
import time
from typing import TYPE_CHECKING, Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.config import TrainConfig
from repro.data.pipeline import ShardedDataset
from repro.models.builder import Model
from repro.models.ffn import HELD_AUX
from repro.train.step import TrainState, init_state, make_train_step

if TYPE_CHECKING:       # core.elastic drives this loop: no import cycle
    from repro.core.checkpoint import CheckpointManager

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

    def run_step(self, state: TrainState, step: int,
                 batch_at: Callable[[int], Dict[str, jax.Array]],
                 lr_scale=1.0, mode: str = "static", **span
                 ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        """One step: ``batch_at(step)`` through the jitted step, recorded
        under ``mode`` (``span`` adds fields to its ``EV_STEP`` span), and
        the save every ``checkpoint_every`` steps."""
        rec = self.rec
        ts = rec.now()
        with jax.profiler.StepTraceAnnotation("train", step_num=step):
            state, m = self.step_fn(state, batch_at(step),
                                    jnp.float32(lr_scale))
        if rec.enabled:
            loss = float(m["loss"])
            dt = rec.now() - ts
            rec.span_at(obs.EV_STEP, cat=obs.CAT_TRAIN, t_wall=ts,
                        dur_wall=dt, sim_t=float(step), dur_sim=1.0,
                        loss=loss, mode=mode, **span)
            rec.metrics.counter("steps_total", mode=mode).inc()
            rec.metrics.histogram("step_latency_ms").observe(dt * 1e3)
            for name in HELD_AUX:
                if name in m:
                    rec.metrics.gauge(name).set(float(m[name]))
        if (self.ckpt is not None and self.tcfg.checkpoint_every
                and (step + 1) % self.tcfg.checkpoint_every == 0):
            self.ckpt.save(step + 1, state)
        return state, m

    def fit(self, state: TrainState, num_steps: int,
            lr_scale: float = 1.0,
            on_step: Optional[Callable[[int, Dict], None]] = None
            ) -> TrainState:
        start = int(state.step)
        t0 = time.monotonic()
        for step in range(start, start + num_steps):
            state, m = self.run_step(state, step, self.dataset.global_batch_at,
                                     lr_scale)
            if on_step is not None:
                on_step(step, m)
            if (step + 1) % self.log_every == 0 or step == start:
                self.metrics_log.append({
                    "step": step, "loss": float(m["loss"]),
                    "grad_norm": float(m["grad_norm"]), "lr": float(m["lr"]),
                    "wall_s": time.monotonic() - t0,
                })
        return state

    # revocation-warning hook (GCE: 30 s). One replica, fsync'd, returns.
    def on_revocation_warning(self, state: TrainState, **extra) -> bool:
        """Fast-save ``state``; ``extra`` joins the save's metadata.
        False where there is no checkpoint manager."""
        if self.ckpt is None:
            return False
        self.ckpt.save(int(state.step), state, fast=True,
                       extra={"reason": "revocation_warning", **extra})
        self.rec.metrics.counter("fast_saves_total").inc()
        return True

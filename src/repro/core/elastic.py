"""Elastic runtime — dynamic cluster membership without recompilation (C3/C5).

The paper's sparse mapping fills worker *slots* opportunistically; training
must keep stepping as slots fill and empty. Membership is data, not program
structure: the runtime trains through the one train step and the Trainer's
per-step code, and only chooses what it feeds them:

- the slot-major global batch, ``max_slots * per_slot`` rows, whose
  ``row_weight`` leaf weighs an active slot's rows 1 and an empty slot's 0
  (masked), or a slot's first ``count`` rows, its allocated examples
  (hetero): the weighted mean is the plain mean over the dynamic batch;
- the adaptive-LR multiplier (paper C6) as the step's runtime
  ``lr_scale``: active slots over ``base_workers`` (masked) or the
  allocator's aggregate-throughput ratio (hetero).

Shapes never change, so membership changes NEVER recompile.

Revocation flow (GCE gives a 30 s warning):
    warn(slot) -> fast_save (one replica, fsync'd)   [checkpoint.py]
               -> revoke(slot) -> row weights + LR rescale
               -> shard reassignment is implicit: batches are pure
                  functions of (step, shard, num_shards)   [data/pipeline.py]
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.config import TrainConfig
from repro.core.cluster import SparseCluster
from repro.models.builder import Model
from repro.train.step import TrainState
from repro.train.trainer import Trainer


# ---------------------------------------------------------------------------
# Membership as host data: live rows per slot, the slot batch, the LR scale
# ---------------------------------------------------------------------------

def slot_counts(cluster: SparseCluster, per_slot: int, allocator=None
                ) -> np.ndarray:
    """Live rows per slot, ``(max_slots,)`` float32: ``per_slot`` for an
    active slot and 0 for an empty one (masked), or the allocator's
    counts (``hetero.DynamicBatchAllocator``) capped at the layout's
    ``per_slot`` (hetero)."""
    if allocator is None:
        counts = np.zeros((cluster.max_slots,), np.float32)
        counts[cluster.active_slots()] = per_slot
        return counts
    return np.minimum(allocator.allocation().counts,
                      per_slot).astype(np.float32)


def lr_scale(tcfg: TrainConfig, counts: np.ndarray,
             lr_ratio: Optional[float] = None) -> np.float32:
    """The step's LR multiplier, in float32. Adaptive (paper C6): active
    slots over ``base_workers`` (masked), or the allocator's
    aggregate-throughput ``lr_ratio`` (hetero). Naive: configured slots
    over ``base_workers``, whatever is active."""
    opt = tcfg.optimizer
    if not opt.adaptive_lr:
        return np.float32(len(counts) / opt.base_workers)
    if lr_ratio is None:
        n_active = np.float32(max(int((counts > 0).sum()), 1))
        return n_active / np.float32(opt.base_workers)
    return np.maximum(np.float32(lr_ratio), np.float32(1e-9))


def slot_batch(dataset, step: int, counts: np.ndarray
               ) -> Dict[str, jnp.ndarray]:
    """The slot-major global batch with its ``row_weight``: slot ``s``
    weighs its first ``counts[s]`` rows 1 and the rest 0. Every slot's
    rows come from its *own* deterministic stream (pure in (step, shard,
    num_shards=max_slots)); empty slots get placeholder rows, weighed 0."""
    slots = len(counts)
    parts = [dataset.shard_batch(step, s, slots) for s in range(slots)]
    batch = jax.tree.map(lambda *xs: jnp.concatenate(xs), *parts)
    per = dataset.global_batch // slots
    live = np.arange(per)[None, :] < counts[:, None]
    batch["row_weight"] = jnp.asarray(live.reshape(-1), jnp.float32)
    return batch


# ---------------------------------------------------------------------------
# ElasticRuntime: event plumbing between cluster, checkpoint, and the step
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RevocationEvent:
    step: int
    slot: int
    kind: str            # "warn" | "revoke" | "join"
    server_kind: str = "K80"
    region: str = "us-east1"


class ElasticRuntime:
    """Drives elastic training through a revocation/join event trace.

    The trace abstraction lets tests and benchmarks replay *deterministic*
    membership histories (e.g. the paper's Fig 5 schedule: +1 worker every
    16K steps) while production wires the same callbacks to the cloud
    metadata server's preemption notice. Each step runs through
    ``Trainer.run_step``; the runtime adds only the membership.
    """

    def __init__(self, model: Model, tcfg: TrainConfig, dataset,
                 cluster: SparseCluster, ckpt=None, allocator=None,
                 recorder: Optional[obs.Recorder] = None):
        self.tcfg = tcfg
        self.dataset = dataset
        self.cluster = cluster
        self.ckpt = ckpt
        # allocator (hetero.DynamicBatchAllocator): per-slot example counts
        # re-solved on membership bumps; None = homogeneous masked mode
        self.allocator = allocator
        self.trainer = Trainer(model, tcfg, dataset, ckpt, recorder=recorder)
        self.rec = self.trainer.rec
        self.mode = "masked" if allocator is None else "hetero"
        self.events: Dict[int, list] = {}
        self.fast_saves = 0
        self.metrics_log: list = []

    def add_events(self, events) -> None:
        for e in events:
            self.events.setdefault(e.step, []).append(e)

    def _apply_events(self, state: TrainState, step: int) -> None:
        rec = self.rec
        for e in self.events.get(step, ()):
            # training's sim clock is the step index: membership events
            # share an axis with the EV_STEP spans in the timeline
            if e.kind == "warn":
                rec.instant(obs.EV_REVOKE_WARN, cat=obs.CAT_TRAIN,
                            track=f"slot{e.slot}", sim_t=float(step),
                            kind=e.server_kind, region=e.region,
                            fast_save=self.ckpt is not None)
                if self.trainer.on_revocation_warning(state, slot=e.slot):
                    self.fast_saves += 1
            elif e.kind == "revoke":
                rec.instant(obs.EV_REVOKE_FIRE, cat=obs.CAT_TRAIN,
                            track=f"slot{e.slot}", sim_t=float(step),
                            kind=e.server_kind, region=e.region)
                rec.metrics.counter("revocations_total", kind=e.server_kind,
                                    region=e.region).inc()
                self.cluster.revoke(e.slot, step)
            elif e.kind == "join":
                rec.instant(obs.EV_SLOT_JOIN, cat=obs.CAT_TRAIN,
                            track=f"slot{e.slot}", sim_t=float(step),
                            kind=e.server_kind, region=e.region)
                self.cluster.fill_and_activate(e.slot, step,
                                               kind=e.server_kind,
                                               region=e.region)

    def run(self, state: TrainState, num_steps: int, start_step: int = 0
            ) -> TrainState:
        rec = self.rec
        per = self.dataset.global_batch // self.cluster.max_slots
        for step in range(start_step, start_step + num_steps):
            self._apply_events(state, step)
            if self.cluster.n_active == 0:
                raise RuntimeError(f"no active workers at step {step}")
            counts = slot_counts(self.cluster, per, self.allocator)
            ratio = (None if self.allocator is None
                     else self.allocator.allocation().lr_ratio)
            n_active = int((counts > 0).sum())
            state, m = self.trainer.run_step(
                state, step, lambda s: slot_batch(self.dataset, s, counts),
                lr_scale(self.tcfg, counts, ratio), mode=self.mode,
                n_active=n_active)
            self.metrics_log.append(
                {"step": step, "loss": float(m["loss"]),
                 "active": n_active, "lr": float(m["lr"])})
            if rec.enabled:
                rec.metrics.gauge("workers", mode=self.mode).set(n_active)
                if self.allocator is not None:
                    rec.metrics.gauge("examples_per_step").set(
                        float(counts.sum()))
        return state

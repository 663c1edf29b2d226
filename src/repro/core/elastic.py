"""Elastic runtime — dynamic cluster membership without recompilation (C3/C5).

The paper's sparse mapping fills worker *slots* opportunistically; training
must keep stepping as slots fill and empty. Two TPU-native execution modes:

**masked** (default, used single-host and inside one slice)
    The global batch is laid out as ``(max_slots, per_slot, ...)`` with a
    runtime ``active_mask`` of shape ``(max_slots,)``. Inactive slots
    contribute zero weight to the loss, and the adaptive-LR multiplier
    (paper C6) is ``mask.sum() / base_workers`` — a *runtime scalar*, so
    membership changes NEVER recompile or change shapes. This is the
    sparse-mapping idea made SPMD-friendly: the mesh template is sized for
    ``max_slots`` and occupancy is data, not program structure.

**remesh** (multi-slice production path)
    When a whole data-parallel slice is revoked the survivor set forms a
    smaller mesh; jitted steps are cached per distinct active-count so a
    membership size seen before costs zero recompilation (the paper's
    "dynamic cluster" join/leave maps to a template-cache hit).

Revocation flow (GCE gives a 30 s warning):
    warn(slot) -> fast_save (one replica, fsync'd)   [checkpoint.py]
               -> revoke(slot) -> mask update / remesh -> LR rescale
               -> shard reassignment is implicit: batches are pure
                  functions of (step, shard, num_shards)   [data/pipeline.py]
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.config import ModelConfig, TrainConfig
from repro.core.cluster import SparseCluster
from repro.models import modality
from repro.models.builder import Model
from repro.obs.profiling import region
from repro.train.step import TrainState, cross_entropy, _token_weights

PyTree = Any


# ---------------------------------------------------------------------------
# Masked-membership train step (fixed shapes; no recompile on change)
# ---------------------------------------------------------------------------

def _make_row_weighted_loss(model: Model, tcfg: TrainConfig) -> Callable:
    """Loss over a slot-major batch with arbitrary per-row weights.

    ``row_w`` has shape ``(max_slots * per_slot,)``; a row's weight is its
    share of the loss mean, so a slot's contribution is proportional to
    its weighted row count — the unbiasedness seam both the masked (0/1
    slot mask) and the hetero (per-slot example counts) steps build on.
    """
    cfg = model.cfg

    def loss_fn(params, batch, row_w):
        flat = jax.tree.map(
            lambda x: x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:]),
            batch)
        remat = tcfg.remat != "none"
        logits, aux = model.apply(params, flat, remat=remat)
        with region("head"):
            if cfg.family == "resnet":
                lse = jax.nn.logsumexp(logits.astype(jnp.float32), -1)
                onehot = jax.nn.one_hot(flat["labels"], logits.shape[-1],
                                        dtype=jnp.float32)
                nll = lse - jnp.sum(onehot * logits.astype(jnp.float32), -1)
                loss = jnp.sum(nll * row_w) / jnp.maximum(jnp.sum(row_w),
                                                          1.0)
            else:
                S = logits.shape[1]
                w = _token_weights(cfg, flat, S) * row_w[:, None]
                loss = cross_entropy(logits, flat["labels"], w)
        total = loss + cfg.router_aux_coef * aux
        return total, {"loss": loss, "aux": aux}

    return loss_fn


def _apply_grads(state: TrainState, grads, lr_scale, tcfg: TrainConfig,
                 opt, sched, metrics) -> Tuple[TrainState, Dict]:
    from repro.optim.optimizers import clip_by_global_norm, global_norm

    with region("update"):
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        if tcfg.optimizer.grad_clip > 0:
            grads, gnorm = clip_by_global_norm(grads,
                                               tcfg.optimizer.grad_clip)
        else:
            gnorm = global_norm(grads)
        lr = tcfg.optimizer.lr * sched(state.step) * lr_scale
        updates, new_opt = opt.update(grads, state.opt, state.params, lr)
        new_params = jax.tree.map(lambda p, u: (p + u).astype(p.dtype),
                                  state.params, updates)
    new_state = TrainState(params=new_params, opt=new_opt,
                           step=state.step + 1)
    return new_state, dict(metrics, grad_norm=gnorm, lr=lr)


def make_masked_train_step(model: Model, tcfg: TrainConfig
                           ) -> Callable[..., Tuple[TrainState, Dict]]:
    """Elastic train step over a slot-major batch.

    batch leaves: (max_slots, per_slot, ...). active_mask: (max_slots,)
    float32 in {0,1}. Loss averages over *active* tokens only; the LR
    multiplier follows the paper's adaptive rule when tcfg.optimizer
    .adaptive_lr, else the naive (configured-slots) rule.
    """
    from repro.optim import make_optimizer, make_schedule

    opt = make_optimizer(tcfg.optimizer)
    sched = make_schedule(tcfg.schedule)
    loss_fn = _make_row_weighted_loss(model, tcfg)

    def train_step(state: TrainState, batch: Dict[str, jax.Array],
                   active_mask: jax.Array
                   ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        per = next(iter(batch.values())).shape[1]
        row_w = jnp.repeat(active_mask, per)                # (slots*per,)
        (_, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, row_w), has_aux=True
        )(state.params)
        n_active = jnp.maximum(active_mask.sum(), 1.0)
        if tcfg.optimizer.adaptive_lr:
            lr_scale = n_active / tcfg.optimizer.base_workers       # C6: fix
        else:
            lr_scale = jnp.float32(active_mask.shape[0]             # naive TF
                                   / tcfg.optimizer.base_workers)
        new_state, out = _apply_grads(state, grads, lr_scale, tcfg, opt,
                                      sched, metrics)
        return new_state, dict(out, active=n_active)

    return train_step


def make_hetero_train_step(model: Model, tcfg: TrainConfig
                           ) -> Callable[..., Tuple[TrainState, Dict]]:
    """Heterogeneity-aware elastic step: ragged slot batches, fixed shapes.

    ``slot_counts`` (``(max_slots,)`` float32) is the allocator's per-slot
    example count: slot ``s`` contributes its first ``slot_counts[s]``
    rows of the ``(max_slots, per_slot, ...)`` layout (a K80 slot carries
    fewer live rows than a V100 slot). Rows past the count are masked, so
    per-slot loss weight is proportional to allocated examples — the
    weighted mean over live rows equals the plain mean over the dynamic
    global batch, which is what makes the gradient an unbiased estimate
    under *any* allocation. ``lr_ratio`` is the allocator's
    aggregate-throughput ratio, generalizing the paper's adaptive-LR rule
    (C6) beyond worker counts; both inputs are runtime data, so
    allocation changes NEVER recompile.
    """
    from repro.optim import make_optimizer, make_schedule

    opt = make_optimizer(tcfg.optimizer)
    sched = make_schedule(tcfg.schedule)
    loss_fn = _make_row_weighted_loss(model, tcfg)

    def train_step(state: TrainState, batch: Dict[str, jax.Array],
                   slot_counts: jax.Array, lr_ratio: jax.Array
                   ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        slots, per = next(iter(batch.values())).shape[:2]
        row_w = (jnp.arange(per, dtype=jnp.float32)[None, :]
                 < slot_counts[:, None]).astype(jnp.float32).reshape(-1)
        (_, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, row_w), has_aux=True
        )(state.params)
        if tcfg.optimizer.adaptive_lr:
            lr_scale = jnp.maximum(lr_ratio, 1e-9)
        else:
            lr_scale = jnp.float32(slots / tcfg.optimizer.base_workers)
        new_state, out = _apply_grads(state, grads, lr_scale, tcfg, opt,
                                      sched, metrics)
        return new_state, dict(out, active=(slot_counts > 0).sum(),
                               examples=slot_counts.sum())

    return train_step


def slot_batch(cfg: ModelConfig, dataset, step: int, cluster: SparseCluster
               ) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
    """Assemble the (max_slots, per_slot, ...) batch + active mask.

    Every slot's rows are generated from its *own* deterministic stream
    (pure in (step, shard, num_shards=max_slots)); inactive slots still
    get placeholder rows (masked out) so shapes never change.
    """
    slots = cluster.max_slots
    parts = [dataset.shard_batch(step, s, slots) for s in range(slots)]
    batch = jax.tree.map(lambda *xs: jnp.stack(xs), *parts)
    mask = np.zeros((slots,), np.float32)
    for s in cluster.active_slots():
        mask[s] = 1.0
    return batch, jnp.asarray(mask)


# ---------------------------------------------------------------------------
# Remesh-mode template cache (multi-slice path; exercised by the dry-run)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RemeshCache:
    """jit-compiled train steps keyed by active-slice count.

    Growing/shrinking to a previously seen size is a cache hit (the paper's
    dynamic cluster without the re-provisioning stall). Compilation happens
    at most once per distinct size — at 1000+ nodes sizes repeat (you lose
    and regain slices), so steady-state recompiles go to zero.
    """
    build: Callable[[int], Callable]          # n_active -> compiled step
    _cache: Dict[int, Callable] = dataclasses.field(default_factory=dict)
    compile_count: int = 0

    def step_for(self, n_active: int) -> Callable:
        if n_active not in self._cache:
            self._cache[n_active] = self.build(n_active)
            self.compile_count += 1
        return self._cache[n_active]


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
    """Drives masked elastic training through a revocation/join event trace.

    The trace abstraction lets tests and benchmarks replay *deterministic*
    membership histories (e.g. the paper's Fig 5 schedule: +1 worker every
    16K steps) while production wires the same callbacks to the cloud
    metadata server's preemption notice.
    """

    def __init__(self, model: Model, tcfg: TrainConfig, dataset,
                 cluster: SparseCluster, ckpt=None, allocator=None,
                 recorder: Optional[obs.Recorder] = None):
        self.model = model
        self.tcfg = tcfg
        self.dataset = dataset
        self.cluster = cluster
        self.ckpt = ckpt
        # allocator (hetero.DynamicBatchAllocator): per-slot example counts
        # re-solved on membership bumps; None = homogeneous masked mode
        self.allocator = allocator
        self.rec = recorder if recorder is not None else obs.NULL
        self.mode = "masked" if allocator is None else "hetero"
        if allocator is None:
            self.step_fn = jax.jit(make_masked_train_step(model, tcfg))
        else:
            self.step_fn = jax.jit(make_hetero_train_step(model, tcfg))
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
                if self.ckpt is not None:       # 30 s window: one fsync'd copy
                    self.ckpt.save(step, state, fast=True,
                                   extra={"reason": "revocation_warning",
                                          "slot": e.slot})
                    self.fast_saves += 1
                    rec.metrics.counter("fast_saves_total").inc()
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
        for step in range(start_step, start_step + num_steps):
            self._apply_events(state, step)
            if self.cluster.n_active == 0:
                raise RuntimeError(f"no active workers at step {step}")
            t0 = rec.now()
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                batch, mask = slot_batch(self.model.cfg, self.dataset, step,
                                         self.cluster)
                if self.allocator is not None:
                    per = next(iter(batch.values())).shape[1]
                    alloc = self.allocator.allocation()
                    counts = np.minimum(alloc.counts, per)  # layout capacity
                    state, m = self.step_fn(state, batch,
                                            jnp.asarray(counts, jnp.float32),
                                            jnp.float32(alloc.lr_ratio))
                else:
                    state, m = self.step_fn(state, batch, mask)
                loss = float(m["loss"])
                n_active = int(m["active"])
            self.metrics_log.append(
                {"step": step, "loss": loss,
                 "active": n_active, "lr": float(m["lr"])})
            if rec.enabled:
                dt = rec.now() - t0
                rec.span_at(obs.EV_STEP, cat=obs.CAT_TRAIN,
                            t_wall=t0, dur_wall=dt,
                            sim_t=float(step), dur_sim=1.0,
                            loss=loss, n_active=n_active, mode=self.mode)
                rec.metrics.counter("steps_total", mode=self.mode).inc()
                rec.metrics.histogram("step_latency_ms").observe(dt * 1e3)
                rec.metrics.gauge("workers", mode=self.mode).set(n_active)
                if self.allocator is not None:
                    rec.metrics.gauge("examples_per_step").set(
                        float(m["examples"]))
            if (self.ckpt is not None and self.tcfg.checkpoint_every
                    and (step + 1) % self.tcfg.checkpoint_every == 0):
                self.ckpt.save(step + 1, state)
        return state

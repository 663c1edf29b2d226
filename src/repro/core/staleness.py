"""AsyncPSSimulator — functionally exact async parameter-server training (C4).

The paper's training mode is TensorFlow's between-graph asynchronous
replication: each worker pulls the current model from the PS, computes a
gradient on its own shard, and pushes it; the PS applies pushes in arrival
order with NO barrier. Gradients are therefore computed at *stale*
parameters, and the staleness distribution is what degrades converged
accuracy as clusters grow (Tables I/III: 93.07% @1 -> 88.65% @8 K80).

XLA SPMD cannot express this (it is a barrier machine), so the production
TPU path uses elastic synchronous DP (see elastic.py and DESIGN.md §2). To
keep every paper claim *testable in real JAX training*, this module runs K
logical async workers inside one process with exact event-ordering:

  - a virtual clock per worker; completion times from per-kind step rates
    (pricing.SERVER_TYPES) with optional jitter,
  - the PS applies each push immediately (SGD-momentum, the paper's
    optimizer) at the LR given by the schedule x scaling rule,
  - staleness of a push = #PS-updates since that worker's pull,
  - revocation/join events edit the worker set mid-run (sparse mapping),
  - adaptive vs naive LR: scale by ACTIVE vs CONFIGURED workers (C6).

The gradient/update math runs under jit; only event ordering is host-side,
so this trains real models (used by benchmarks/staleness_accuracy.py and
fig5_dynamic_cluster.py to reproduce the paper's accuracy deltas).
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import OptimizerConfig, ScheduleConfig
from repro.core import pricing
from repro.optim import make_optimizer, make_schedule
from repro.optim.optimizers import clip_by_global_norm
from repro.train.step import apply_update

PyTree = Any


@dataclasses.dataclass
class AsyncWorker:
    wid: int
    kind: str = "K80"
    rate: float = 0.0            # steps/s; 0 -> use the kind's table rate
    join_t: float = 0.0          # wall-clock arrival (sparse mapping)
    revoke_t: float = np.inf     # wall-clock revocation
    # runtime:
    snapshot: PyTree = None      # stale params the worker computes on
    pull_version: int = 0        # PS update count at last pull

    def step_rate(self) -> float:
        return self.rate or pricing.SERVER_TYPES[self.kind].steps_per_sec


@dataclasses.dataclass
class AsyncResult:
    params: PyTree
    updates_applied: int
    staleness: np.ndarray              # per-push staleness
    active_worker_curve: List[Tuple[float, int]]   # (t, n_active) steps
    loss_curve: List[Tuple[int, float]]
    lr_history: List[float] = dataclasses.field(default_factory=list)
    staleness_by_worker: Dict[int, List[int]] = dataclasses.field(
        default_factory=dict)          # wid -> its pushes' staleness

    @property
    def mean_staleness(self) -> float:
        return float(self.staleness.mean()) if len(self.staleness) else 0.0

    def staleness_histogram(self) -> Dict[int, int]:
        """``{staleness -> push count}`` over every applied push — the
        distribution the paper's accuracy-vs-workers mechanism rides on
        (gym ledgers report it per episode)."""
        if not len(self.staleness):
            return {}
        vals, counts = np.unique(self.staleness, return_counts=True)
        return {int(v): int(c) for v, c in zip(vals, counts)}


class AsyncPSSimulator:
    """Event-ordered async-PS training of a real JAX model."""

    def __init__(self, loss_fn: Callable[[PyTree, Dict], jax.Array],
                 params: PyTree,
                 ocfg: OptimizerConfig,
                 scfg: ScheduleConfig,
                 *, grad_clip: Optional[float] = None):
        self.opt = make_optimizer(ocfg)
        self.sched = make_schedule(scfg)
        self.ocfg = ocfg
        self.params = params
        self.opt_state = self.opt.init(params)
        self.version = 0
        clip = ocfg.grad_clip if grad_clip is None else grad_clip

        def push(ps_params, opt_state, worker_params, batch, lr):
            # async-PS semantic: grad at STALE params, applied to CURRENT.
            grads = jax.grad(lambda p: loss_fn(p, batch))(worker_params)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            if clip and clip > 0:
                grads, _ = clip_by_global_norm(grads, clip)
            return apply_update(self.opt, grads, opt_state, ps_params, lr)

        self._push = jax.jit(push)
        self._loss = jax.jit(loss_fn)

    def run(self, workers: List[AsyncWorker],
            batch_fn: Callable[[int, int], Dict],
            total_updates: int,
            *, seed: int = 0, jitter: float = 0.05,
            adaptive_lr: bool = True,
            configured_workers: Optional[int] = None,
            eval_every: int = 0,
            eval_fn: Optional[Callable[[PyTree], float]] = None
            ) -> AsyncResult:
        """Run until the PS has applied ``total_updates`` pushes.

        batch_fn(update_index, worker_id) -> batch dict (pure; the data
        pipeline's determinism contract). configured_workers defaults to
        len(workers) — the TF slot count used by the NAIVE lr rule.
        """
        rng = np.random.default_rng(seed)
        configured = configured_workers or len(workers)
        for w in workers:
            w.snapshot = self.params
            w.pull_version = self.version

        # priority queue of (completion_time, wid)
        pq: List[Tuple[float, int]] = []
        alive: Dict[int, AsyncWorker] = {}

        def schedule(w: AsyncWorker, now: float):
            dt = 1.0 / w.step_rate()
            dt *= 1.0 + jitter * rng.standard_normal() if jitter else 1.0
            heapq.heappush(pq, (now + max(dt, 1e-6), w.wid))

        for w in workers:
            if w.join_t <= 0:
                alive[w.wid] = w
                schedule(w, 0.0)
        pending = sorted((w for w in workers if w.join_t > 0),
                         key=lambda w: w.join_t)

        staleness: List[int] = []
        by_worker: Dict[int, List[int]] = {}
        curve: List[Tuple[float, int]] = [(0.0, len(alive))]
        losses: List[Tuple[int, float]] = []
        lr_hist: List[float] = []
        t = 0.0

        while self.version < total_updates and (pq or pending):
            # admit joins that have arrived by the head event's time
            if pending and (not pq or pending[0].join_t <= pq[0][0]):
                w = pending.pop(0)
                t = max(t, w.join_t)
                w.snapshot, w.pull_version = self.params, self.version
                alive[w.wid] = w
                schedule(w, t)
                curve.append((t, len(alive)))
                continue
            t, wid = heapq.heappop(pq)
            w = alive.get(wid)
            if w is None:
                continue
            if t >= w.revoke_t:                      # revoked mid-step: push lost
                del alive[wid]
                curve.append((t, len(alive)))
                continue

            lr_workers = len(alive) if adaptive_lr else configured
            lr = (self.ocfg.lr * float(self.sched(self.version))
                  * lr_workers / self.ocfg.base_workers)
            lr_hist.append(lr)
            batch = batch_fn(self.version, wid)
            self.params, self.opt_state = self._push(
                self.params, self.opt_state, w.snapshot, batch,
                jnp.float32(lr))
            staleness.append(self.version - w.pull_version)
            by_worker.setdefault(wid, []).append(self.version
                                                 - w.pull_version)
            self.version += 1
            w.snapshot, w.pull_version = self.params, self.version
            schedule(w, t)

            if eval_every and eval_fn and self.version % eval_every == 0:
                losses.append((self.version, float(eval_fn(self.params))))

        return AsyncResult(params=self.params, updates_applied=self.version,
                           staleness=np.asarray(staleness, np.int64),
                           active_worker_curve=curve, loss_curve=losses,
                           lr_history=lr_hist, staleness_by_worker=by_worker)


def sync_baseline(loss_fn, params: PyTree, ocfg: OptimizerConfig,
                  scfg: ScheduleConfig, batch_fn, total_updates: int
                  ) -> PyTree:
    """Single-worker synchronous SGD — the staleness-free control arm."""
    sim = AsyncPSSimulator(loss_fn, params, ocfg, scfg)
    w = [AsyncWorker(wid=0)]
    out = sim.run(w, batch_fn, total_updates, jitter=0.0, adaptive_lr=True)
    assert out.mean_staleness == 0.0     # one worker can never be stale
    return out.params

"""train_step / serve_step factories.

``make_train_step`` builds the jittable SPMD step: loss -> grad (with remat
inside the model's scan-over-layers) -> clip -> LR schedule x adaptive
worker scale -> optimizer update. Microbatching accumulates gradients with
``lax.scan`` so the activation peak is one microbatch while collectives
amortize over the full batch. The adaptive-LR multiplier (paper C6) enters
as a *runtime scalar* so elastic membership changes never recompile.

Cross-entropy uses the one-hot/elementwise form: with logits sharded
(batch over 'data', vocab over 'model'), the one-hot product keeps every
op elementwise + reduction on the existing layout, so GSPMD inserts one
small all-reduce instead of re-gathering the (B, S, V) logits.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, TrainConfig
from repro.models import modality
from repro.models.builder import Model
from repro.obs.profiling import region
from repro.optim import make_optimizer, make_schedule
from repro.optim.optimizers import clip_by_global_norm

PyTree = Any


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: PyTree
    opt: PyTree
    step: jax.Array          # int32 scalar


def init_state(model: Model, tcfg: TrainConfig, key: jax.Array,
               unboxed_params: Optional[PyTree] = None) -> TrainState:
    from repro.models import layers as L
    params = unboxed_params if unboxed_params is not None \
        else L.unbox(model.init(key))
    opt = make_optimizer(tcfg.optimizer).init(params)
    return TrainState(params=params, opt=opt, step=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def _token_weights(cfg: ModelConfig, batch: Dict[str, jax.Array],
                   S: int) -> jax.Array:
    """Per-position loss weights; masks the VLM image prefix."""
    if cfg.family == "vlm":
        n_img, _ = modality.vlm_split(cfg, S)
        pos = jnp.arange(S)
        return (pos >= n_img).astype(jnp.float32)[None, :]
    return jnp.ones((1, S), jnp.float32)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  weights: Optional[jax.Array] = None) -> jax.Array:
    """Stable CE via one-hot (keeps the sharded (B,S,V) layout intact)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    gold = jnp.sum(onehot * logits, axis=-1)
    nll = lse - gold
    if weights is None:
        return nll.mean()
    w = jnp.broadcast_to(weights, nll.shape)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def loss_fn(model: Model, params: PyTree, batch: Dict[str, jax.Array],
            tcfg: TrainConfig) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    cfg = model.cfg
    remat = tcfg.remat != "none"
    logits, aux = model.apply(params, batch, remat=remat)
    with region("head"):
        if cfg.family == "resnet":
            loss = cross_entropy(logits, batch["labels"])
        else:
            S = logits.shape[1]
            w = _token_weights(cfg, batch, S)
            loss = cross_entropy(logits, batch["labels"], w)
    total = loss + cfg.router_aux_coef * aux
    return total, {"loss": loss, "aux": aux}


# ---------------------------------------------------------------------------
# train_step
# ---------------------------------------------------------------------------

def make_train_step(model: Model, tcfg: TrainConfig, param_shardings=None,
                    zero1_mask=None
                    ) -> Callable[..., Tuple[TrainState, Dict[str, jax.Array]]]:
    """``param_shardings`` (optional tree of NamedShardings matching the
    params) unlocks the SPMD communication controls:

    - gradients are pinned to the param sharding BEFORE the fp32 cast, so
      the DP reduction is a reduce-scatter at ``tcfg.grad_dtype`` (bf16
      halves the wire) instead of GSPMD's fp32 all-reduce after the cast;
    - layout "zero1": the bf16 compute copy is gathered ONCE per step
      (replicated through fwd+bwd) — per-layer FSDP gathers collapse to a
      single params-sized all-gather. ``zero1_mask`` (bool tree, optional)
      limits the gather to selected leaves: expert weights stay EP-sharded
      (gathering every expert to every device would undo EP).
    """
    opt = make_optimizer(tcfg.optimizer)
    sched = make_schedule(tcfg.schedule)
    base_lr = tcfg.optimizer.lr

    replicated = None
    if param_shardings is not None and tcfg.layout == "zero1":
        from jax.sharding import NamedSharding, PartitionSpec
        mask = zero1_mask if zero1_mask is not None else jax.tree.map(
            lambda s: True, param_shardings)
        replicated = jax.tree.map(
            lambda s, m: (NamedSharding(s.mesh, PartitionSpec())
                          if m else s),
            param_shardings, mask)

    def grads_of(params, batch):
        compute_dt = (jnp.bfloat16 if tcfg.grad_dtype == "bfloat16"
                      else None)
        p = params
        if compute_dt is not None:
            # Differentiate wrt a bf16 view: the grad reduce then moves
            # bf16 on the wire; the fp32 master update happens after the
            # cast-back. Pin the cast output to the PARAM sharding so the
            # downcast happens shard-local, BEFORE any gather.
            p = jax.tree.map(lambda q: q.astype(compute_dt), p)
            if param_shardings is not None:
                p = jax.tree.map(jax.lax.with_sharding_constraint,
                                 p, param_shardings)
        if replicated is not None:
            # ZeRO-1: gather the compute copy once; fwd/bwd reuse it.
            p = jax.tree.map(jax.lax.with_sharding_constraint, p, replicated)
        (_, metrics), grads = jax.value_and_grad(
            lambda q: loss_fn(model, q, batch, tcfg), has_aux=True)(p)
        if param_shardings is not None:
            # pin the DP reduction (reduce-scatter to the param shard) at
            # the compute dtype, before any cast widens the wire
            grads = jax.tree.map(jax.lax.with_sharding_constraint,
                                 grads, param_shardings)
        return grads, metrics

    def train_step(state: TrainState, batch: Dict[str, jax.Array],
                   lr_scale: jax.Array = jnp.float32(1.0)
                   ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        k = tcfg.microbatches
        if k > 1:
            def mb(carry, mbatch):
                g_acc, m_acc = carry
                g, m = grads_of(state.params, mbatch)
                g_acc = jax.tree.map(lambda a, b: a + b / k, g_acc, g)
                m_acc = jax.tree.map(lambda a, b: a + b / k, m_acc, m)
                return (g_acc, m_acc), None

            split = jax.tree.map(
                lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]), batch)
            zeros_g = jax.tree.map(jnp.zeros_like, state.params)
            zeros_m = {"loss": jnp.float32(0), "aux": jnp.float32(0)}
            (grads, metrics), _ = jax.lax.scan(mb, (zeros_g, zeros_m), split)
        else:
            grads, metrics = grads_of(state.params, batch)

        with region("update"):
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            if tcfg.optimizer.grad_clip > 0:
                grads, gnorm = clip_by_global_norm(grads,
                                                   tcfg.optimizer.grad_clip)
            else:
                from repro.optim.optimizers import global_norm
                gnorm = global_norm(grads)

            lr = base_lr * sched(state.step) * lr_scale
            updates, new_opt = opt.update(grads, state.opt, state.params, lr)
            new_params = jax.tree.map(lambda p, u: (p + u).astype(p.dtype),
                                      state.params, updates)
        new_state = TrainState(params=new_params, opt=new_opt,
                               step=state.step + 1)
        metrics = dict(metrics, grad_norm=gnorm, lr=lr)
        return new_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# serve_step (decode)
# ---------------------------------------------------------------------------

def make_serve_step(model: Model, *, sample: str = "greedy"
                    ) -> Callable[..., Tuple[jax.Array, PyTree]]:
    """One-token decode step: (params, cache, tokens (B,1)) -> (next, cache)."""

    def serve_step(params: PyTree, cache: PyTree, tokens: jax.Array
                   ) -> Tuple[jax.Array, PyTree]:
        logits, cache = model.decode(params, cache, {"tokens": tokens})
        if sample == "greedy":
            nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        else:
            raise ValueError(sample)
        return nxt[:, None], cache

    return serve_step


def make_prefill_step(model: Model, batch_axes: PyTree
                      ) -> Callable[..., PyTree]:
    """Blocked prefill: ingest up to T prompt tokens per row in ONE
    compiled dispatch instead of one engine step per token.

    ``(params, cache, tokens (B, T), n_valid (B,)) -> cache``. The block
    is a ``lax.scan`` over the same decode cell ``make_serve_step`` runs,
    so the resulting cache is token-for-token identical to the
    single-token fallback for every family — including SSM/RWKV/hybrid
    recurrent state, which a separate attention-only prefill kernel would
    get wrong. Rows advance only while their scan index is below
    ``n_valid``: a per-leaf select on the batch axis (``batch_axes``,
    from :func:`repro.models.builder.cache_batch_axes`) freezes decode
    rows and already-finished prefill rows, so mixed-phase batches share
    the dispatch safely. Prefill logits are discarded — the engine's
    decode phase re-feeds the final prompt token, exactly like the
    fallback path, so both paths stay parity-testable.
    """

    def select_rows(ax: int, mask: jax.Array, new: jax.Array,
                    old: jax.Array) -> jax.Array:
        m = mask.reshape((1,) * ax + (-1,) + (1,) * (new.ndim - ax - 1))
        return jnp.where(m, new, old)

    def prefill_step(params: PyTree, cache: PyTree, tokens: jax.Array,
                     n_valid: jax.Array) -> PyTree:
        T = tokens.shape[1]

        def body(cache, xs):
            tok, t = xs                       # tok: (B,), t: scalar index
            adv = t < n_valid                 # rows consuming this token
            _, new_cache = model.decode(params, cache,
                                        {"tokens": tok[:, None]})
            cache = jax.tree.map(
                lambda ax, new, old: select_rows(ax, adv, new, old),
                batch_axes, new_cache, cache)
            return cache, None

        cache, _ = jax.lax.scan(body, cache,
                                (tokens.T, jnp.arange(T, dtype=jnp.int32)))
        return cache

    return prefill_step


def make_paged_serve_step(model: Model, *, sample: str = "greedy"
                          ) -> Callable[..., Tuple[jax.Array, PyTree]]:
    """One-token decode against the paged cache:
    ``(params, cache, tokens (B,1), active (B,)) -> (next, cache)``.

    Unlike the dense step, the active-row mask is part of the compiled
    cell: inactive rows' page-table entries may point at pages owned by
    another request, so their KV writes must be dropped inside the
    kernel, not merely ignored by the engine afterwards.
    """

    def serve_step(params: PyTree, cache: PyTree, tokens: jax.Array,
                   active: jax.Array) -> Tuple[jax.Array, PyTree]:
        logits, cache = model.decode_paged(params, cache,
                                           {"tokens": tokens},
                                           advance=active)
        if sample == "greedy":
            nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        else:
            raise ValueError(sample)
        return nxt[:, None], cache

    return serve_step


def make_paged_prefill_step(model: Model, row_axes: PyTree
                            ) -> Callable[..., PyTree]:
    """Blocked prefill over the paged decode cell. Same contract as
    :func:`make_prefill_step` — ``(params, cache, tokens (B, T),
    n_valid (B,)) -> cache`` — but row freezing is split by leaf kind:
    pool leaves (marked ``-1`` in ``row_axes``, from
    :func:`repro.models.builder.paged_cache_axes`) are protected by the
    decode cell's own write-drop on the advance mask, while per-row
    leaves (page table, pos, recurrent state) get the same batch-axis
    select as the dense path.
    """

    def select_rows(ax: int, mask: jax.Array, new: jax.Array,
                    old: jax.Array) -> jax.Array:
        m = mask.reshape((1,) * ax + (-1,) + (1,) * (new.ndim - ax - 1))
        return jnp.where(m, new, old)

    def prefill_step(params: PyTree, cache: PyTree, tokens: jax.Array,
                     n_valid: jax.Array) -> PyTree:
        T = tokens.shape[1]

        def body(cache, xs):
            tok, t = xs
            adv = t < n_valid
            _, new_cache = model.decode_paged(params, cache,
                                              {"tokens": tok[:, None]},
                                              advance=adv)
            cache = jax.tree.map(
                lambda ax, new, old:
                new if ax == -1 else select_rows(ax, adv, new, old),
                row_axes, new_cache, cache)
            return cache, None

        cache, _ = jax.lax.scan(body, cache,
                                (tokens.T, jnp.arange(T, dtype=jnp.int32)))
        return cache

    return prefill_step

"""Feed-forward layers: dense (SwiGLU / GeLU-4x) and mixture-of-experts.

Two kinds of expert layer, chosen by ``cfg.router_scoring``:

- ``"sigmoid"``: the dropless held-expert layer (``apply_held_moe``,
  deepseek-v3's routing). It is told which experts it holds
  (``experts_held`` from ``first_held_expert``), routes every token over
  all ``num_experts``, and computes its held experts' part of each
  token's sum with grouped matmuls over the assignments sorted by
  expert: no capacity, so no token is dropped. On a chip of an
  expert-parallel deployment that is the chip's share of the layer.
- ``"softmax"``: *row-local capacity dispatch* (``moe_impl`` gspmd, ep,
  a2a): top-k routing, tokens packed into per-expert capacity buffers
  independently within each batch row. Keeping the scatter row-local
  means the dispatch never moves tokens across the ``data`` mesh axis —
  only the expert-sharded einsum communicates over ``model``. FLOPs are
  proportional to *active* (top-k) compute, not ``num_experts``.
  Over-capacity tokens are dropped (Switch-style, capacity_factor 1.25);
  the residual connection passes them through unchanged.

Every expert layer returns ``(out, aux)``; ``aux`` holds the router's
balance loss (``"balance"``), and the held-expert layer adds
``"moe_held_share"`` (the share of token-expert assignments that landed
on held experts) and ``"moe_load_max"`` (the most rows one held expert
got, over the held experts' mean).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import layers as L
from repro.obs.profiling import region

CAPACITY_FACTOR = 1.25


# ---------------------------------------------------------------------------
# Dense MLP
# ---------------------------------------------------------------------------

def init_mlp(kg: L.KeyGen, d_model: int, d_ff: int, gated: bool
             ) -> Dict[str, L.Boxed]:
    p = {
        "wi": L.param(kg, (d_model, d_ff), ("embed", "ff")),
        "wo": L.param(kg, (d_ff, d_model), ("ff", "embed")),
    }
    if gated:
        p["wg"] = L.param(kg, (d_model, d_ff), ("embed", "ff"))
    return p


def apply_mlp(p: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    dt = x.dtype
    h = x @ p["wi"].astype(dt)
    if "wg" in p:
        h = jax.nn.silu(x @ p["wg"].astype(dt)) * h
    else:
        h = jax.nn.gelu(h)
    return h @ p["wo"].astype(dt)


# ---------------------------------------------------------------------------
# Mixture of experts
# ---------------------------------------------------------------------------

def moe_capacity(seq_len: int, cfg: ModelConfig) -> int:
    c = math.ceil(seq_len * cfg.top_k / cfg.num_experts * CAPACITY_FACTOR)
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


def init_moe(kg: L.KeyGen, cfg: ModelConfig) -> Dict[str, L.Boxed]:
    d, f, E = cfg.d_model, cfg.expert_ff, cfg.num_experts
    Eh = cfg.held_experts
    sigmoid = cfg.router_scoring == "sigmoid"
    p = {
        # the sigmoid router runs in float32: not cast to cfg.dtype
        "router": L.param(kg, (d, E), ("embed", "experts"), scale=0.02,
                          cast=not sigmoid),
        "wi": L.param(kg, (Eh, d, f), ("experts", "embed", "ff")),
        "wg": L.param(kg, (Eh, d, f), ("experts", "embed", "ff")),
        "wo": L.param(kg, (Eh, f, d), ("experts", "ff", "embed")),
    }
    if sigmoid:
        # chooses experts, does not weight them; set by a load-based
        # update in the source's optimizer, not by the gradient
        p["router_bias"] = L.param(kg, (E,), ("experts",), init="zeros")
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(kg, d, cfg.num_shared_experts * f, gated=True)
    if cfg.dense_ff:
        # arctic-style dense residual branch, parallel to the routed experts
        p["dense"] = init_mlp(kg, d, cfg.dense_ff, gated=True)
    return p


def _route_row(x: jax.Array, probs: jax.Array, cfg: ModelConfig, capacity: int
               ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Row-local dispatch. x: (S, D); probs: (S, E).

    Returns (buffer (E*C, D), slot (S*k,), keep (S*k,), weight (S*k,)).
    """
    S, D = x.shape
    E, k, C = cfg.num_experts, cfg.top_k, capacity
    topw, topi = jax.lax.top_k(probs, k)                     # (S, k)
    topw = topw / jnp.clip(topw.sum(-1, keepdims=True), 1e-9)
    flat_e = topi.reshape(S * k)
    flat_w = topw.reshape(S * k)
    tok = jnp.repeat(jnp.arange(S), k)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)      # (S*k, E)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0), flat_e[:, None],
                              axis=1)[:, 0] - 1              # position in expert
    keep = pos < C
    slot = jnp.where(keep, flat_e * C + pos, 0)
    contrib = jnp.where(keep[:, None], x[tok], 0.0)
    buf = jnp.zeros((E * C, D), x.dtype).at[slot].add(contrib, mode="drop")
    return buf, slot, keep, flat_w


# the held-expert layer's counters, beside the balance loss every expert
# layer returns
HELD_AUX = ("moe_held_share", "moe_load_max")


def aux_zeros(cfg: ModelConfig) -> Dict[str, jax.Array]:
    """The aux dict ``apply_moe`` returns for ``cfg``, at zero: where the
    expert layers' aux is folded in with ``merge_aux``."""
    names = ("balance",) + (HELD_AUX if cfg.router_scoring == "sigmoid"
                            else ())
    return {k: jnp.zeros((), jnp.float32) for k in names}


def merge_aux(acc: Dict[str, jax.Array], aux: Dict[str, jax.Array]
              ) -> Dict[str, jax.Array]:
    """Fold one expert layer's aux, or one microbatch's step metrics, into
    the running dict: ``moe_load_max`` is a max, every other entry a sum."""
    return {k: jnp.maximum(v, aux[k]) if k == "moe_load_max" else v + aux[k]
            for k, v in acc.items()}


def layers_aux(aux: Dict[str, jax.Array], layers: int
               ) -> Dict[str, jax.Array]:
    """The aux merged over ``layers`` expert layers: the balance loss is
    their sum, ``moe_held_share`` their mean, ``moe_load_max`` their max."""
    if "moe_held_share" in aux:
        aux = dict(aux, moe_held_share=aux["moe_held_share"] / layers)
    return aux


def mean_aux(aux: Dict[str, jax.Array], n: int) -> Dict[str, jax.Array]:
    """The step metrics merged over ``n`` microbatches: each sum becomes
    a mean; ``moe_load_max`` stays their max."""
    return {k: v if k == "moe_load_max" else v / n for k, v in aux.items()}


def apply_moe(p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, D) -> (out, aux). The held-expert layer for a sigmoid
    router, else row-local capacity dispatch."""
    if cfg.router_scoring == "sigmoid":
        return apply_held_moe(p, x, cfg)
    out, aux = _apply_capacity_moe(p, x, cfg)
    return out, {"balance": aux}


def _apply_capacity_moe(p: Dict[str, jax.Array], x: jax.Array,
                        cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    if cfg.moe_impl == "ep" and x.shape[1] > 1:
        out, aux = _apply_moe_ep(p, x, cfg)
        if out is not None:
            return out, aux
    if cfg.moe_impl == "a2a":          # S==1 decode included: for huge MoE,
        out, aux = _apply_moe_a2a(p, x, cfg)   # moving tokens beats moving
        if out is not None:                    # or replicating weights
            return out, aux
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.top_k
    C = moe_capacity(S, cfg)
    dt = x.dtype

    logits = (x @ p["router"].astype(dt)).astype(jnp.float32)   # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)

    buf, slot, keep, flat_w = jax.vmap(
        lambda xr, pr: _route_row(xr, pr, cfg, C))(x, probs)
    ebuf = buf.reshape(B, E, C, D)

    h = jnp.einsum("becd,edf->becf", ebuf, p["wi"].astype(dt))
    g = jnp.einsum("becd,edf->becf", ebuf, p["wg"].astype(dt))
    y = jnp.einsum("becf,efd->becd", jax.nn.silu(g) * h, p["wo"].astype(dt))
    y = y.reshape(B, E * C, D)

    # gather back to token order; weight and sum over the k assignments
    y_ent = jnp.take_along_axis(y, slot[..., None], axis=1)     # (B,S*k,D)
    y_ent = y_ent * (keep[..., None] * flat_w[..., None]).astype(dt)
    out = y_ent.reshape(B, S, k, D).sum(axis=2)

    if "shared" in p:
        out = out + apply_mlp(p["shared"], x)
    if "dense" in p:
        out = out + apply_mlp(p["dense"], x)

    # Switch-style load-balance aux: E * sum_e( frac_tokens_e * mean_prob_e )
    sel = jax.nn.one_hot(jnp.argmax(logits, -1), E, dtype=jnp.float32)
    aux = E * jnp.mean(jnp.mean(sel, axis=(0, 1)) * jnp.mean(probs, axis=(0, 1)))
    return out, aux


# ---------------------------------------------------------------------------
# Dropless held-expert MoE (sigmoid routing with a correction bias)
# ---------------------------------------------------------------------------

def route_sigmoid(p: Dict[str, jax.Array], x2: jax.Array, cfg: ModelConfig
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x2: (T, D) -> (expert ids (T, k), weights (T, k), scores (T, E)),
    in float32: sigmoid scores, plus the bias to choose the top k, the
    unbiased scores (normalised over the k, times ``routed_scaling``) to
    weight them."""
    logits = jnp.dot(x2.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + p["router_bias"].astype(jnp.float32),
                           cfg.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * cfg.routed_scaling, scores


def seq_balance_loss(idx: jax.Array, scores: jax.Array, E: int
                     ) -> jax.Array:
    """DeepSeek-V3's sequence-wise balance loss, idx (B, S, k), scores
    (B, S, E): the mean over sequences of sum_e f_e P_e, with f_e the
    expert's share of the sequence's assignments times E, and P_e its
    mean score normalised over all experts."""
    S, k = idx.shape[1], idx.shape[2]
    count = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(axis=(1, 2))
    f = count * (E / (S * k))
    P = (scores / scores.sum(-1, keepdims=True)).mean(axis=1)
    return jnp.mean(jnp.sum(f * P, axis=-1))


def held_experts(p: Dict[str, jax.Array], x2: jax.Array, idx: jax.Array,
                 w: jax.Array, cfg: ModelConfig
                 ) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of each token's weighted sum, x2 (T, D),
    idx/w (T, k) -> ((T, D), rows per held expert). Nothing is dropped:
    every assignment to a held expert is computed."""
    T, D = x2.shape
    k = idx.shape[1]
    held = p["wi"].shape[0]
    dt = x2.dtype
    n = T * k
    rows = T * min(k, held)           # at most min(k, held) held per token
    with region("router"):
        # counting sort of the assignments: held experts first, by expert
        e = idx.reshape(n) - cfg.first_held_expert
        key = jnp.where((e >= 0) & (e < held), e, held)
        onehot = jax.nn.one_hot(key, held + 1, dtype=jnp.int32)
        rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0), key[:, None],
                                   axis=1)[:, 0] - 1
        counts = onehot.sum(axis=0)
        dest = (jnp.cumsum(counts) - counts)[key] + rank   # slot -> row
        src = jnp.zeros((n,), jnp.int32).at[dest].set(
            jnp.arange(n, dtype=jnp.int32))[:rows]         # row -> slot
        sizes = counts[:held]
        live = (jnp.arange(rows) < sizes.sum())[:, None]
    with region("experts"):
        xs = jnp.where(live, x2[src // k], 0)
        h = jax.lax.ragged_dot(xs, p["wi"].astype(dt), sizes)
        g = jax.lax.ragged_dot(xs, p["wg"].astype(dt), sizes)
        y = jax.lax.ragged_dot(jax.nn.silu(g) * h, p["wo"].astype(dt), sizes)
        y = jnp.where(live, y, 0)
        if rows < n:
            y = jnp.concatenate([y, jnp.zeros((n - rows, D), dt)])
        y = y[dest].reshape(T, k, D)
        out = jnp.einsum("tkd,tk->td", y, w.astype(dt),
                         preferred_element_type=jnp.float32)
    return out.astype(dt), sizes


def apply_held_moe(p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, D) -> (held experts' part + shared experts, aux)."""
    B, S, D = x.shape
    x2 = x.reshape(B * S, D)
    with region("router"):
        idx, w, scores = route_sigmoid(p, x2, cfg)
    out, sizes = held_experts(p, x2, idx, w, cfg)
    out = out.reshape(B, S, D)
    if "shared" in p:
        out = out + apply_mlp(p["shared"], x)
    rows = sizes.sum().astype(jnp.float32)
    aux = {
        "balance": seq_balance_loss(idx.reshape(B, S, -1),
                                    scores.reshape(B, S, -1),
                                    cfg.num_experts),
        "moe_held_share": rows / idx.size,
        "moe_load_max": jnp.max(sizes) * sizes.shape[0] / jnp.maximum(rows,
                                                                      1.0),
    }
    return out, aux


# ---------------------------------------------------------------------------
# Expert-parallel MoE (shard_map): the §Perf fix for GSPMD's combine choice
# ---------------------------------------------------------------------------
# GSPMD's auto-sharding of the capacity-dispatch einsums all-reduces the
# (B, E, C, D) DISPATCH BUFFERS over the model axis — ~E*C/S times more
# bytes than the mathematically sufficient combine on (B, S, D). The
# explicit expert-parallel form pins the schedule:
#
#   - routing + dispatch are computed redundantly on every model-rank
#     (token activations are replicated over 'model' — dispatch is FREE,
#     zero collectives),
#   - each model-rank runs ONLY its E/M experts' FFN (same active-FLOPs
#     total, now partitioned),
#   - each rank combines its experts' outputs into a partial (B, S, D)
#     and ONE psum over 'model' finishes the layer — the same wire cost
#     as a Megatron MLP block, ~E*C/S (x10-60) less than GSPMD's choice.
#
# Expert weights stay FSDP-sharded on their embed/ff dims; shard_map's
# in_specs materialize exactly the per-rank expert slices (the standard
# FSDP gather), never the full expert stack.

def _ep_local(x_loc, router, wi, wg, wo, *, cfg: ModelConfig, capacity: int,
              e_loc: int):
    """Per-(data x model)-shard MoE body. x_loc: (B_loc, S, D); wi/wg/wo:
    this rank's (e_loc, ...) expert slices."""
    B, S, D = x_loc.shape
    E, k, C = cfg.num_experts, cfg.top_k, capacity
    dt = x_loc.dtype

    logits = (x_loc @ router.astype(dt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    buf, slot, keep, flat_w = jax.vmap(
        lambda xr, pr: _route_row(xr, pr, cfg, C))(x_loc, probs)
    # slice out this rank's experts from the (E*C, D) buffer
    e0 = jax.lax.axis_index("model") * e_loc
    ebuf = jax.lax.dynamic_slice_in_dim(buf, e0 * C, e_loc * C, axis=1)
    ebuf = ebuf.reshape(B, e_loc, C, D)

    h = jnp.einsum("becd,edf->becf", ebuf, wi.astype(dt))
    g = jnp.einsum("becd,edf->becf", ebuf, wg.astype(dt))
    y = jnp.einsum("becf,efd->becd", jax.nn.silu(g) * h, wo.astype(dt))
    y = y.reshape(B, e_loc * C, D)

    # combine: local slots that belong to this rank's experts
    local_slot = slot - e0 * C
    local_keep = keep & (local_slot >= 0) & (local_slot < e_loc * C)
    y_ent = jnp.take_along_axis(
        y, jnp.clip(local_slot, 0, e_loc * C - 1)[..., None], axis=1)
    y_ent = y_ent * (local_keep[..., None] * flat_w[..., None]).astype(dt)
    out = y_ent.reshape(B, S, k, D).sum(axis=2)
    out = jax.lax.psum(out, "model")             # ONE (B,S,D) combine

    sel = jax.nn.one_hot(jnp.argmax(logits, -1), E, dtype=jnp.float32)
    aux = E * jnp.mean(jnp.mean(sel, axis=(0, 1))
                       * jnp.mean(probs, axis=(0, 1)))
    return out, aux


def _apply_moe_ep(p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig
                  ) -> Tuple[Optional[jax.Array], jax.Array]:
    """shard_map expert-parallel MoE. Returns (None, 0) when inapplicable
    (no mesh / fsdp layout / E not divisible) so the caller falls back."""
    import functools

    from jax.sharding import PartitionSpec as P

    from repro.sharding import current_layout, current_mesh, data_axes

    mesh = current_mesh()
    if (mesh is None or current_layout() != "tp"
            or "model" not in mesh.axis_names):
        return None, jnp.zeros((), jnp.float32)
    M = mesh.shape["model"]
    if cfg.num_experts % M:
        return None, jnp.zeros((), jnp.float32)
    e_loc = cfg.num_experts // M
    B, S, D = x.shape
    C = moe_capacity(S, cfg)
    dax = data_axes(mesh)
    dspec = dax if len(dax) > 1 else dax[0]
    # batch spec: shard over data axes when divisible, else replicate
    dsz = 1
    for a in dax:
        dsz *= mesh.shape[a]
    xspec = P(dspec, None, None) if B % dsz == 0 else P(None, None, None)

    body = functools.partial(_ep_local, cfg=cfg, capacity=C, e_loc=e_loc)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec,
                  P(None, None),                 # router: replicated
                  P("model", None, None),        # wi: expert-sharded
                  P("model", None, None),        # wg
                  P("model", None, None)),       # wo
        out_specs=(xspec, P()),
        check_vma=False)
    out, aux = fn(x, p["router"], p["wi"], p["wg"], p["wo"])

    if "shared" in p:
        out = out + apply_mlp(p["shared"], x)
    if "dense" in p:
        out = out + apply_mlp(p["dense"], x)
    return out, aux


# ---------------------------------------------------------------------------
# All-to-all expert parallelism (shard_map) — tokens unique per rank
# ---------------------------------------------------------------------------
# Under the fsdp/zero1 layouts the batch is flattened over EVERY mesh axis,
# so each model-rank holds DIFFERENT tokens and the replicated-dispatch EP
# above would be wrong (and its per-layer (B,S,D) combine psum is the cost
# that remains in cell A's iteration 2). The all-to-all form moves only the
# ROUTED activations: each rank packs per-destination expert buffers,
# all_to_all over 'model' ships them to the experts' owners, the expert FFN
# runs on its own tokens, and a second all_to_all ships results back —
# wire per layer ~ tokens_loc * top_k * D * capacity_factor, independent of
# E*C buffer sizes and with NO (B,S,D) all-reduce at all.

def _a2a_local(x_loc, router, wi, wg, wo, *, cfg: ModelConfig, cap: int,
               e_loc: int, M: int, ep_axes=("model",)):
    """x_loc: (B_loc, S, D) tokens unique to this rank. wi/wg/wo: this
    rank's (e_loc, ...) expert slices. cap: per-(source-rank, expert)
    capacity. ep_axes: the mesh axes experts are sharded over — ("model",)
    for partial EP, the full axis tuple for one-expert-per-chip serving
    (arctic decode: 128 experts over a 128-chip (16,8) mesh)."""
    B, S, D = x_loc.shape
    E, k = cfg.num_experts, cfg.top_k
    dt = x_loc.dtype
    T = B * S
    xf = x_loc.reshape(T, D)

    logits = (xf @ router.astype(dt)).astype(jnp.float32)       # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)
    topw = topw / jnp.clip(topw.sum(-1, keepdims=True), 1e-9)
    flat_e = topi.reshape(T * k)
    flat_w = topw.reshape(T * k)
    tok = jnp.repeat(jnp.arange(T), k)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0), flat_e[:, None],
                              axis=1)[:, 0] - 1
    keep = pos < cap
    # global slot layout: expert e = m*e_loc + j  ->  m*(e_loc*cap) + j*cap
    slot = jnp.where(keep, flat_e // e_loc * (e_loc * cap)
                     + (flat_e % e_loc) * cap + pos, 0)
    contrib = jnp.where(keep[:, None], xf[tok], 0.0)
    buf = jnp.zeros((M * e_loc * cap, D), dt).at[slot].add(contrib,
                                                           mode="drop")

    # ship token slabs to their experts' owners and back
    axes_arg = ep_axes if len(ep_axes) > 1 else ep_axes[0]
    recv = jax.lax.all_to_all(buf.reshape(M, e_loc * cap, D), axes_arg,
                              split_axis=0, concat_axis=0, tiled=False)
    ebuf = recv.reshape(M, e_loc, cap, D).transpose(1, 0, 2, 3) \
        .reshape(e_loc, M * cap, D)
    h = jnp.einsum("ecd,edf->ecf", ebuf, wi.astype(dt))
    g = jnp.einsum("ecd,edf->ecf", ebuf, wg.astype(dt))
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, wo.astype(dt))
    y = y.reshape(e_loc, M, cap, D).transpose(1, 0, 2, 3) \
        .reshape(M, e_loc * cap, D)
    back = jax.lax.all_to_all(y, axes_arg, split_axis=0, concat_axis=0,
                              tiled=False).reshape(M * e_loc * cap, D)

    y_ent = back[slot] * (keep[:, None] * flat_w[:, None]).astype(dt)
    out = y_ent.reshape(T, k, D).sum(axis=1).reshape(B, S, D)

    sel = jax.nn.one_hot(jnp.argmax(logits, -1), E, dtype=jnp.float32)
    aux = E * jnp.mean(jnp.mean(sel, axis=0) * jnp.mean(probs, axis=0))
    return out, aux


def _apply_moe_a2a(p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig
                   ) -> Tuple[Optional[jax.Array], jax.Array]:
    """Token-unique a2a EP; requires the fsdp/zero1 layout (batch over all
    axes) and E % model == 0. Returns (None, 0) when inapplicable."""
    import functools
    import math as _math

    from jax.sharding import PartitionSpec as P

    from repro.sharding import current_layout, current_mesh

    mesh = current_mesh()
    if (mesh is None
            or current_layout() not in ("fsdp", "zero1", "moe_serve")
            or "model" not in mesh.axis_names):
        return None, jnp.zeros((), jnp.float32)
    B, S, D = x.shape
    total = mesh.size
    if B * S % total:
        return None, jnp.zeros((), jnp.float32)
    all_axes = tuple(mesh.axis_names)
    # EP group: one expert per chip when E divides the WHOLE mesh (the
    # 480B-MoE serving layout); otherwise EP over 'model' only.
    if cfg.num_experts % total == 0:
        ep_axes = all_axes
        M = total
    elif cfg.num_experts % mesh.shape["model"] == 0:
        ep_axes = ("model",)
        M = mesh.shape["model"]
    else:
        return None, jnp.zeros((), jnp.float32)
    if B % total:
        return None, jnp.zeros((), jnp.float32)
    e_loc = cfg.num_experts // M
    T_loc = (B // total) * S
    cap = _math.ceil(T_loc * cfg.top_k / cfg.num_experts * CAPACITY_FACTOR)
    cap = max(8, -(-cap // 8) * 8)

    bspec = all_axes if len(all_axes) > 1 else all_axes[0]
    espec = ep_axes if len(ep_axes) > 1 else ep_axes[0]
    body = functools.partial(_a2a_local, cfg=cfg, cap=cap, e_loc=e_loc, M=M,
                             ep_axes=ep_axes)
    n_ranks = mesh.size

    def wrapped(x_, router, wi, wg, wo):
        out, aux = body(x_, router, wi, wg, wo)
        aux = jax.lax.psum(aux, all_axes) / n_ranks
        return out, aux

    fn = jax.shard_map(
        wrapped, mesh=mesh,
        in_specs=(P(bspec, None, None),
                  P(None, None),
                  P(espec, None, None),
                  P(espec, None, None),
                  P(espec, None, None)),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False)
    out, aux = fn(x, p["router"], p["wi"], p["wg"], p["wo"])

    if "shared" in p:
        out = out + apply_mlp(p["shared"], x)
    if "dense" in p:
        out = out + apply_mlp(p["dense"], x)
    return out, aux

"""Logical-axis -> mesh-axis mapping (GSPMD annotations).

Params carry logical axis names (see ``repro.models.layers.Boxed``); this
module turns them into ``PartitionSpec``s for a concrete mesh. The baseline
layout is:

- **TP over ``model``**: heads / kv_heads / ff / experts / vocab / ssm dims.
- **FSDP over ``data``**: the ``embed`` dim of every >=2D weight, so even
  478B-param Arctic fits (params fully sharded over the whole mesh).
- **DP over ``pod``+``data``**: activation batch dim; the ``pod`` axis is the
  transient/revocation domain (DESIGN.md §2).

Non-divisible cases (e.g. 40 heads over 16-way model) are allowed — GSPMD
pads — except size-1 dims (MQA kv_heads=1), which we replicate instead.
A context mesh (``use_mesh``) makes ``shard_act`` constraints apply inside
model code; with no mesh active they are no-ops, so smoke tests on one CPU
device run the identical model code.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import MeshConfig, ModelConfig

_ctx = threading.local()

# Layouts (the §Perf hillclimb lever — same physical mesh, different logical
# assignment of parallelism):
#   "tp"    Megatron-style: TP over 'model' (heads/ff/experts/vocab) +
#           FSDP over the data axes. The paper-faithful baseline — it maps
#           "multiple parameter servers" onto tensor-sharded state.
#   "fsdp"  pure data parallelism: params fully sharded over ALL mesh axes,
#           batch flattened over all axes, zero TP. No per-layer activation
#           all-reduces — wire cost is the per-layer param gathers plus one
#           grad reduce-scatter per step.
#   "zero1" same parameter/optimizer sharding as "fsdp", but the train step
#           gathers the bf16 compute copy ONCE per step (replicated through
#           fwd+bwd) instead of per-layer: minimum possible DP wire
#           (1 param all-gather + 1 grad reduce-scatter), at the cost of
#           holding the full bf16 replica in HBM. Wins when params(bf16)
#           fit comfortably (see EXPERIMENTS.md §Perf).
#   "moe_serve"  giant-MoE serving: experts EP-resident (one expert-group
#           per chip when E divides the mesh), non-expert weights
#           TP-resident (no FSDP gathers), tokens flattened over all axes
#           so the a2a dispatch sees unique tokens per rank.
LAYOUTS = ("tp", "fsdp", "zero1", "moe_serve")


def current_mesh() -> Optional[Mesh]:
    return getattr(_ctx, "mesh", None)


def current_layout() -> str:
    return getattr(_ctx, "layout", "tp")


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], layout: str = "tp"):
    assert layout in LAYOUTS, layout
    prev = current_mesh()
    prev_layout = current_layout()
    _ctx.mesh = mesh
    _ctx.layout = layout
    try:
        yield
    finally:
        _ctx.mesh = prev
        _ctx.layout = prev_layout


def data_axes(mesh: Mesh, layout: str = "tp") -> Tuple[str, ...]:
    if layout in ("fsdp", "zero1", "moe_serve"):
        return tuple(mesh.axis_names)          # batch over everything
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def data_size(mesh: Mesh, layout: str = "tp") -> int:
    n = 1
    for a in data_axes(mesh, layout):
        n *= mesh.shape[a]
    return n


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def _model_ok(dim: int, mesh: Mesh) -> bool:
    # jit argument shardings require exact divisibility (GSPMD cannot pad
    # an *input* buffer). Non-divisible model dims (e.g. 40 heads on a
    # 16-way model axis) fall back to replication + FSDP on the embed dim;
    # the useful-FLOPs ratio in the roofline flags the lost TP, and the
    # §Perf hillclimb can re-shape the mesh (e.g. 32x8) to recover it.
    return dim > 1 and dim % mesh.shape["model"] == 0


def param_spec(axes: Sequence[Optional[str]], cfg: ModelConfig, mesh: Mesh,
               shape: Sequence[int], fsdp: bool = True,
               layout: str = "tp") -> P:
    """Map one parameter's logical axes to a PartitionSpec."""
    ndims = len(axes)
    entries: list = [None] * ndims

    if layout == "moe_serve" and "experts" not in axes:
        # non-expert weights: TP-resident (no FSDP) — serving streams them
        # from local HBM every token; gathers would dominate decode
        return param_spec(axes, cfg, mesh, shape, fsdp=False, layout="tp")

    if layout in ("fsdp", "zero1", "moe_serve"):
        all_axes = tuple(mesh.axis_names)
        total = mesh.size
        cands = sorted(range(ndims), key=lambda i: -shape[i])
        # Expert weights: KEEP expert parallelism over 'model' (the a2a
        # MoE path owns that axis) and FSDP the largest other dim over the
        # remaining axes — gathering all experts to every device would
        # undo EP (see EXPERIMENTS.md §Perf cell A iteration 4).
        if "experts" in axes and "model" in mesh.axis_names:
            ei = axes.index("experts")
            if shape[ei] > 1 and shape[ei] % mesh.size == 0:
                # one expert (group) per chip: full-mesh EP, weights
                # resident — the 480B-MoE serving layout
                entries[ei] = all_axes if len(all_axes) > 1 else all_axes[0]
                return P(*entries)
            if shape[ei] % mesh.shape["model"] == 0 and shape[ei] > 1:
                entries[ei] = "model"
                rest = tuple(a for a in mesh.axis_names if a != "model")
                rsz = 1
                for a in rest:
                    rsz *= mesh.shape[a]
                for i in cands:
                    if i == ei or axes[i] in ("layers", "blocks"):
                        continue
                    if shape[i] > 1 and shape[i] % rsz == 0:
                        entries[i] = rest if len(rest) > 1 else rest[0]
                        break
                return P(*entries)
        # Fully shard the largest non-layer-stacked dim over ALL mesh axes
        # (ZeRO-3-style); fall back to the data axes, else replicate.
        for i in cands:
            if axes[i] in ("layers", "blocks") or shape[i] <= 1:
                continue
            if shape[i] % total == 0:
                entries[i] = all_axes if len(all_axes) > 1 else all_axes[0]
                return P(*entries)
        if fsdp and ndims >= 2:
            dax = data_axes(mesh)
            dsz = data_size(mesh)
            for i in cands:
                if axes[i] in ("layers", "blocks"):
                    continue
                if shape[i] > 1 and shape[i] % dsz == 0:
                    entries[i] = dax if len(dax) > 1 else dax[0]
                    break
        return P(*entries)

    model_axes = {"heads", "kv_heads", "ff", "experts", "vocab",
                  "ssm_inner", "ssm_heads", "heads_flat", "embed_out"}
    used_model = False
    for i, ax in enumerate(axes):
        dim = shape[i]
        if ax in model_axes and not used_model and _model_ok(dim, mesh):
            entries[i] = "model"
            used_model = True
    # FSDP: shard the (first) embed axis over data — only for >=2D weights
    if fsdp and ndims >= 2:
        dax = data_axes(mesh)
        dsz = data_size(mesh)
        for i, ax in enumerate(axes):
            if ax == "embed" and entries[i] is None and shape[i] % dsz == 0:
                entries[i] = dax if len(dax) > 1 else dax[0]
                break
    return P(*entries)


def param_shardings(params, cfg: ModelConfig, mesh: Mesh, fsdp: bool = True,
                    layout: str = "tp"):
    """Boxed param tree -> matching tree of NamedShardings."""
    from repro.models import layers as L  # deferred: avoids import cycle

    def one(b: L.Boxed):
        spec = param_spec(b.axes, cfg, mesh, b.value.shape, fsdp=fsdp,
                          layout=layout)
        return NamedSharding(mesh, spec)
    return jax.tree.map(one, params, is_leaf=L.is_boxed)


@dataclasses.dataclass(frozen=True)
class LayerGather:
    """How a layer scan hands one leaf of a stacked trunk to a layer under
    ``fsdp``: cast to ``dtype``, and gathered to ``whole`` (replicated)
    unless ``whole`` is None."""
    dtype: Any
    whole: Optional[NamedSharding]


def layer_gathers(layer, stack, cfg: ModelConfig):
    """The gather plan of a scan over stacked layer weights, or None where
    the scan should hand each layer its weights as stored.

    ``layer`` is one layer's Boxed tree (abstract values, logical axes
    without ``layers``) and ``stack`` the stacked weights it describes.
    A plan exists only under ``use_mesh`` with layout ``fsdp``, on a mesh
    of more than one device, where :func:`param_spec` shards some stacked
    leaf: then each leaf maps to a :class:`LayerGather`. A sharded leaf is
    gathered, except an expert leaf: gathering every expert to every
    device would undo expert parallelism. Leaves the model casts
    (``Boxed.cast``) move in ``cfg.dtype``, the dtype they are used in.
    """
    from repro.models import layers as L  # deferred: avoids import cycle

    mesh, layout = current_mesh(), current_layout()
    if mesh is None or mesh.size == 1 or layout != "fsdp":
        return None

    def one(b: L.Boxed, a: jax.Array) -> LayerGather:
        spec = param_spec(("layers",) + b.axes, cfg, mesh, a.shape,
                          layout=layout)
        gather = "experts" not in b.axes and any(e is not None for e in spec)
        return LayerGather(jnp.dtype(cfg.dtype) if b.cast else a.dtype,
                           NamedSharding(mesh, P()) if gather else None)

    plan = jax.tree.map(one, layer, stack, is_leaf=L.is_boxed)
    if all(g.whole is None for g in jax.tree.leaves(plan)):
        return None
    return plan


def gather_layer(plan, lp):
    """One layer's weights (a slice of the stack) as the layer uses them:
    each leaf cast to its plan's dtype, the gathered leaves replicated."""
    def one(g: LayerGather, a: jax.Array) -> jax.Array:
        a = a.astype(g.dtype)
        if g.whole is None:
            return a
        return jax.lax.with_sharding_constraint(a, g.whole)
    return jax.tree.map(one, plan, lp)


def opt_state_spec(axes: Sequence[Optional[str]], cfg: ModelConfig,
                   mesh: Mesh, shape: Sequence[int], zero1: bool = True) -> P:
    """Optimizer-state sharding — same as params (ZeRO-1 comes free with
    FSDP params; kept as a separate hook so non-FSDP layouts can still
    shard optimizer state)."""
    return param_spec(axes, cfg, mesh, shape, fsdp=zero1)


# ---------------------------------------------------------------------------
# Activation constraints
# ---------------------------------------------------------------------------

_ACT_MAP = {
    "batch": "DATA",       # resolved to ("pod","data") / ("data",)
    "heads": "model",
    "kv_heads": "model",
    "ff": "model",
    "experts": "model",
    "vocab": "model",
    "ssm_inner": "model",
    "kv_seq": "DATA",      # long-context decode: shard the cache over data
}


def act_spec(axes: Sequence[Optional[str]], mesh: Mesh,
             shape: Optional[Sequence[int]] = None,
             layout: str = "tp") -> P:
    """Activation PartitionSpec; skips axes whose size doesn't divide the
    mesh extent (GSPMD would pad — e.g. batch=1 long-context decode)."""
    entries = []
    for i, ax in enumerate(axes):
        tgt = _ACT_MAP.get(ax)
        if tgt == "DATA":
            dax = data_axes(mesh, layout)
            if shape is not None and shape[i] % data_size(mesh, layout) != 0:
                entries.append(None)
            else:
                entries.append(dax if len(dax) > 1 else dax[0])
        elif tgt is not None:
            if layout in ("fsdp", "zero1", "moe_serve"):
                entries.append(None)       # no TP: model-ish dims replicate
            elif shape is not None and shape[i] % mesh.shape["model"] != 0:
                entries.append(None)
            else:
                entries.append(tgt)
        else:
            entries.append(None)
    return P(*entries)


def shard_act(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    """Annotate an activation; no-op outside a mesh context."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = act_spec(axes, mesh, x.shape, current_layout())
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

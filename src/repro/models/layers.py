"""Shared building blocks: params-with-logical-axes, norms, rotary embeddings.

Models are pure functions over nested-dict param pytrees. Every leaf is
created through :func:`param` as a ``Boxed(value, axes)`` pair where ``axes``
is a tuple of *logical* axis names (``"embed"``, ``"heads"``, ``"ff"``, ...).
``repro.sharding`` maps logical names onto mesh axes, which is how the same
model definition serves the 1-device smoke tests and the 512-chip dry-run.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

PyTree = Any


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Boxed:
    """A parameter leaf carrying logical-axis metadata through the pytree.

    ``cast`` marks the leaves the model casts to ``cfg.dtype`` before
    every use: holding those in ``cfg.dtype`` (:func:`unbox_for_compute`)
    changes no result and halves their bytes.
    """
    value: jax.Array
    axes: Tuple[Optional[str], ...]
    cast: bool = False

    def tree_flatten(self):
        return (self.value,), (self.axes, self.cast)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


def is_boxed(x) -> bool:
    return isinstance(x, Boxed)


def unbox(tree: PyTree) -> PyTree:
    """Strip Boxed wrappers -> raw array pytree."""
    return jax.tree.map(lambda b: b.value, tree, is_leaf=is_boxed)


def axes_tree(tree: PyTree) -> PyTree:
    """Matching pytree of logical-axes tuples."""
    return jax.tree.map(lambda b: b.axes, tree, is_leaf=is_boxed)


def unbox_for_compute(tree: PyTree, dtype) -> PyTree:
    """Strip Boxed wrappers, holding every ``cast`` leaf in ``dtype``.
    The model computes the same values from the result as from
    :func:`unbox`: it casts those leaves to ``dtype`` before every use."""
    return jax.tree.map(
        lambda b: b.value.astype(dtype) if b.cast else b.value, tree,
        is_leaf=is_boxed)


class KeyGen:
    """Split-on-demand PRNG key source for init code."""

    def __init__(self, key: jax.Array):
        self._key = key

    def __call__(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub


def param(kg: KeyGen, shape: Sequence[int], axes: Sequence[Optional[str]],
          scale: Optional[float] = None, dtype=jnp.float32,
          init: str = "normal", cast: Optional[bool] = None) -> Boxed:
    """Create one parameter. ``scale=None`` -> fan-in 1/sqrt(fan_in).
    ``cast`` (see :class:`Boxed`) defaults to True for >=2-D leaves, the
    weights every layer casts to ``cfg.dtype`` before its matmuls; a
    >=2-D leaf used in float32 must pass ``cast=False``."""
    shape = tuple(shape)
    assert len(shape) == len(axes), (shape, axes)
    if init == "zeros":
        v = jnp.zeros(shape, dtype)
    elif init == "ones":
        v = jnp.ones(shape, dtype)
    else:
        if scale is None:
            fan_in = shape[0] if len(shape) > 1 else shape[-1]
            scale = 1.0 / math.sqrt(max(1, fan_in))
        v = (jax.random.normal(kg(), shape, dtype) * scale).astype(dtype)
    return Boxed(v, tuple(axes), len(shape) > 1 if cast is None else cast)


# ---------------------------------------------------------------------------
# Norms (operate on raw arrays; params passed in already unboxed)
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + gamma.astype(jnp.float32))).astype(dt)


def layer_norm(x: jax.Array, gamma: jax.Array, beta: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (out * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(dt)


def init_rms(kg: KeyGen, d: int) -> Dict[str, Boxed]:
    # stored as zero-centered (applied as 1+gamma)
    return {"gamma": param(kg, (d,), ("embed",), init="zeros")}


# ---------------------------------------------------------------------------
# Rotary position embeddings (standard + multimodal M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    freqs = rope_freqs(x.shape[-1], theta)                       # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs       # (..., S, D/2)
    cos = jnp.cos(ang)[..., None, :]                             # (..., S, 1, D/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions_thw: jax.Array, theta: float) -> jax.Array:
    """Qwen2-VL multimodal rotary. positions_thw: (..., S, 3) = (t, h, w) ids.

    The head_dim/2 frequency channels are split 2:1:1 across (t, h, w)
    sections (Qwen2-VL mrope_section pattern).
    """
    D = x.shape[-1]
    half = D // 2
    sec_t = half // 2
    sec_h = (half - sec_t) // 2
    sec_w = half - sec_t - sec_h
    freqs = rope_freqs(D, theta)
    pos_t = positions_thw[..., 0]
    pos_h = positions_thw[..., 1]
    pos_w = positions_thw[..., 2]
    ang_t = pos_t[..., None].astype(jnp.float32) * freqs[:sec_t]
    ang_h = pos_h[..., None].astype(jnp.float32) * freqs[sec_t:sec_t + sec_h]
    ang_w = pos_w[..., None].astype(jnp.float32) * freqs[sec_t + sec_h:]
    ang = jnp.concatenate([ang_t, ang_h, ang_w], axis=-1)        # (..., S, D/2)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embed(kg: KeyGen, vocab: int, d_model: int, tie: bool) -> Dict[str, Boxed]:
    p = {"tok": param(kg, (vocab, d_model), ("vocab", "embed"), scale=1.0)}
    if not tie:
        p["out"] = param(kg, (d_model, vocab), ("embed", "vocab"))
    return p


def embed(params: Dict[str, jax.Array], tokens: jax.Array, dtype) -> jax.Array:
    return params["tok"].astype(dtype)[tokens]


def unembed(params: Dict[str, jax.Array], x: jax.Array, tie: bool) -> jax.Array:
    if tie:
        w = params["tok"].astype(x.dtype).T
    else:
        w = params["out"].astype(x.dtype)
    return x @ w

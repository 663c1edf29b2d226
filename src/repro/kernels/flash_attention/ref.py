"""Pure-jnp oracle for the flash-attention kernel (no blocking, fp32)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window=0,
                  sm_scale: float | None = None) -> jax.Array:
    """q: (B, Sq, H, D); k/v: (B, Sk, KV, D) -> (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    group = H // KV
    if sm_scale is None:
        sm_scale = D ** -0.5
    kf = jnp.repeat(k, group, axis=2).astype(jnp.float32)
    vf = jnp.repeat(v, group, axis=2).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kf) * sm_scale
    q_pos = jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Sk)[None, :]
    mask = (window <= 0) | (k_pos > q_pos - window)
    if causal:
        mask &= k_pos <= q_pos
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)          # fully-masked rows -> 0
    return jnp.einsum("bhqk,bkhd->bqhd", p, vf).astype(q.dtype)

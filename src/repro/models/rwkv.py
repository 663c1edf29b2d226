"""RWKV-6 (Finch): attention-free time-mix with data-dependent decay.

Recurrence per head (state S in R^{Dk x Dv}, decay w_t per k-channel):

    o_t = r_t @ (S_{t-1} + diag(u) k_t^T v_t)
    S_t = diag(w_t) S_{t-1} + k_t^T v_t

The data-dependent decay ``w_t = exp(-exp(w0 + lora(x_t)))`` is the defining
RWKV-6 feature and is kept exactly. Token-shift lerps for r/k/v/g use static
mix vectors (the full ddlerp LoRA tower is orthogonal to the systems study;
noted in DESIGN.md). Channel-mix uses squared-ReLU.

The XLA path runs the recurrence as a chunked scan (sequential inside a
chunk, lax.scan across chunks) — the Pallas kernel in ``repro.kernels.rwkv6``
is the TPU fast path.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import layers as L

LORA_R = 64


def _dims(cfg: ModelConfig) -> Tuple[int, int]:
    Dh = cfg.rwkv_head_dim
    H = cfg.d_model // Dh
    return H, Dh


def init_rwkv_tmix(kg: L.KeyGen, cfg: ModelConfig) -> Dict[str, L.Boxed]:
    d = cfg.d_model
    H, Dh = _dims(cfg)
    return {
        "mix_r": L.param(kg, (d,), ("embed",), scale=0.5),
        "mix_k": L.param(kg, (d,), ("embed",), scale=0.5),
        "mix_v": L.param(kg, (d,), ("embed",), scale=0.5),
        "mix_g": L.param(kg, (d,), ("embed",), scale=0.5),
        "mix_w": L.param(kg, (d,), ("embed",), scale=0.5),
        "wr": L.param(kg, (d, d), ("embed", "heads_flat")),
        "wk": L.param(kg, (d, d), ("embed", "heads_flat")),
        "wv": L.param(kg, (d, d), ("embed", "heads_flat")),
        "wg": L.param(kg, (d, d), ("embed", "heads_flat")),
        "wo": L.param(kg, (d, d), ("heads_flat", "embed")),
        "w0": L.param(kg, (d,), ("embed",), init="zeros"),
        "w_lora_a": L.param(kg, (d, LORA_R), ("embed", None), scale=0.01),
        "w_lora_b": L.param(kg, (LORA_R, d), (None, "embed"), scale=0.01),
        "u": L.param(kg, (H, Dh), ("heads", "head_dim"), scale=0.5,
                     cast=False),                   # used in float32
        "ln_x": L.param(kg, (d,), ("embed",), init="zeros"),
    }


def init_rwkv_cmix(kg: L.KeyGen, cfg: ModelConfig) -> Dict[str, L.Boxed]:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "mix_k": L.param(kg, (d,), ("embed",), scale=0.5),
        "mix_r": L.param(kg, (d,), ("embed",), scale=0.5),
        "wk": L.param(kg, (d, f), ("embed", "ff")),
        "wv": L.param(kg, (f, d), ("ff", "embed")),
        "wr": L.param(kg, (d, d), ("embed", "embed_out")),
    }


def _shift(x: jax.Array, prev: jax.Array) -> jax.Array:
    """Token shift: x_{t-1} with ``prev`` (B,1,D) as the t=0 predecessor."""
    return jnp.concatenate([prev, x[:, :-1]], axis=1)


def _lerp(x, xs, mix):
    m = jax.nn.sigmoid(mix.astype(jnp.float32)).astype(x.dtype)
    return x + (xs - x) * m


def rwkv_decay(p, xw: jax.Array) -> jax.Array:
    """Data-dependent decay w_t in (0,1): exp(-exp(w0 + lora(x)))."""
    lo = jnp.tanh(xw @ p["w_lora_a"].astype(xw.dtype)) @ p["w_lora_b"].astype(xw.dtype)
    logw = p["w0"].astype(jnp.float32) + lo.astype(jnp.float32)
    return jnp.exp(-jnp.exp(jnp.clip(logw, -8.0, 4.0)))


def _wkv_scan(r, k, v, w, u, state):
    """Sequential WKV recurrence. r/k/v/w: (B, S, H, Dh) fp32; state (B,H,Dh,Dh).

    Returns (o (B,S,H,Dh), final_state).
    """
    def step(S, inp):
        rt, kt, vt, wt = inp                                # (B,H,Dh)
        kv = kt[..., :, None] * vt[..., None, :]            # (B,H,Dk,Dv)
        o = jnp.einsum("bhk,bhkv->bhv", rt, S + u[None, :, :, None] * kv)
        S = wt[..., :, None] * S + kv
        return S, o

    xs = tuple(a.transpose(1, 0, 2, 3) for a in (r, k, v, w))
    state, os = jax.lax.scan(step, state, xs)
    return os.transpose(1, 0, 2, 3), state


def apply_tmix(p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig,
               prev_tok: jax.Array, state: jax.Array
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Time-mix over a full sequence. Returns (out, last_tok, new_state)."""
    B, S, d = x.shape
    H, Dh = _dims(cfg)
    xs = _shift(x, prev_tok)
    xr = _lerp(x, xs, p["mix_r"])
    xk = _lerp(x, xs, p["mix_k"])
    xv = _lerp(x, xs, p["mix_v"])
    xg = _lerp(x, xs, p["mix_g"])
    xw = _lerp(x, xs, p["mix_w"])

    dt = x.dtype
    r = (xr @ p["wr"].astype(dt)).reshape(B, S, H, Dh).astype(jnp.float32)
    k = (xk @ p["wk"].astype(dt)).reshape(B, S, H, Dh).astype(jnp.float32)
    v = (xv @ p["wv"].astype(dt)).reshape(B, S, H, Dh).astype(jnp.float32)
    g = jax.nn.silu(xg @ p["wg"].astype(dt))
    w = rwkv_decay(p, xw).reshape(B, S, H, Dh)              # fp32

    if cfg.rwkv_impl == "pallas" and S > 1:
        from repro.kernels.rwkv6.ops import wkv
        o, state = wkv(r, k, v, w, p["u"].astype(jnp.float32), state,
                       chunk=min(64, S) if S % min(64, S) == 0 else S)
    else:
        o, state = _wkv_scan(r, k, v, w, p["u"].astype(jnp.float32), state)
    o = o.reshape(B, S, d).astype(dt)
    o = L.rms_norm(o, p["ln_x"], cfg.norm_eps) * g
    return o @ p["wo"].astype(dt), x[:, -1:], state


def apply_cmix(p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig,
               prev_tok: jax.Array) -> Tuple[jax.Array, jax.Array]:
    xs = _shift(x, prev_tok)
    xk = _lerp(x, xs, p["mix_k"])
    xr = _lerp(x, xs, p["mix_r"])
    dt = x.dtype
    kk = jnp.square(jax.nn.relu(xk @ p["wk"].astype(dt)))
    out = jax.nn.sigmoid(xr @ p["wr"].astype(dt)) * (kk @ p["wv"].astype(dt))
    return out, x[:, -1:]


def init_rwkv_state(cfg: ModelConfig, batch: int, dtype) -> Dict[str, jax.Array]:
    H, Dh = _dims(cfg)
    return {
        "wkv": jnp.zeros((batch, H, Dh, Dh), jnp.float32),
        "tok_t": jnp.zeros((batch, 1, cfg.d_model), dtype),
        "tok_c": jnp.zeros((batch, 1, cfg.d_model), dtype),
    }


def decode_tmix(p, x, cfg, st):
    """x: (B,1,d). One-step time-mix against carried state."""
    out, last, wkv = apply_tmix(p, x, cfg, st["tok_t"], st["wkv"])
    return out, {**st, "tok_t": last, "wkv": wkv}


def decode_cmix(p, x, cfg, st):
    out, last = apply_cmix(p, x, cfg, st["tok_c"])
    return out, {**st, "tok_c": last}

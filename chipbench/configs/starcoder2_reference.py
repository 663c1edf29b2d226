"""Plain reference of the starcoder2-3b configurations, as they are run.

A straightforward ``jax.numpy`` forward pass and loss in float32 with
``default_matmul_precision("highest")``: no kernel, no cache, no batching
across requests. It imports nothing of the program and reads only the
weights the benchmark made (``chipbench/weights.py``), by the names of
the leaves. The equations are those the configuration file states:

    x   = E[tok]
    per layer:  h = rms(x) * (1 + g1)
                q, k, v = h Wq + bq, h Wk + bk, h Wv + bv   (24 q, 2 kv heads)
                q, k    = rope(q), rope(k)       (theta 1e4, split halves)
                x      += causal_softmax(q k^T / sqrt(128)) v  Wo
                h = rms(x) * (1 + g2)
                x      += gelu_tanh(h Wi) Wo2
    logits = (rms(x) * (1 + gf)) W_out

``quantize`` turns the same pass into the check's control: every matrix
product takes both operands rounded to float8 e4m3 with one scale per
tensor, the precision below bfloat16.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
CHUNK = 1024      # query rows per block of scores, to bound memory


def _round8(x: jax.Array) -> jax.Array:
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(FP8).astype(F32) * s


@jax.custom_vjp
def fp8(x: jax.Array) -> jax.Array:
    """``x`` rounded to float8 e4m3 under one scale for the tensor; in the
    backward pass the cotangent is rounded the same way, under a scale
    of its own, as an fp8 training step scales its gradients."""
    return _round8(x)


fp8.defvjp(lambda x: (_round8(x), None), lambda _, ct: (_round8(ct),))


def mm(a, b, spec, quantize):
    """einsum ``spec`` of ``a`` and ``b``; with ``quantize`` both operands
    are rounded to float8 first."""
    if quantize:
        a, b = fp8(a), fp8(b)
    return jnp.einsum(spec, a, b)


def _rms(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g)


def _rope(x, pos, theta):
    """x: (..., S, H, D); pos: (..., S). Rotates the two halves of D."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = pos[..., None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend(q, k, v, quantize):
    """Causal attention, q: (B, S, KV, G, D), k/v: (B, S, KV, D); the
    scores are made ``CHUNK`` query rows at a time."""
    B, S, KV, G, D = q.shape
    C = min(CHUNK, S)
    if S % C:
        C = S
    kj = jnp.arange(S)

    def block(args):
        qc, off = args                                   # (B, C, KV, G, D)
        s = mm(qc, k, "bqkgd,bskd->bkgqs", quantize) * D ** -0.5
        mask = kj[None, :] <= (off + jnp.arange(C))[:, None]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return mm(p, v, "bkgqs,bskd->bqkgd", quantize)

    if C == S:
        return block((q, 0))
    qs = q.reshape(B, S // C, C, KV, G, D).swapaxes(0, 1)
    out = jax.lax.map(jax.checkpoint(block),
                      (qs, jnp.arange(S // C) * C))      # (n, B, C, ...)
    return out.swapaxes(0, 1).reshape(B, S, KV, G, D)


def _layer(x, lp, pos, sizes, quantize):
    eps, theta = sizes["norm_epsilon"], sizes["rope_theta"]
    H, KV = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    a = lp["attn"]
    h = _rms(x, lp["ln1"]["gamma"], eps)
    q = mm(h, a["wq"], "bsd,dhk->bshk", quantize) + a["bq"]
    k = mm(h, a["wk"], "bsd,dhk->bshk", quantize) + a["bk"]
    v = mm(h, a["wv"], "bsd,dhk->bshk", quantize) + a["bv"]
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    B, S, _, D = q.shape
    o = _attend(q.reshape(B, S, KV, H // KV, D), k, v, quantize)
    o = o.reshape(B, S, H, D)
    x = x + mm(o, a["wo"], "bshk,hkd->bsd", quantize)
    h = _rms(x, lp["ln2"]["gamma"], eps)
    m = lp["mlp"]
    u = jax.nn.gelu(mm(h, m["wi"], "bsd,df->bsf", quantize),
                    approximate=True)
    return x + mm(u, m["wo"], "bsf,fd->bsd", quantize)


def hidden(params: Dict[str, Any], tokens: jax.Array, sizes: Dict[str, Any],
           quantize: bool = False, remat: bool = False,
           place: Optional[Callable] = None) -> jax.Array:
    """Final normed hidden states (B, S, d) for tokens (B, S), causal.
    ``remat`` recomputes each layer in the backward pass; ``place``, if
    given, is applied to the residual stream at every layer (to keep it
    split by rows across chips)."""
    place = place or (lambda x: x)
    with jax.default_matmul_precision("highest"):
        S = tokens.shape[1]
        pos = jnp.arange(S)[None, :]
        x = place(params["embed"]["tok"][tokens].astype(F32))

        def body(x, lp):
            return place(_layer(x, lp, pos, sizes, quantize)), None

        if remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["layers"])
        return _rms(x, params["final_norm"]["gamma"].astype(F32),
                    sizes["norm_epsilon"])


def loss(params: Dict[str, Any], tokens: jax.Array, labels: jax.Array,
         sizes: Dict[str, Any], quantize: bool = False,
         place: Optional[Callable] = None) -> jax.Array:
    """Mean next-token cross-entropy over every position, float32."""
    x = hidden(params, tokens, sizes, quantize, remat=True, place=place)
    with jax.default_matmul_precision("highest"):
        lg = mm(x, params["embed"]["out"].astype(F32), "bsd,dv->bsv",
                 quantize)
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return (lse - gold).mean()

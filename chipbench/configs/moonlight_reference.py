"""Plain reference of the Moonlight-16B-A3B configurations, as they are run.

A straightforward ``jax.numpy`` forward pass and loss in float32 with
``default_matmul_precision("highest")``: no kernel, no cache, no sorting
of tokens by expert. It imports nothing of the program and reads only
the weights the benchmark made (``chipbench/weights.py``), by the names
of the leaves; the widths come from the leaves' shapes. The equations
(deepseek-v3, as the configuration file states them):

    x   = E[tok]
    per layer:  h = rms(x) * (1 + g1)
                q = h Wq = [q_n, q_r];  [c, k_r] = h Wdkv;  c = rms(c)(1 + gc)
                k = [c Wk, rope(k_r) for every head];  v = c Wv
                x += causal_softmax([q_n, rope(q_r)] k^T / sqrt(192)) v Wo
                h = rms(x) * (1 + g2)
      dense layer:   x += (silu(h Wg) * h Wi) Wo
      expert layer:  s = sigmoid(h R);  top-k of s + b chooses the experts;
                     w = s at those, normalised over the k, times 2.446;
                     x += sum over held experts e of w_e SwiGLU_e(h)
                          + SwiGLU_shared(h)
    logits = (rms(x) * (1 + gf)) W_out

Each held expert runs on every token, weighted by its routing weight
(0 where it was not chosen), one expert at a time so that the layer fits
beside the optimizer's state. The held experts are the leaves' first
axis, experts ``FIRST_HELD`` on; the routing constants the benchmark's
``sizes`` do not carry are the module constants below, which a test
holds to the configuration file.

The loss's value is the mean next-token cross-entropy, the program's
``loss``; its gradient also carries ``AUX_COEF`` times each expert
layer's sequence-wise balance loss, as the program's does.

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
CHUNK = 1024      # query rows per block of scores, and positions per block
                  # of logits, to bound memory

TOP_K = 6                 # num_experts_per_tok
ROUTED_SCALING = 2.446    # routed_scaling_factor
FIRST_HELD = 0            # the first expert this chip holds
AUX_COEF = 0.001          # assumed.aux_loss_alpha


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


def _blocks(S: int) -> int:
    C = min(CHUNK, S)
    return C if S % C == 0 else S


def _attend(q, k, v, quantize):
    """Causal attention, q/k: (B, S, H, Dq), v: (B, S, H, Dv); the scores
    are made ``CHUNK`` query rows at a time."""
    B, S, H, D = q.shape
    C = _blocks(S)
    kj = jnp.arange(S)

    def block(args):
        qc, off = args                                   # (B, C, H, D)
        s = mm(qc, k, "bqhd,bshd->bhqs", quantize) * D ** -0.5
        mask = kj[None, :] <= (off + jnp.arange(C))[:, None]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return mm(p, v, "bhqs,bshd->bqhd", quantize)

    if C == S:
        return block((q, 0))
    qs = q.reshape(B, S // C, C, H, D).swapaxes(0, 1)
    out = jax.lax.map(jax.checkpoint(block),
                      (qs, jnp.arange(S // C) * C))      # (n, B, C, ...)
    return out.swapaxes(0, 1).reshape(B, S, H, v.shape[-1])


def _attention(x, a, pos, sizes, quantize):
    eps, theta = sizes["norm_epsilon"], sizes["rope_theta"]
    r, _, Dn = a["wk"].shape
    q = mm(x, a["wq"], "bsd,dhk->bshk", quantize)
    q = jnp.concatenate([q[..., :Dn], _rope(q[..., Dn:], pos, theta)], -1)
    ckv = mm(x, a["wdkv"], "bsd,dr->bsr", quantize)
    c = _rms(ckv[..., :r], a["kv_norm"]["gamma"], eps)
    k_r = _rope(ckv[..., None, r:], pos, theta)
    k_n = mm(c, a["wk"], "bsr,rhk->bshk", quantize)
    v = mm(c, a["wv"], "bsr,rhk->bshk", quantize)
    k = jnp.concatenate(
        [k_n, jnp.broadcast_to(k_r, k_n.shape[:-1] + k_r.shape[-1:])], -1)
    o = _attend(q, k, v, quantize)
    return mm(o, a["wo"], "bshk,hkd->bsd", quantize)


def _swiglu(h, m, quantize):
    u = jax.nn.silu(mm(h, m["wg"], "bsd,df->bsf", quantize)) \
        * mm(h, m["wi"], "bsd,df->bsf", quantize)
    return mm(u, m["wo"], "bsf,fd->bsd", quantize)


def _experts(h, m, quantize):
    """(the held experts' part + the shared experts, balance loss)."""
    E = m["router"].shape[-1]
    s = jax.nn.sigmoid(mm(h, m["router"], "bsd,de->bse", quantize))
    _, idx = jax.lax.top_k(s + m["router_bias"], TOP_K)     # (B, S, k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20)    # norm_topk_prob
    w = w * ROUTED_SCALING

    def one(y, e):
        we, wi, wg, wo = e
        gate = jnp.sum(jnp.where(idx == we, w, 0.0), axis=-1)   # (B, S)
        part = _swiglu(h, {"wi": wi, "wg": wg, "wo": wo}, quantize)
        return y + gate[..., None] * part, None

    held = FIRST_HELD + jnp.arange(m["wi"].shape[0])
    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(h),
                        (held, m["wi"], m["wg"], m["wo"]))
    y = y + _swiglu(h, m["shared"], quantize)
    # sequence-wise balance loss: f_e (share of the sequence's
    # assignments, times E) times P_e (mean score normalised over experts)
    S = h.shape[1]
    f = jax.nn.one_hot(idx, E, dtype=F32).sum(axis=(1, 2)) * E / (S * TOP_K)
    P = (s / s.sum(-1, keepdims=True)).mean(axis=1)
    return y, jnp.mean(jnp.sum(f * P, axis=-1))


def _layer(x, lp, pos, sizes, quantize):
    """One layer: ``(x, balance loss)``, 0 for a dense layer."""
    eps = sizes["norm_epsilon"]
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    h = _rms(x, lp["ln1"]["gamma"], eps)
    x = x + _attention(h, lp["attn"], pos, sizes, quantize)
    h = _rms(x, lp["ln2"]["gamma"], eps)
    if "mlp" in lp:
        return x + _swiglu(h, lp["mlp"], quantize), jnp.zeros((), F32)
    y, bal = _experts(h, lp["moe"], quantize)
    return x + y, bal


def hidden(params: Dict[str, Any], tokens: jax.Array, sizes: Dict[str, Any],
           quantize: bool = False, remat: bool = False,
           place: Optional[Callable] = None):
    """``(final normed hidden states (B, S, d), summed balance loss)`` for
    tokens (B, S), causal. ``remat`` recomputes each layer in the
    backward pass; ``place``, if given, is applied to the residual stream
    at every layer (to keep it split by rows across chips)."""
    place = place or (lambda x: x)
    with jax.default_matmul_precision("highest"):
        S = tokens.shape[1]
        pos = jnp.arange(S)[None, :]
        x = place(params["embed"]["tok"][tokens].astype(F32))

        def body(carry, lp):
            x, bal = carry
            x, b = _layer(x, lp, pos, sizes, quantize)
            return (place(x), bal + b), None

        if remat:
            body = jax.checkpoint(body)
        carry = (x, jnp.zeros((), F32))
        for stack in ("dense_layers", "layers"):
            if stack in params:
                carry, _ = jax.lax.scan(body, carry, params[stack])
        x, bal = carry
        return _rms(x, params["final_norm"]["gamma"].astype(F32),
                    sizes["norm_epsilon"]), bal


def loss(params: Dict[str, Any], tokens: jax.Array, labels: jax.Array,
         sizes: Dict[str, Any], quantize: bool = False,
         place: Optional[Callable] = None) -> jax.Array:
    """Mean next-token cross-entropy over every position, float32; the
    balance term adds to the gradient and not to the value."""
    x, bal = hidden(params, tokens, sizes, quantize, remat=True, place=place)
    w = params["embed"]["out"].astype(F32)
    B, S, D = x.shape
    C = _blocks(S)

    def nll(args):
        xc, lc = args                                    # (B, C, D), (B, C)
        lg = mm(xc, w, "bsd,dv->bsv", quantize)
        gold = jnp.take_along_axis(lg, lc[..., None], axis=-1)[..., 0]
        return (jax.nn.logsumexp(lg, axis=-1) - gold).sum()

    with jax.default_matmul_precision("highest"):
        xs = x.reshape(B, S // C, C, D).swapaxes(0, 1)
        ls = labels.reshape(B, S // C, C).swapaxes(0, 1)
        ce = jax.lax.map(jax.checkpoint(nll), (xs, ls)).sum() / (B * S)
    bal = AUX_COEF * bal
    return ce + bal - jax.lax.stop_gradient(bal)

"""Single-token decode attention vs. a long KV cache — Pallas TPU kernel.

GPU split-K decode parallelizes one query's KV reduction across SMs and
merges partial softmaxes in a second pass. The TPU adaptation streams KV
blocks *sequentially* through VMEM (grid last axis "arbitrary") while the
online-softmax state rides in VMEM scratch — same O(S) HBM traffic, no
merge pass, and the block stream is double-buffered by Mosaic so the
kernel is HBM-bandwidth-bound, which is the roofline for decode.

Decode is memory-bound: arithmetic intensity ~ 2 flops/byte of KV, so the
only lever is moving KV bytes at line rate — hence blocks shaped
(blk_k x D) with D on lanes, and all q heads of one kv group processed
against each streamed KV block (the GQA reuse is free: q is tiny).

The cache may be longer than the valid prefix; ``lengths`` masks per batch
row. Grid: (B, KV, nk). Each step does a (G x D) @ (D x blk_k) MXU pass
where G = heads-per-kv-group.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")
LANES = 128


def _decode_kernel(len_ref, win_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *,
                   sm_scale: float, blk_k: int):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    length = len_ref[pl.program_id(0)]
    win = win_ref[0]                                 # <=0 means full history

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k_start = ki * blk_k

    run = k_start < length                           # skip fully-invalid blocks
    run = jnp.logical_and(                           # and blocks below window
        run, jnp.logical_or(win <= 0, k_start + blk_k - 1 >= length - win))

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (blk_k, D)
        v = v_ref[0, 0].astype(jnp.float32)          # (blk_k, D)
        def valid(k_pos):
            return jnp.logical_and(
                k_pos < length,
                jnp.logical_or(win <= 0, k_pos >= length - win))

        # The mask is built twice, as a column for the (blk_k, D) k/v
        # blocks and as a row for the (G, blk_k) scores: Mosaic has no
        # transpose of boolean vectors.
        col = valid(k_start + jax.lax.broadcasted_iota(jnp.int32, (blk_k, 1), 0))
        row = valid(k_start + jax.lax.broadcasted_iota(jnp.int32, (1, blk_k), 1))
        k = jnp.where(col, k, 0.0)
        v = jnp.where(col, v, 0.0)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                             # (G, blk_k)
        s = jnp.where(row, s, NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(jnp.where(m_new == NEG_INF, 0.0, m_prev - m_new))
        l_ref[...] = jnp.broadcast_to(alpha * l_prev
                                      + jnp.sum(p, -1, keepdims=True),
                                      l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0, 0, ...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                            ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "blk_k", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array, *, window=0,
                     sm_scale: float | None = None, blk_k: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q: (B, H, D) one token; k/v: (B, KV, S, D); lengths: (B,) int32.

    Valid cache positions for row b are [0, lengths[b]); a positive
    ``window`` (traced or static) restricts to the last ``window`` of them.
    Returns (B, H, D).
    """
    B, H, D = q.shape
    _, KV, S, _ = k.shape
    assert H % KV == 0
    G = H // KV
    if sm_scale is None:
        sm_scale = D ** -0.5
    blk_k = min(blk_k, S)
    nk = pl.cdiv(S, blk_k)
    qg = q.reshape(B, KV, G, D)
    win = jnp.asarray(window, jnp.int32).reshape(1)

    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale, blk_k=blk_k)

    out = pl.pallas_call(
        kernel,
        grid=(B, KV, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),     # lengths, whole array
            pl.BlockSpec(memory_space=pltpu.SMEM),     # window scalar
            pl.BlockSpec((1, 1, G, D), lambda b, g, ki: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, blk_k, D), lambda b, g, ki: (b, g, ki, 0)),
            pl.BlockSpec((1, 1, blk_k, D), lambda b, g, ki: (b, g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, g, ki: (b, g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, LANES), jnp.float32),
            pltpu.VMEM((G, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, win, qg, k, v)
    return out.reshape(B, H, D)

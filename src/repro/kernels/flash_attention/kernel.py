"""Blocked online-softmax attention (flash) — Pallas TPU kernel.

TPU adaptation (not a CUDA port): the grid's last axis iterates KV blocks
*sequentially* ("arbitrary" dimension semantics) while fp32 running-max /
running-sum / accumulator live in VMEM scratch that persists across that
axis — the TPU analogue of a CUDA thread block's shared-memory state. Block
shapes keep the MXU busy: (blk_q x head_dim) @ (head_dim x blk_k) contractions
with blk_q/blk_k multiples of 128 and head_dim padded to lanes by Mosaic.

Supports causal masking, GQA (q-head -> kv-head via the k/v index_map, no
materialized head broadcast), and gemma3-style sliding windows. The window
is a *traced scalar* (SMEM) because gemma3 scans over layers with per-layer
windows — one compiled kernel serves local and global layers. Fully-masked
KV blocks are skipped with ``pl.when`` — for causal masks that's ~2x fewer
MXU passes, and for sliding windows the skip makes attention O(S*W).

VMEM working set per grid step (bf16 in, fp32 scratch):
    q: blk_q*D*2  k,v: blk_k*D*2*2  acc: blk_q*D*4  m,l: blk_q*128*4*2
    (blk_q=blk_k=256, D=128: ~0.7 MB — far under the ~16 MB VMEM budget,
     leaving room for Mosaic's double buffering of the k/v streams.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")
LANES = 128


def _attn_kernel(win_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                 *, sm_scale: float, causal: bool,
                 blk_q: int, blk_k: int, seq_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    win = win_ref[0]                                       # <=0 means global

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * blk_q
    k_start = ki * blk_k

    # Block-level skip: entirely above the diagonal (causal) or entirely
    # below the window. Row/col offsets inside the block are handled by the
    # element mask; this predicate only prunes whole blocks.
    run = k_start < seq_k
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + blk_q - 1)
    run = jnp.logical_and(
        run, jnp.logical_or(win <= 0,
                            k_start + blk_k - 1 >= q_start - win + 1))

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                 # (blk_q, D)
        k = k_ref[0, 0].astype(jnp.float32)                 # (blk_k, D)
        v = v_ref[0, 0].astype(jnp.float32)                 # (blk_k, D)
        # Ragged tail: rows past seq_k are padding (undefined contents) —
        # zero them so 0-weight x garbage can't poison the accumulator.
        kv_valid = (k_start + jax.lax.broadcasted_iota(
            jnp.int32, (blk_k, 1), 0)) < seq_k
        k = jnp.where(kv_valid, k, 0.0)
        v = jnp.where(kv_valid, v, 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                    # (blk_q, blk_k)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < seq_k                                # ragged tail
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        mask = jnp.logical_and(
            mask, jnp.logical_or(win <= 0, k_pos > q_pos - win))
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                               # (blk_q, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # all-masked rows keep m = -inf; exp(-inf - -inf) guarded to 0
        p = jnp.exp(jnp.where(m_new == NEG_INF, NEG_INF, s - m_new))
        alpha = jnp.exp(jnp.where(m_new == NEG_INF, 0.0, m_prev - m_new))
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, ...] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sm_scale", "blk_q", "blk_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window=0,
                    sm_scale: float | None = None,
                    blk_q: int = 256, blk_k: int = 256,
                    interpret: bool = False) -> jax.Array:
    """q: (B, H, Sq, D); k/v: (B, KV, Sk, D). Returns (B, H, Sq, D).

    H must be a multiple of KV (GQA); q-head h reads kv-head h // (H//KV).
    ``window`` may be a python int or a traced int32 scalar (<=0 = global).
    """
    B, H, Sq, D = q.shape
    _, KV, Sk, _ = k.shape
    assert H % KV == 0, (H, KV)
    group = H // KV
    if sm_scale is None:
        sm_scale = D ** -0.5
    blk_q = min(blk_q, Sq)
    blk_k = min(blk_k, Sk)
    nq = pl.cdiv(Sq, blk_q)
    nk = pl.cdiv(Sk, blk_k)
    win = jnp.asarray(window, jnp.int32).reshape(1)

    kernel = functools.partial(
        _attn_kernel, sm_scale=sm_scale, causal=causal,
        blk_q=blk_q, blk_k=blk_k, seq_k=Sk)

    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, blk_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, D), jnp.float32),       # acc
            pltpu.VMEM((blk_q, LANES), jnp.float32),   # running max
            pltpu.VMEM((blk_q, LANES), jnp.float32),   # running sum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(win, q, k, v)

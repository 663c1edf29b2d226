"""RWKV-6 (Finch) WKV recurrence — chunked Pallas TPU kernel.

The defining recurrence (per head, state S in R^{Dk x Dv}):

    o_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T          w_t in (0,1): data-dependent

A naive port is a length-S sequential loop — dead on the MXU. The TPU form
expands each chunk in *pairwise log-decay space*: with L_t = sum_{s<=t}
log w_t (elementwise, <= 0) the contribution of token j to token t>j is

    A[t, j] = sum_d  r[t,d] k[j,d] exp(L_{t-1,d} - L_{j,d})

where every exponent is <= 0 (decay), so unlike the classic k/W
"de-decayed keys" trick there is NO overflow for any data-dependent w —
the (L, L, D) decay tensor trades VMEM (L^2 D fp32; 1 MB at L=D=64) for
unconditional fp32 safety. Chunk -> chunk carries only S in VMEM scratch
across the sequential grid axis, exactly like the SSD kernel.

Per grid step:  A @ v, (r * exp(L_excl)) @ S, and the rank-L state update
(k * exp(L_last - L))^T @ v — three MXU contractions per chunk. The
wrapper computes log w and its in-chunk cumsums (L, L_excl) with XLA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv6_kernel(r_ref, k_ref, v_ref, lw_ref, lwx_ref, u_ref, s0_ref,
                  o_ref, sout_ref, state_ref, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)              # (L, D)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0]                                # inclusive  (L, D)
    lwx = lwx_ref[0, 0]                              # exclusive: L_{t-1}
    u = u_ref[0].astype(jnp.float32)                 # (1, D)

    # pairwise intra-chunk attention with per-channel decay
    dec = jnp.exp(lwx[:, None, :] - lw[None, :, :])  # (L, L, D); tril <= 1
    a = jnp.sum(r[:, None, :] * k[None, :, :] * dec, axis=-1)  # (L, L)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    j_idx = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    diag = jnp.sum(r * u * k, axis=-1, keepdims=True)  # bonus term at j == t
    a = jnp.where(t_idx > j_idx, a, 0.0) + jnp.where(t_idx == j_idx, diag, 0.0)

    o_intra = jax.lax.dot_general(a, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    state_t = state_ref[...]                         # (Dv, Dk) pre-chunk
    o_state = jax.lax.dot_general(r * jnp.exp(lwx), state_t,
                                  (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    o_ref[0, 0, ...] = (o_intra + o_state).astype(o_ref.dtype)

    last = lw[chunk - 1:, :]                         # (1, D)
    kd = k * jnp.exp(last - lw)                      # (L, D), factors <= 1
    state_ref[...] = jnp.exp(last) * state_t + jax.lax.dot_general(
        v, kd, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ci == nc - 1)
    def _emit_state():
        sout_ref[0, 0, ...] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
               u: jax.Array, s0: jax.Array | None = None, *, chunk: int = 64,
               interpret: bool = False):
    """r/k/v/w: (B, H, S, D) fp32 (w = per-step decay in (0,1));
    u: (H, D); s0 optional initial state (B, H, D, D) fp32.
    Returns (o (B, H, S, D) fp32, final_state (B, H, D, D) fp32)."""
    B, H, S, D = r.shape
    L = min(chunk, S)
    assert S % L == 0, f"seq {S} not divisible by chunk {L}"
    nc = S // L
    if s0 is None:
        s0 = jnp.zeros((B, H, D, D), jnp.float32)
    # Per-chunk log-decay integrals come in precomputed: Mosaic has no
    # cumsum. The state is carried transposed, (Dv, Dk), so that the
    # per-key decay scales lanes and no vector is transposed in-kernel.
    logw = jnp.log(w.astype(jnp.float32))            # <= 0
    lw = jnp.cumsum(logw.reshape(B, H, nc, L, D), axis=3).reshape(B, H, S, D)
    lwx = lw - logw

    kernel = functools.partial(_rwkv6_kernel, chunk=L)
    blk = pl.BlockSpec((1, 1, L, D), lambda b, h, c: (b, h, c, 0))
    sblk = pl.BlockSpec((1, 1, D, D), lambda b, h, c: (b, h, 0, 0))
    o, s_t = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[blk, blk, blk, blk, blk,
                  # u as (H, 1, D): a (1, D) block of (H, D) would break
                  # the TPU's (8, 128) tiling of the last two dimensions
                  pl.BlockSpec((1, 1, D), lambda b, h, c: (h, 0, 0)), sblk],
        out_specs=(blk, sblk),
        out_shape=(jax.ShapeDtypeStruct((B, H, S, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, D, D), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, lw, lwx, u[:, None, :], s0.swapaxes(-1, -2))
    return o, s_t.swapaxes(-1, -2)

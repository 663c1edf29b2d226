"""Blocked online-softmax attention (flash) and its backward — Pallas TPU.

TPU adaptation (not a CUDA port): each kernel's last grid axis iterates
blocks *sequentially* ("arbitrary" dimension semantics) while its fp32
statistics and accumulators live in VMEM scratch that persists across
that axis — the TPU analogue of a CUDA thread block's shared-memory
state. Both dots of every block take their operands in the input dtype
(bf16 in the models) with fp32 accumulation, so the MXU runs in its
native type; the softmax statistics (running max, running sum,
log-sum-exp) and every accumulator stay fp32, and the probabilities are
cast to v's dtype for the PV dot, as the XLA path casts its ``probs``.

Layout: the model's own (B, S, heads, D), read as (B, S, heads*D) so a
block is ``(blk, D)`` at column ``head*D`` — whole (16, 128) tiles of the
HBM layout, and no transpose to head-major on either side.

The backward follows FlashAttention-2. The forward also returns each
row's log-sum-exp (fp32, (B, H, 1, Sq)); residuals are q, k, v, o and
the lse, never a score block. D = rowsum(dO * O) is one XLA reduction;
then two kernels recompute P = exp(S - lse) block by block:

- dK/dV: grid over kv blocks, sequential over (q-head of the group, q
  block), so GQA's sum over the group happens in the VMEM accumulator;
  it works on transposed blocks (S^T = K Q^T), where lse and D are rows.
- dQ: grid over q blocks, sequential over kv blocks.

Masks: causal, GQA (q-head -> kv-head via the index maps, no broadcast of
k/v), ragged tails, and gemma3-style sliding windows. The window is a
*traced scalar* (scalar-prefetched into SMEM) because gemma3 scans over
layers with per-layer windows — one compiled kernel serves local and
global layers. Every kernel skips whole blocks outside the causal or
window mask (``pl.when``), and its index maps clamp the skipped steps
onto a block that runs, so a skipped step issues no DMA; only blocks on
the mask's edge pay for the element mask.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")
LANES = 128
NT = (((1,), (1,)), ((), ()))          # a @ b.T
NN = (((1,), (0,)), ((), ()))          # a @ b
# Scoped VMEM a kernel may use: the larger blocks' fp32 score tiles
# (4 MB each at 1024 x 1024) outgrow the compiler's default limit.
VMEM_LIMIT = 100 * 2 ** 20


class Tiles(NamedTuple):
    """Static geometry of one kernel call."""
    causal: bool
    seq_q: int
    seq_k: int
    bq: int
    bk: int

    @property
    def nq(self) -> int:
        return pl.cdiv(self.seq_q, self.bq)

    @property
    def nk(self) -> int:
        return pl.cdiv(self.seq_k, self.bk)


def _tiles(causal, seq_q, seq_k, blocks) -> Tiles:
    return Tiles(causal, seq_q, seq_k, min(blocks[0], seq_q),
                 min(blocks[1], seq_k))


def _kv_range(qi, win, t: Tiles):
    """[lo, hi]: the kv blocks q block ``qi`` attends to (may be empty)."""
    q_start = qi * t.bq
    hi = t.nk - 1
    if t.causal:
        hi = jnp.minimum(hi, (q_start + t.bq - 1) // t.bk)
    lo = jnp.where(win > 0, jnp.maximum(q_start - win + 1, 0) // t.bk, 0)
    return lo, hi


def _q_range(ki, win, t: Tiles):
    """[lo, hi]: the q blocks that attend to kv block ``ki``."""
    k_start = ki * t.bk
    lo = k_start // t.bq if t.causal else 0
    hi = jnp.where(win > 0,
                   jnp.minimum(t.nq - 1, (k_start + t.bk + win - 2) // t.bq),
                   t.nq - 1)
    return lo, hi


def _clamp(i, lo, hi, n):
    """Index map for a skipped step: the nearest block that runs."""
    return jnp.clip(jnp.clip(i, lo, hi), 0, n - 1)


def _interior(qi, ki, win, t: Tiles):
    """True where no element of block (qi, ki) is masked."""
    q_start, k_start = qi * t.bq, ki * t.bk
    q_end, k_end = q_start + t.bq - 1, k_start + t.bk - 1
    full = jnp.logical_or(win <= 0, k_start > q_end - win)
    if t.causal:
        full = jnp.logical_and(full, k_end <= q_start)
    if t.seq_k % t.bk:
        full = jnp.logical_and(full, k_end < t.seq_k)
    if t.seq_q % t.bq:
        full = jnp.logical_and(full, q_end < t.seq_q)
    return full


def _mask(q_pos, k_pos, win, t: Tiles):
    ok = jnp.logical_or(win <= 0, k_pos > q_pos - win)
    if t.causal:
        ok = jnp.logical_and(ok, k_pos <= q_pos)
    if t.seq_k % t.bk:
        ok = jnp.logical_and(ok, k_pos < t.seq_k)
    if t.seq_q % t.bq:
        ok = jnp.logical_and(ok, q_pos < t.seq_q)
    return ok


def _rows_valid(start, n, limit):
    """(n, 1) mask of the rows of a block that lie before ``limit``."""
    return start + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0) < limit


def _col(row):
    """(1, n) row -> (n, 1) column."""
    return jnp.transpose(jnp.broadcast_to(row, (8, row.shape[1])))[:, :1]


def _branches(run, interior, step):
    """Run ``step(masked)`` where ``run``: the element mask only on the
    mask's edge."""
    pl.when(jnp.logical_and(run, interior))(lambda: step(False))
    pl.when(jnp.logical_and(run, jnp.logical_not(interior)))(
        lambda: step(True))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(win_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, t: Tiles, sm_scale: float):
    qi, ki = pl.program_id(2), pl.program_id(3)
    win = win_ref[0]                                       # <=0 means global

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    lo, hi = _kv_range(qi, win, t)

    def step(masked: bool):
        q, k, v = q_ref[...], k_ref[...], v_ref[...]        # (blk, D)
        if masked and t.seq_k % t.bk:
            # Ragged tail: rows past seq_k are padding (undefined contents)
            # — zero them so 0-weight x garbage can't poison the sums.
            valid = _rows_valid(ki * t.bk, t.bk, t.seq_k)
            k = jnp.where(valid, k, 0)
            v = jnp.where(valid, v, 0)
        s = jax.lax.dot_general(q, k, NT, preferred_element_type=jnp.float32)
        s = s * sm_scale                                    # (bq, bk)
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]         # (bq, 1)
        if masked:
            q_pos = qi * t.bq + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = ki * t.bk + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(_mask(q_pos, k_pos, win, t), s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # all-masked rows keep m = -inf; exp(-inf - -inf) guarded to 0
            p = jnp.exp(jnp.where(m_new == NEG_INF, NEG_INF, s - m_new))
            alpha = jnp.exp(jnp.where(m_new == NEG_INF, 0.0, m_prev - m_new))
        else:
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, NN, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    _branches(jnp.logical_and(ki >= lo, ki <= hi),
              _interior(qi, ki, win, t), step)

    @pl.when(ki == t.nk - 1)
    def _finalize():
        l = l_ref[...]                                      # (bq, LANES)
        out = acc_ref[...] / jnp.where(l[:, :1] == 0.0, 1.0, l[:, :1])
        o_ref[...] = out.astype(o_ref.dtype)
        # a row that sees no key gets lse = +inf, so exp(s - lse) = 0
        lse = jnp.where(l == 0.0, jnp.inf, m_ref[...] + jnp.log(l))
        lse_ref[...] = jnp.transpose(lse)[:1, :]            # (1, bq)


def _attend_fwd(q, k, v, win, *, D: int, group: int, t: Tiles,
                sm_scale: float, interpret: bool):
    B, _, HD = q.shape
    H = HD // D

    def kv_block(b, h, qi, ki, win_ref):
        lo, hi = _kv_range(qi, win_ref[0], t)
        return b, _clamp(ki, lo, hi, t.nk), h // group

    q_spec = pl.BlockSpec((None, t.bq, D), lambda b, h, qi, ki, w: (b, qi, h))
    kv_spec = pl.BlockSpec((None, t.bk, D), kv_block)
    lse_spec = pl.BlockSpec((None, None, 1, t.bq),
                            lambda b, h, qi, ki, w: (b, h, 0, qi))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, t=t, sm_scale=sm_scale),
        name="flash_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, t.nq, t.nk),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[q_spec, lse_spec],
            scratch_shapes=[
                pltpu.VMEM((t.bq, D), jnp.float32),        # acc
                pltpu.VMEM((t.bq, LANES), jnp.float32),    # running max
                pltpu.VMEM((t.bq, LANES), jnp.float32),    # running sum
            ]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, t.seq_q), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(win, q, k, v)


# ---------------------------------------------------------------------------
# Backward: dK/dV over kv blocks, dQ over q blocks
# ---------------------------------------------------------------------------

def _dkv_kernel(win_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, t: Tiles, sm_scale: float):
    ki, g, qi = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    win = win_ref[0]

    @pl.when(jnp.logical_and(g == 0, qi == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    lo, hi = _q_range(ki, win, t)

    def step(masked: bool):
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        lse, di = lse_ref[...], di_ref[...]                 # (1, bq)
        if masked and t.seq_q % t.bq:
            valid = _rows_valid(qi * t.bq, t.bq, t.seq_q)
            q = jnp.where(valid, q, 0)
            do = jnp.where(valid, do, 0)
            row_ok = qi * t.bq + jax.lax.broadcasted_iota(
                jnp.int32, (1, t.bq), 1) < t.seq_q
            lse = jnp.where(row_ok, lse, 0.0)
            di = jnp.where(row_ok, di, 0.0)
        if masked and t.seq_k % t.bk:
            valid = _rows_valid(ki * t.bk, t.bk, t.seq_k)
            k = jnp.where(valid, k, 0)
            v = jnp.where(valid, v, 0)
        st = jax.lax.dot_general(k, q, NT, preferred_element_type=jnp.float32)
        pt = jnp.exp(st * sm_scale - lse)                   # (bk, bq) = P^T
        if masked:
            k_pos = ki * t.bk + jax.lax.broadcasted_iota(
                jnp.int32, st.shape, 0)
            q_pos = qi * t.bq + jax.lax.broadcasted_iota(
                jnp.int32, st.shape, 1)
            pt = jnp.where(_mask(q_pos, k_pos, win, t), pt, 0.0)
        dv_acc[...] += jax.lax.dot_general(
            pt.astype(do.dtype), do, NN, preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - di)                               # dS^T
        dk_acc[...] += jax.lax.dot_general(
            dst.astype(q.dtype), q, NN, preferred_element_type=jnp.float32)

    _branches(jnp.logical_and(qi >= lo, qi <= hi),
              _interior(qi, ki, win, t), step)

    @pl.when(jnp.logical_and(g == pl.num_programs(3) - 1, qi == t.nq - 1))
    def _finalize():
        dk_ref[...] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(win_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
               dq_ref, dq_acc, *, t: Tiles, sm_scale: float):
    qi, ki = pl.program_id(2), pl.program_id(3)
    win = win_ref[0]

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    lo, hi = _kv_range(qi, win, t)

    def step(masked: bool):
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        lse, di = _col(lse_ref[...]), _col(di_ref[...])     # (bq, 1)
        if masked and t.seq_k % t.bk:
            valid = _rows_valid(ki * t.bk, t.bk, t.seq_k)
            k = jnp.where(valid, k, 0)
            v = jnp.where(valid, v, 0)
        s = jax.lax.dot_general(q, k, NT, preferred_element_type=jnp.float32)
        p = jnp.exp(s * sm_scale - lse)                     # (bq, bk)
        if masked:
            q_pos = qi * t.bq + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = ki * t.bk + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            p = jnp.where(_mask(q_pos, k_pos, win, t), p, 0.0)
        dp = jax.lax.dot_general(do, v, NT, preferred_element_type=jnp.float32)
        ds = p * (dp - di)
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, NN, preferred_element_type=jnp.float32)

    _branches(jnp.logical_and(ki >= lo, ki <= hi),
              _interior(qi, ki, win, t), step)

    @pl.when(ki == t.nk - 1)
    def _finalize():
        dq_ref[...] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)


def _attend_dkv(q, k, v, do, lse, di, win, *, D: int, group: int, t: Tiles,
                sm_scale: float, interpret: bool):
    """dK, dV: grid (b, kv-head, kv block, q-head of the group, q block)."""
    B, _, KVD = k.shape

    def q_block(b, kvh, ki, g, qi, w):
        lo, hi = _q_range(ki, w[0], t)
        return b, _clamp(qi, lo, hi, t.nq), kvh * group + g

    def row_block(b, kvh, ki, g, qi, w):
        lo, hi = _q_range(ki, w[0], t)
        return b, kvh * group + g, 0, _clamp(qi, lo, hi, t.nq)

    kv_spec = pl.BlockSpec((None, t.bk, D),
                           lambda b, kvh, ki, g, qi, w: (b, ki, kvh))
    q_spec = pl.BlockSpec((None, t.bq, D), q_block)
    row_spec = pl.BlockSpec((None, None, 1, t.bq), row_block)
    return pl.pallas_call(
        functools.partial(_dkv_kernel, t=t, sm_scale=sm_scale),
        name="flash_dkv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, KVD // D, t.nk, group, t.nq),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=[kv_spec, kv_spec],
            scratch_shapes=[pltpu.VMEM((t.bk, D), jnp.float32),   # dk
                            pltpu.VMEM((t.bk, D), jnp.float32)]),  # dv
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(win, q, k, v, do, lse, di)


def _attend_dq(q, k, v, do, lse, di, win, *, D: int, group: int, t: Tiles,
               sm_scale: float, interpret: bool):
    """dQ: grid (b, q-head, q block, kv block)."""
    B, _, HD = q.shape

    def kv_block(b, h, qi, ki, w):
        lo, hi = _kv_range(qi, w[0], t)
        return b, _clamp(ki, lo, hi, t.nk), h // group

    q_spec = pl.BlockSpec((None, t.bq, D), lambda b, h, qi, ki, w: (b, qi, h))
    kv_spec = pl.BlockSpec((None, t.bk, D), kv_block)
    row_spec = pl.BlockSpec((None, None, 1, t.bq),
                            lambda b, h, qi, ki, w: (b, h, 0, qi))
    return pl.pallas_call(
        functools.partial(_dq_kernel, t=t, sm_scale=sm_scale),
        name="flash_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, HD // D, t.nq, t.nk),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((t.bq, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(win, q, k, v, do, lse, di)


# ---------------------------------------------------------------------------
# Differentiable entry
# ---------------------------------------------------------------------------

class _Static(NamedTuple):
    causal: bool
    sm_scale: float
    blocks: Tuple[int, int]
    interpret: bool


def _flat(x):
    B, S, H, D = x.shape
    return x.reshape(B, S, H * D)


def _flash_fwd(q, k, v, win, c: _Static):
    _, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    o, lse = _attend_fwd(_flat(q), _flat(k), _flat(v), win, D=D,
                         group=H // KV,
                         t=_tiles(c.causal, Sq, Sk, c.blocks),
                         sm_scale=c.sm_scale, interpret=c.interpret)
    o = o.reshape(q.shape)
    return o, (q, k, v, win, o, lse)


def _flash_bwd(c: _Static, res, do):
    q, k, v, win, o, lse = res
    _, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    di = jnp.transpose(di, (0, 2, 1))[:, :, None, :]         # (B, H, 1, Sq)
    args = (_flat(q), _flat(k), _flat(v), _flat(do), lse, di, win)
    kw = dict(D=D, group=H // KV, t=_tiles(c.causal, Sq, Sk, c.blocks),
              sm_scale=c.sm_scale, interpret=c.interpret)
    dk, dv = _attend_dkv(*args, **kw)
    dq = _attend_dq(*args, **kw)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _flash(q, k, v, win, c: _Static):
    return _flash_fwd(q, k, v, win, c)[0]


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit,
                   static_argnames=("causal", "sm_scale", "blocks",
                                    "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window=0,
                    sm_scale: float | None = None,
                    blocks: Tuple[int, int] = (1024, 1024),
                    interpret: bool = False) -> jax.Array:
    """q: (B, Sq, H, D); k/v: (B, Sk, KV, D). Returns (B, Sq, H, D).

    Differentiable in q, k and v. H must be a multiple of KV (GQA);
    q-head h reads kv-head h // (H//KV). ``window`` may be a python int
    or a traced int32 scalar (<=0 = global). ``blocks`` is the
    ``(blk_q, blk_k)`` of all three kernels.
    """
    H, KV = q.shape[2], k.shape[2]
    assert H % KV == 0, (H, KV)
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    win = jnp.asarray(window, jnp.int32).reshape(1)
    return _flash(q, k, v, win, _Static(causal, float(sm_scale),
                                        tuple(blocks), interpret))

"""GQA/MQA/MHA and latent attention: training/prefill (blockwise) and
decode paths.

Latent attention (MLA, ``cfg.kv_lora_rank > 0``) projects each token down
to a normed latent and one rotary key shared by all heads, and up from
the latent to each head's key and value (``project_qkv``); its q/k heads
are wider than its v heads, and it has no decode cache yet.

Full attention (``attend``) has two paths, chosen by ``cfg.attn_impl``:

- the Pallas flash kernel (``repro.kernels.flash_attention``), which
  skips whole masked blocks and never writes a score block to HBM, with
  its own backward. ``"auto"`` (the default) takes it where the traced
  program compiles for a TPU, ``kv_len`` is None and the shapes tile the
  kernel's blocks; ``"pallas"`` takes it wherever ``kv_len`` is None.
  Under a mesh it runs per shard, and where the mesh cannot hold it per
  shard both fall back to the XLA path.
- the XLA path, which computes attention in query chunks
  (``cfg.attn_chunk``) so the materialized score block is
  (B, kvh, g, Cq, Skv) instead of the full (B, H, S, S), in the backward
  pass too where there are more than two chunks (each is recomputed
  there). ``"xla"`` forces it.

Each path runs under its own named scope (``kernel.flash_attention.pallas``,
``kernel.attention.xla_scan``), so a trace tells which one ran. Decode
(``attend_decode``) takes its kernel only under ``"pallas"``.

Sliding windows are passed as *per-layer runtime scalars* so a scan over
layers can mix local and global layers (gemma3's 5:1 pattern):
``window <= 0`` means full (global) attention.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.kernels.flash_attention import ops as flash
from repro.models import layers as L
from repro.obs.profiling import annotate_span


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_attention(kg: L.KeyGen, cfg: ModelConfig) -> Dict[str, L.Boxed]:
    if cfg.kv_lora_rank:
        return _init_latent(kg, cfg)
    d, H, KV, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "wq": L.param(kg, (d, H, Dh), ("embed", "heads", "head_dim")),
        "wk": L.param(kg, (d, KV, Dh), ("embed", "kv_heads", "head_dim")),
        "wv": L.param(kg, (d, KV, Dh), ("embed", "kv_heads", "head_dim")),
        "wo": L.param(kg, (H, Dh, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = L.param(kg, (H, Dh), ("heads", "head_dim"), init="zeros")
        p["bk"] = L.param(kg, (KV, Dh), ("kv_heads", "head_dim"), init="zeros")
        p["bv"] = L.param(kg, (KV, Dh), ("kv_heads", "head_dim"), init="zeros")
    return p


def _init_latent(kg: L.KeyGen, cfg: ModelConfig) -> Dict[str, L.Boxed]:
    d, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    qk, v = cfg.head_dim + cfg.qk_rope_head_dim, cfg.v_head_dim or cfg.head_dim
    return {
        "wq": L.param(kg, (d, H, qk), ("embed", "heads", "head_dim")),
        # down to the latent and the shared rotary key
        "wdkv": L.param(kg, (d, r + cfg.qk_rope_head_dim),
                        ("embed", "kv_lora")),
        "kv_norm": {"gamma": L.param(kg, (r,), ("kv_lora",), init="zeros")},
        # up from the latent to each head's no-rotary key and value
        "wk": L.param(kg, (r, H, cfg.head_dim),
                      ("kv_lora", "heads", "head_dim")),
        "wv": L.param(kg, (r, H, v), ("kv_lora", "heads", "head_dim")),
        "wo": L.param(kg, (H, v, d), ("heads", "head_dim", "embed")),
    }


def _project_latent(p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig,
                    positions: jax.Array
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Latent attention's q, k (B,S,H,Dh+rope) and v (B,S,H,Dv):

        q = x Wq = [q_n, rope(q_r)];   [c, k_r] = x Wdkv;   c = rms(c)
        k = [c Wk, rope(k_r) for every head];   v = c Wv
    """
    dt = x.dtype
    Dn, r = cfg.head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    q = jnp.concatenate(
        [q[..., :Dn], L.apply_rope(q[..., Dn:], positions, cfg.rope_theta)],
        axis=-1)
    ckv = x @ p["wdkv"].astype(dt)
    c = L.rms_norm(ckv[..., :r], p["kv_norm"]["gamma"], cfg.norm_eps)
    k_r = L.apply_rope(ckv[..., None, r:], positions, cfg.rope_theta)
    k_n = jnp.einsum("bsr,rhk->bshk", c, p["wk"].astype(dt))
    v = jnp.einsum("bsr,rhk->bshk", c, p["wv"].astype(dt))
    k_r = jnp.broadcast_to(k_r, k_n.shape[:-1] + k_r.shape[-1:])
    return q, jnp.concatenate([k_n, k_r], axis=-1), v


def project_qkv(p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig,
                positions: Optional[jax.Array] = None,
                mrope_positions: Optional[jax.Array] = None,
                rope: bool = True) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, d) -> q (B,S,H,Dh), k/v (B,S,KV,Dh), rotary applied."""
    dt = x.dtype
    if cfg.kv_lora_rank:
        if positions is None:
            positions = jnp.arange(x.shape[1])[None, :]
        return _project_latent(p, x, cfg, positions)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if rope:
        if cfg.use_mrope and mrope_positions is not None:
            q = L.apply_mrope(q, mrope_positions, cfg.rope_theta)
            k = L.apply_mrope(k, mrope_positions, cfg.rope_theta)
        else:
            if positions is None:
                positions = jnp.arange(x.shape[1])[None, :]
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k = L.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def out_proj(p: Dict[str, jax.Array], attn: jax.Array) -> jax.Array:
    """attn: (B, S, H, Dh) -> (B, S, d)."""
    return jnp.einsum("bshk,hkd->bsd", attn, p["wo"].astype(attn.dtype))


# ---------------------------------------------------------------------------
# Blockwise full attention (training / prefill)
# ---------------------------------------------------------------------------

def _chunk_attend(q_chunk: jax.Array, k: jax.Array, v: jax.Array,
                  q_off: jax.Array, *, causal: bool, window: jax.Array,
                  kv_len: Optional[jax.Array] = None) -> jax.Array:
    """q_chunk: (B, Cq, KV, G, Dh); k/v: (B, Skv, KV, Dh). Returns (B,Cq,KV,G,Dh).

    ``window`` is a runtime scalar (<=0 -> global). ``kv_len`` optionally
    masks padded kv positions (cross-attention / ragged batches).
    """
    Dh = q_chunk.shape[-1]
    scale = Dh ** -0.5
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q_chunk, k).astype(jnp.float32)
    scores = scores * scale
    Skv = k.shape[1]
    kj = jnp.arange(Skv)
    mask = jnp.ones(scores.shape[-2:], dtype=bool)
    if causal:
        qi = q_off + jnp.arange(q_chunk.shape[1])
        cmask = kj[None, :] <= qi[:, None]
        wmask = jnp.where(window > 0, kj[None, :] > qi[:, None] - window, True)
        mask = cmask & wmask
    if kv_len is not None:
        mask = mask & (kj[None, :] < kv_len)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q_chunk.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", probs, v)


def _use_flash(q: jax.Array, k: jax.Array, v: jax.Array, cfg: ModelConfig,
               kv_len) -> bool:
    # the kernel holds one head dim for q, k and v
    if kv_len is not None or v.shape[-1] != q.shape[-1]:
        return False
    if cfg.attn_impl == "pallas":
        return True
    return (cfg.attn_impl == "auto" and flash.platform() == "tpu"
            and flash.tiles(q.shape[1], k.shape[1], q.shape[-1]))


def attend(q: jax.Array, k: jax.Array, v: jax.Array, cfg: ModelConfig, *,
           causal: bool = True, window=0,
           kv_len: Optional[jax.Array] = None) -> jax.Array:
    """Full attention. q: (B,S,H,Dh), k: (B,Skv,KV,Dh), v: (B,Skv,KV,Dv)."""
    if _use_flash(q, k, v, cfg, kv_len):
        out = flash.attention(q, k, v, causal=causal, window=window)
        if out is not None:
            return out
    with annotate_span("kernel.attention.xla_scan"):
        return _attend_xla(q, k, v, cfg, causal=causal, window=window,
                           kv_len=kv_len)


def _attend_xla(q, k, v, cfg: ModelConfig, *, causal, window, kv_len):
    """The q-chunked XLA path."""
    B, S, H, Dh = q.shape
    KV, Dv = k.shape[2], v.shape[-1]
    G = H // KV
    window = jnp.asarray(window, jnp.int32)
    qg = q.reshape(B, S, KV, G, Dh)

    C = min(cfg.attn_chunk, S)
    if S % C != 0:  # smoke-test shapes; fall back to one chunk
        C = S
    n = S // C
    if n == 1:
        out = _chunk_attend(qg, k, v, jnp.asarray(0), causal=causal,
                            window=window, kv_len=kv_len)
        return out.reshape(B, S, H, Dv)

    qcs = qg.reshape(B, n, C, KV, G, Dh).transpose(1, 0, 2, 3, 4, 5)
    offs = jnp.arange(n) * C

    def body(_, xs):
        qc, off = xs
        return None, _chunk_attend(qc, k, v, off, causal=causal,
                                   window=window, kv_len=kv_len)

    if n > 2:
        # the backward pass would hold every chunk's scores, n times the
        # (B, KV, G, C, S) block the chunking bounds: make each chunk's
        # again there instead. Up to two chunks, holding them costs at
        # most twice a block and saves a pass over the scores.
        body = jax.checkpoint(body, prevent_cse=False)
    _, outs = jax.lax.scan(body, None, (qcs, offs))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, KV, G, Dv)
    return out.reshape(B, S, H, Dv)


# ---------------------------------------------------------------------------
# Decode (one new token against a KV cache)
# ---------------------------------------------------------------------------

def attend_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                  pos: jax.Array, *, window=0, impl: str = "xla") -> jax.Array:
    """q: (B,1,H,Dh); caches: (B,Smax,KV,Dh); pos: (B,) current index.

    Attends over cache[0..pos] (inclusive: the new token is already written).
    """
    if impl == "pallas":
        from repro.kernels.decode_attention.ops import decode_attend
        return decode_attend(q, k_cache, v_cache, pos + 1, window=window)
    B, _, H, Dh = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    window = jnp.asarray(window, jnp.int32)
    qg = q.reshape(B, KV, G, Dh)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache).astype(jnp.float32)
    scores = scores * (Dh ** -0.5)
    kj = jnp.arange(k_cache.shape[1])
    mask = kj[None, :] <= pos[:, None]
    mask = mask & jnp.where(window > 0, kj[None, :] > pos[:, None] - window, True)
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v_cache)
    return out.reshape(B, 1, H, Dh)


def update_cache(k_cache: jax.Array, v_cache: jax.Array, k_new: jax.Array,
                 v_new: jax.Array, pos: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
    """Write (B,1,KV,Dh) new entries at per-row positions (B,)."""
    B = k_cache.shape[0]
    rows = jnp.arange(B)
    k_cache = k_cache.at[rows, pos].set(k_new[:, 0].astype(k_cache.dtype))
    v_cache = v_cache.at[rows, pos].set(v_new[:, 0].astype(v_cache.dtype))
    return k_cache, v_cache


# ---------------------------------------------------------------------------
# Paged decode (vLLM-style): KV lives in a shared physical page pool
# ---------------------------------------------------------------------------

def gather_pages(pages: jax.Array, page_table: jax.Array) -> jax.Array:
    """pages: (P, ps, ...); page_table: (B, Lp) logical->physical map.
    Returns the contiguous logical row views (B, Lp*ps, ...): position j
    of row b lives in physical page ``page_table[b, j // ps]`` at offset
    ``j % ps``. Out-of-range table entries gather arbitrary (but finite)
    pages — callers mask by ``pos`` exactly like the dense path, so
    garbage beyond the written prefix never reaches the softmax."""
    B, Lp = page_table.shape
    ps = pages.shape[1]
    view = pages[page_table]                     # (B, Lp, ps, ...)
    return view.reshape((B, Lp * ps) + pages.shape[2:])


def attend_decode_paged(q: jax.Array, k_pages: jax.Array,
                        v_pages: jax.Array, page_table: jax.Array,
                        pos: jax.Array, *, window=0,
                        impl: str = "xla") -> jax.Array:
    """Page-table-indexed decode attention. q: (B,1,H,Dh); pools:
    (P, ps, KV, Dh); page_table: (B, Lp); pos: (B,) current index.

    The page table is the ONLY indirection: after the gather the logical
    row view is exactly the dense cache row (padded to Lp*ps with masked
    positions that underflow to 0 in the softmax), so parity with
    :func:`attend_decode` is structural, not numerical luck.
    """
    k = gather_pages(k_pages, page_table)
    v = gather_pages(v_pages, page_table)
    return attend_decode(q, k, v, pos, window=window, impl=impl)


def update_cache_paged(k_pages: jax.Array, v_pages: jax.Array,
                       k_new: jax.Array, v_new: jax.Array,
                       page_table: jax.Array, pos: jax.Array,
                       write_mask: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """Scatter (B,1,KV,Dh) new entries into the page pool at per-row
    positions (B,). Rows with ``write_mask`` False are redirected to an
    out-of-bounds physical page and dropped by the scatter — essential
    in the paged layout, where a stale page-table row may point at pages
    that now belong to ANOTHER request (the dense layout's idle-row
    writes were merely wasted; here they would corrupt a neighbour)."""
    P, ps = k_pages.shape[0], k_pages.shape[1]
    B = page_table.shape[0]
    Lp = page_table.shape[1]
    logical = jnp.clip(pos // ps, 0, Lp - 1)
    phys = page_table[jnp.arange(B), logical]            # (B,)
    if write_mask is not None:
        phys = jnp.where(write_mask, phys, P)            # P = OOB -> drop
    off = pos % ps
    k_pages = k_pages.at[phys, off].set(k_new[:, 0].astype(k_pages.dtype),
                                        mode="drop")
    v_pages = v_pages.at[phys, off].set(v_new[:, 0].astype(v_pages.dtype),
                                        mode="drop")
    return k_pages, v_pages

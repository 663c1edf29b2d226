"""Model stacks for every assigned family, with scan-over-layers.

Families
--------
- ``dense``  : decoder-only (GQA/MQA/MHA), optional gemma3-style 5:1
               local:global sliding-window pattern (per-layer runtime window).
- ``moe``    : decoder-only with MoE FFN; supports moonshot's dense first
               layer(s) and arctic's parallel dense-residual branch.
- ``hybrid`` : zamba2 — Mamba2 backbone with a *weight-tied shared* attention
               block invoked every ``shared_attn_every`` layers.
- ``ssm``    : rwkv6 — attention-free time-mix / channel-mix.
- ``encdec`` : seamless — bidirectional encoder + causal decoder with
               cross-attention (modality frontend is a stub upstream).
- ``vlm``    : qwen2-vl — dense decoder fed a precomputed patch-embedding
               prefix, positions via M-RoPE (t, h, w).

All stacks use ``jax.lax.scan`` over *stacked* layer parameters so the HLO
contains one layer body regardless of depth — essential for compile time at
512 devices — with per-layer heterogeneity (gemma3 windows, zamba2 shared
block cadence) expressed as scanned runtime scalars or nested scans.

Public entry points (used by train/serve/dryrun):
    init_params(cfg, key)                      -> Boxed pytree
    forward(params, cfg, batch)                -> logits (train / prefill)
    init_decode_cache(cfg, batch, max_len)     -> cache pytree
    decode_step(params, cfg, cache, batch)     -> (logits, new cache)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import attention as A
from repro.models import ffn as F
from repro.models import layers as L
from repro.models import rwkv as R
from repro.models import ssm as M
from repro.obs.profiling import region
from repro.sharding import shard_act

PyTree = Any


# ---------------------------------------------------------------------------
# Stacking helpers
# ---------------------------------------------------------------------------

def stack_layers(init_fn, n: int, kg: L.KeyGen) -> PyTree:
    """Initialize ``n`` layers and stack leaves along a leading 'layers' axis."""
    trees = [init_fn(kg) for _ in range(n)]
    def _stack(*boxes: L.Boxed) -> L.Boxed:
        v = jnp.stack([b.value for b in boxes])
        return L.Boxed(v, ("layers",) + boxes[0].axes, boxes[0].cast)
    return jax.tree.map(_stack, *trees, is_leaf=L.is_boxed)


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _window_schedule(cfg: ModelConfig, n_layers: int) -> jnp.ndarray:
    """Per-layer sliding window (<=0 means global attention)."""
    wins = [0 if cfg.is_global_layer(i) else cfg.sliding_window
            for i in range(n_layers)]
    return jnp.asarray(wins, jnp.int32)


# ---------------------------------------------------------------------------
# Per-layer blocks (operate on raw/unboxed param dicts)
# ---------------------------------------------------------------------------

def _attn_block(lp, x, cfg: ModelConfig, *, window, positions,
                mrope_positions=None, causal=True):
    with region("attn"):
        h = L.rms_norm(x, lp["ln1"]["gamma"], cfg.norm_eps)
        q, k, v = A.project_qkv(lp["attn"], h, cfg, positions=positions,
                                mrope_positions=mrope_positions)
        att = A.attend(q, k, v, cfg, causal=causal, window=window)
        att = shard_act(att, ("batch", None, "heads", None))
        return x + A.out_proj(lp["attn"], att)


def _mlp_block(lp, x, cfg: ModelConfig):
    with region("mlp"):
        h = L.rms_norm(x, lp["ln2"]["gamma"], cfg.norm_eps)
        h = shard_act(h, ("batch", None, None))
        return x + F.apply_mlp(lp["mlp"], h)


def _moe_block(lp, x, cfg: ModelConfig):
    with region("mlp"):
        h = L.rms_norm(x, lp["ln2"]["gamma"], cfg.norm_eps)
        out, aux = F.apply_moe(lp["moe"], h, cfg)
        out = shard_act(out, ("batch", None, None))
        return x + out, aux


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_dense_layer(kg: L.KeyGen, cfg: ModelConfig) -> Dict[str, PyTree]:
    return {
        "ln1": L.init_rms(kg, cfg.d_model),
        "attn": A.init_attention(kg, cfg),
        "ln2": L.init_rms(kg, cfg.d_model),
        "mlp": F.init_mlp(kg, cfg.d_model, cfg.d_ff, cfg.gated_mlp),
    }


def _init_moe_layer(kg: L.KeyGen, cfg: ModelConfig) -> Dict[str, PyTree]:
    return {
        "ln1": L.init_rms(kg, cfg.d_model),
        "attn": A.init_attention(kg, cfg),
        "ln2": L.init_rms(kg, cfg.d_model),
        "moe": F.init_moe(kg, cfg),
    }


def _init_moe_dense_layer(kg: L.KeyGen, cfg: ModelConfig) -> Dict[str, PyTree]:
    """moonshot: first layer(s) use a plain dense MLP of width dense_ff."""
    return {
        "ln1": L.init_rms(kg, cfg.d_model),
        "attn": A.init_attention(kg, cfg),
        "ln2": L.init_rms(kg, cfg.d_model),
        "mlp": F.init_mlp(kg, cfg.d_model, cfg.dense_ff, True),
    }


def _init_mamba_layer(kg: L.KeyGen, cfg: ModelConfig) -> Dict[str, PyTree]:
    return {
        "ln": L.init_rms(kg, cfg.d_model),
        "mamba": M.init_mamba2(kg, cfg),
    }


def _init_rwkv_layer(kg: L.KeyGen, cfg: ModelConfig) -> Dict[str, PyTree]:
    return {
        "ln1": L.init_rms(kg, cfg.d_model),
        "tmix": R.init_rwkv_tmix(kg, cfg),
        "ln2": L.init_rms(kg, cfg.d_model),
        "cmix": R.init_rwkv_cmix(kg, cfg),
    }


def _init_cross_layer(kg: L.KeyGen, cfg: ModelConfig) -> Dict[str, PyTree]:
    return {
        "ln1": L.init_rms(kg, cfg.d_model),
        "attn": A.init_attention(kg, cfg),
        "lnx": L.init_rms(kg, cfg.d_model),
        "xattn": A.init_attention(kg, cfg),
        "ln2": L.init_rms(kg, cfg.d_model),
        "mlp": F.init_mlp(kg, cfg.d_model, cfg.d_ff, cfg.gated_mlp),
    }


def init_params(cfg: ModelConfig, key: jax.Array) -> PyTree:
    kg = L.KeyGen(key)
    p: Dict[str, PyTree] = {
        "embed": L.init_embed(kg, cfg.vocab_size, cfg.d_model, cfg.tie_embeddings),
        "final_norm": L.init_rms(kg, cfg.d_model),
    }
    fam = cfg.family
    if fam in ("dense", "vlm"):
        p["layers"] = stack_layers(lambda k: _init_dense_layer(k, cfg),
                                   cfg.num_layers, kg)
    elif fam == "moe":
        nd = cfg.first_dense_layers
        if nd:
            p["dense_layers"] = stack_layers(
                lambda k: _init_moe_dense_layer(k, cfg), nd, kg)
        p["layers"] = stack_layers(lambda k: _init_moe_layer(k, cfg),
                                   cfg.num_layers - nd, kg)
    elif fam == "hybrid":
        cad = cfg.shared_attn_every
        n_blocks, leftover = divmod(cfg.num_layers, cad)
        blocks = [stack_layers(lambda k: _init_mamba_layer(k, cfg), cad, kg)
                  for _ in range(n_blocks)]
        p["blocks"] = jax.tree.map(
            lambda *bs: L.Boxed(jnp.stack([b.value for b in bs]),
                                ("blocks",) + bs[0].axes, bs[0].cast),
            *blocks, is_leaf=L.is_boxed)
        if leftover:
            p["tail"] = stack_layers(lambda k: _init_mamba_layer(k, cfg),
                                     leftover, kg)
        p["shared"] = {                       # ONE weight-tied attn+mlp block
            "ln1": L.init_rms(kg, cfg.d_model),
            "attn": A.init_attention(kg, cfg),
            "ln2": L.init_rms(kg, cfg.d_model),
            "mlp": F.init_mlp(kg, cfg.d_model, cfg.d_ff, cfg.gated_mlp),
        }
    elif fam == "ssm":
        p["layers"] = stack_layers(lambda k: _init_rwkv_layer(k, cfg),
                                   cfg.num_layers, kg)
    elif fam == "encdec":
        p["enc_layers"] = stack_layers(lambda k: _init_dense_layer(k, cfg),
                                       cfg.enc_layers, kg)
        p["enc_norm"] = L.init_rms(kg, cfg.d_model)
        p["layers"] = stack_layers(lambda k: _init_cross_layer(k, cfg),
                                   cfg.dec_layers, kg)
    else:
        raise ValueError(f"unknown family {fam}")
    return p


def num_shared_invocations(cfg: ModelConfig) -> int:
    """How many times zamba2's shared attn block runs per forward."""
    return cfg.num_layers // cfg.shared_attn_every


# ---------------------------------------------------------------------------
# Forward (train / prefill): params are RAW (unboxed) dicts
# ---------------------------------------------------------------------------

def _scan(body, x, xs, cfg: ModelConfig, remat: bool = True):
    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    with region("layers"):
        return jax.lax.scan(body, x, xs)


def _dense_trunk(params, cfg: ModelConfig, x, positions, mrope_positions=None,
                 causal=True, remat=True):
    n = params["layers"]["ln1"]["gamma"].shape[0]
    windows = _window_schedule(cfg, n)

    def body(h, xs):
        lp, win = xs
        h = _attn_block(lp, h, cfg, window=win, positions=positions,
                        mrope_positions=mrope_positions, causal=causal)
        h = _mlp_block(lp, h, cfg)
        return h, None

    x, _ = _scan(body, x, (params["layers"], windows), cfg, remat)
    return x


def _moe_trunk(params, cfg: ModelConfig, x, positions, remat=True):
    aux_total = jnp.zeros((), jnp.float32)
    if "dense_layers" in params:
        def dbody(h, lp):
            h = _attn_block(lp, h, cfg, window=jnp.int32(0), positions=positions)
            h = _mlp_block(lp, h, cfg)
            return h, None
        x, _ = _scan(dbody, x, params["dense_layers"], cfg, remat)

    def body(carry, lp):
        h, aux = carry
        h = _attn_block(lp, h, cfg, window=jnp.int32(0), positions=positions)
        h, a = _moe_block(lp, h, cfg)
        return (h, aux + a), None

    (x, aux_total), _ = _scan(body, (x, aux_total), params["layers"], cfg, remat)
    return x, aux_total


def _shared_block(sp, x, cfg: ModelConfig, positions):
    x = _attn_block(sp, x, cfg, window=jnp.int32(0), positions=positions)
    x = _mlp_block(sp, x, cfg)
    return x


def _hybrid_trunk(params, cfg: ModelConfig, x, positions, remat=True):
    sp = params["shared"]

    def mamba_body(h, lp):
        hn = L.rms_norm(h, lp["ln"]["gamma"], cfg.norm_eps)
        return h + M.apply_mamba2(lp["mamba"], hn, cfg), None

    def block_body(h, bp):
        h, _ = jax.lax.scan(mamba_body, h, bp)
        h = _shared_block(sp, h, cfg, positions)
        return h, None

    body = jax.checkpoint(block_body, prevent_cse=False) if remat else block_body
    with region("layers"):
        x, _ = jax.lax.scan(body, x, params["blocks"])
    if "tail" in params:
        x, _ = _scan(mamba_body, x, params["tail"], cfg, remat)
    return x


def _rwkv_trunk(params, cfg: ModelConfig, x, remat=True):
    B = x.shape[0]
    H, Dh = cfg.d_model // cfg.rwkv_head_dim, cfg.rwkv_head_dim

    def body(h, lp):
        zeros_tok = jnp.zeros((B, 1, cfg.d_model), h.dtype)
        state0 = jnp.zeros((B, H, Dh, Dh), jnp.float32)
        hn = L.rms_norm(h, lp["ln1"]["gamma"], cfg.norm_eps)
        out, _, _ = R.apply_tmix(lp["tmix"], hn, cfg, zeros_tok, state0)
        h = h + out
        hn = L.rms_norm(h, lp["ln2"]["gamma"], cfg.norm_eps)
        out, _ = R.apply_cmix(lp["cmix"], hn, cfg, zeros_tok)
        return h + out, None

    x, _ = _scan(body, x, params["layers"], cfg, remat)
    return x


def _encdec_trunk(params, cfg: ModelConfig, enc_x, dec_x, positions, remat=True):
    # encoder: bidirectional
    enc = _dense_trunk({"layers": params["enc_layers"]}, cfg, enc_x,
                       positions=None, causal=False, remat=remat)
    enc = L.rms_norm(enc, params["enc_norm"]["gamma"], cfg.norm_eps)

    def body(h, lp):
        h = _attn_block(lp, h, cfg, window=jnp.int32(0), positions=positions)
        # cross attention (no rope on cross projections)
        hn = L.rms_norm(h, lp["lnx"]["gamma"], cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", hn, lp["xattn"]["wq"].astype(hn.dtype))
        k = jnp.einsum("bsd,dhk->bshk", enc, lp["xattn"]["wk"].astype(hn.dtype))
        v = jnp.einsum("bsd,dhk->bshk", enc, lp["xattn"]["wv"].astype(hn.dtype))
        att = A.attend(q, k, v, cfg, causal=False)
        h = h + A.out_proj(lp["xattn"], att)
        h = _mlp_block(lp, h, cfg)
        return h, None

    x, _ = _scan(body, dec_x, params["layers"], cfg, remat)
    return x


def forward(params: PyTree, cfg: ModelConfig, batch: Dict[str, jax.Array],
            remat: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward. Returns (logits, moe_aux_loss).

    ``batch`` keys by family:
      dense/moe/ssm : tokens (B, S)
      vlm           : tokens (B, S_txt), patch_embeds (B, S_img, d),
                      mrope_positions (B, S, 3)
      encdec        : frame_embeds (B, S_enc, d), tokens (B, S_dec)
      hybrid        : tokens (B, S)
    """
    dt = _dtype(cfg)
    aux = jnp.zeros((), jnp.float32)
    fam = cfg.family

    if fam == "encdec":
        enc_x = batch["frame_embeds"].astype(dt)
        with region("embed"):
            dec_x = L.embed(params["embed"], batch["tokens"], dt)
        dec_x = shard_act(dec_x, ("batch", None, None))
        pos = jnp.arange(dec_x.shape[1])[None, :]
        x = _encdec_trunk(params, cfg, enc_x, dec_x, pos, remat=remat)
    else:
        if fam == "vlm":
            with region("embed"):
                tok_x = L.embed(params["embed"], batch["tokens"], dt)
            x = jnp.concatenate([batch["patch_embeds"].astype(dt), tok_x], axis=1)
            mrope_pos = batch["mrope_positions"]
            pos = None
        else:
            with region("embed"):
                x = L.embed(params["embed"], batch["tokens"], dt)
            mrope_pos = None
            pos = jnp.arange(x.shape[1])[None, :]
        x = shard_act(x, ("batch", None, None))
        if fam in ("dense", "vlm"):
            x = _dense_trunk(params, cfg, x, pos, mrope_positions=mrope_pos,
                             remat=remat)
        elif fam == "moe":
            x, aux = _moe_trunk(params, cfg, x, pos, remat=remat)
        elif fam == "hybrid":
            x = _hybrid_trunk(params, cfg, x, pos, remat=remat)
        elif fam == "ssm":
            x = _rwkv_trunk(params, cfg, x, remat=remat)
        else:
            raise ValueError(fam)

    with region("head"):
        x = L.rms_norm(x, params["final_norm"]["gamma"], cfg.norm_eps)
        logits = L.unembed(params["embed"], x, cfg.tie_embeddings)
        logits = shard_act(logits, ("batch", None, "vocab"))
    return logits, aux


# ---------------------------------------------------------------------------
# Decode: one new token against per-layer caches
# ---------------------------------------------------------------------------

def init_decode_cache(cfg: ModelConfig, batch: int, max_len: int,
                      enc_len: int = 0) -> PyTree:
    """Cache pytree for ``decode_step``. Family-dependent layout; every
    leaf's leading axis is the stacked layer dimension so decode scans it."""
    dt = _dtype(cfg)
    fam = cfg.family
    KV, Dh = cfg.num_kv_heads, cfg.head_dim

    def kv(nl):
        return {
            "k": jnp.zeros((nl, batch, max_len, KV, Dh), dt),
            "v": jnp.zeros((nl, batch, max_len, KV, Dh), dt),
        }

    if fam in ("dense", "vlm"):
        return {"kv": kv(cfg.num_layers), "pos": jnp.zeros((batch,), jnp.int32)}
    if fam == "moe":
        c = {"kv": kv(cfg.num_layers - cfg.first_dense_layers),
             "pos": jnp.zeros((batch,), jnp.int32)}
        if cfg.first_dense_layers:
            c["kv_dense"] = kv(cfg.first_dense_layers)
        return c
    if fam == "hybrid":
        cad = cfg.shared_attn_every
        n_blocks, leftover = divmod(cfg.num_layers, cad)
        d_in, H, P, N = cfg.ssm_d_inner, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        conv_dim = d_in + 2 * N
        c = {
            "blocks": {
                "state": jnp.zeros((n_blocks, cad, batch, H, N, P), jnp.float32),
                "conv": jnp.zeros((n_blocks, cad, batch, M.CONV_W - 1, conv_dim), dt),
            },
            "shared_kv": kv(n_blocks),
            "pos": jnp.zeros((batch,), jnp.int32),
        }
        if leftover:
            c["tail"] = {
                "state": jnp.zeros((leftover, batch, H, N, P), jnp.float32),
                "conv": jnp.zeros((leftover, batch, M.CONV_W - 1, conv_dim), dt),
            }
        return c
    if fam == "ssm":
        H, Dh2 = cfg.d_model // cfg.rwkv_head_dim, cfg.rwkv_head_dim
        nl = cfg.num_layers
        return {
            "wkv": jnp.zeros((nl, batch, H, Dh2, Dh2), jnp.float32),
            "tok_t": jnp.zeros((nl, batch, 1, cfg.d_model), dt),
            "tok_c": jnp.zeros((nl, batch, 1, cfg.d_model), dt),
            "pos": jnp.zeros((batch,), jnp.int32),
        }
    if fam == "encdec":
        return {
            "kv": kv(cfg.dec_layers),
            "xk": jnp.zeros((cfg.dec_layers, batch, enc_len, KV, Dh), dt),
            "xv": jnp.zeros((cfg.dec_layers, batch, enc_len, KV, Dh), dt),
            "pos": jnp.zeros((batch,), jnp.int32),
        }
    raise ValueError(fam)


def init_paged_decode_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                            page_size: int, num_pages: int,
                            enc_len: int = 0) -> PyTree:
    """Paged cache pytree for ``decode_step_paged``: every length-bearing
    KV leaf becomes a physical page pool ``(layers, num_pages, page_size,
    KV, Dh)`` shared by all rows, indexed through a per-row
    ``page_table`` leaf ``(batch, ceil(max_len/page_size))``. Recurrent
    per-row state (SSM/RWKV/Mamba conv+state) carries no length axis and
    stays dense — paging governs only what grows with tokens."""
    dt = _dtype(cfg)
    fam = cfg.family
    KV, Dh = cfg.num_kv_heads, cfg.head_dim
    pages_per_row = -(-max_len // page_size)

    def kv_pool(nl):
        return {
            "k": jnp.zeros((nl, num_pages, page_size, KV, Dh), dt),
            "v": jnp.zeros((nl, num_pages, page_size, KV, Dh), dt),
        }

    table = jnp.zeros((batch, pages_per_row), jnp.int32)
    pos = jnp.zeros((batch,), jnp.int32)
    if fam in ("dense", "vlm"):
        return {"kv": kv_pool(cfg.num_layers), "page_table": table,
                "pos": pos}
    if fam == "moe":
        c = {"kv": kv_pool(cfg.num_layers - cfg.first_dense_layers),
             "page_table": table, "pos": pos}
        if cfg.first_dense_layers:
            c["kv_dense"] = kv_pool(cfg.first_dense_layers)
        return c
    if fam == "hybrid":
        c = init_decode_cache(cfg, batch, max_len, enc_len=enc_len)
        n_blocks = cfg.num_layers // cfg.shared_attn_every
        c["shared_kv"] = kv_pool(n_blocks)
        c["page_table"] = table
        return c
    if fam == "ssm":
        # attention-free: no KV grows with tokens; the paged cache is the
        # dense cache plus a page table so the engine's page accounting
        # (admission budget, shipping) stays uniform across families
        c = init_decode_cache(cfg, batch, max_len, enc_len=enc_len)
        c["page_table"] = table
        return c
    raise NotImplementedError(
        f"paged decode cache not supported for family {fam!r} "
        "(encdec cross-attention caches are fixed-length; use dense)")


def _decode_attn_layer_paged(lp, x, cfg, kp, vp, table, pos, window, wmask):
    h = L.rms_norm(x, lp["ln1"]["gamma"], cfg.norm_eps)
    q, k, v = A.project_qkv(lp["attn"], h, cfg, positions=pos[:, None])
    kp, vp = A.update_cache_paged(kp, vp, k, v, table, pos, wmask)
    att = A.attend_decode_paged(q, kp, vp, table, pos, window=window,
                                impl=cfg.attn_impl)
    x = x + A.out_proj(lp["attn"], att)
    return x, kp, vp


def decode_step_paged(params: PyTree, cfg: ModelConfig, cache: PyTree,
                      batch: Dict[str, jax.Array],
                      advance: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, PyTree]:
    """One-token decode against the paged cache. Same contract as
    :func:`decode_step`, plus ``advance``: a (B,) bool mask of rows that
    consume this token. Non-advancing rows have their KV writes DROPPED
    (their page-table rows may reference pages now owned by another
    request — a write there would corrupt a neighbour, where the dense
    layout's idle-row writes were merely wasted) and their ``pos``
    frozen. Recurrent per-row leaves still compute for masked rows; the
    paged prefill wrapper selects them back, and the engine resets rows
    at admission, exactly like the dense path."""
    dt = _dtype(cfg)
    fam = cfg.family
    pos = cache["pos"]
    adv = jnp.ones(pos.shape, bool) if advance is None \
        else jnp.asarray(advance)
    if fam == "ssm":
        # no paged leaves: the dense cell already is the paged cell
        logits, new_cache = decode_step(params, cfg, cache, batch)
        new_cache["pos"] = jnp.where(adv, pos + 1, pos)
        return logits, new_cache
    table = cache["page_table"]
    x = L.embed(params["embed"], batch["tokens"], dt)
    x = shard_act(x, ("batch", None, None))
    new_cache = dict(cache)

    if fam in ("dense", "vlm"):
        windows = _window_schedule(cfg, cfg.num_layers)

        def body(h, xs):
            lp, kp, vp, win = xs
            h, kp, vp = _decode_attn_layer_paged(lp, h, cfg, kp, vp,
                                                 table, pos, win, adv)
            h = _mlp_block(lp, h, cfg)
            return h, (kp, vp)

        x, (ks, vs) = jax.lax.scan(
            body, x, (params["layers"], cache["kv"]["k"],
                      cache["kv"]["v"], windows))
        new_cache["kv"] = {"k": ks, "v": vs}

    elif fam == "moe":
        if cfg.first_dense_layers:
            def dbody(h, xs):
                lp, kp, vp = xs
                h, kp, vp = _decode_attn_layer_paged(
                    lp, h, cfg, kp, vp, table, pos, jnp.int32(0), adv)
                h = _mlp_block(lp, h, cfg)
                return h, (kp, vp)
            x, (ks, vs) = jax.lax.scan(
                dbody, x, (params["dense_layers"],
                           cache["kv_dense"]["k"], cache["kv_dense"]["v"]))
            new_cache["kv_dense"] = {"k": ks, "v": vs}

        def body(h, xs):
            lp, kp, vp = xs
            h, kp, vp = _decode_attn_layer_paged(
                lp, h, cfg, kp, vp, table, pos, jnp.int32(0), adv)
            h2, _ = _moe_block(lp, h, cfg)
            return h2, (kp, vp)

        x, (ks, vs) = jax.lax.scan(
            body, x, (params["layers"], cache["kv"]["k"],
                      cache["kv"]["v"]))
        new_cache["kv"] = {"k": ks, "v": vs}

    elif fam == "hybrid":
        sp = params["shared"]

        def mamba_body(h, xs):
            lp, st, cv = xs
            hn = L.rms_norm(h, lp["ln"]["gamma"], cfg.norm_eps)
            out, nc = M.decode_mamba2(lp["mamba"], hn,
                                      {"state": st, "conv": cv}, cfg)
            return h + out, (nc["state"], nc["conv"])

        def block_body(h, xs):
            bp, st, cv, kp, vp = xs
            h, (st, cv) = jax.lax.scan(mamba_body, h, (bp, st, cv))
            hn = L.rms_norm(h, sp["ln1"]["gamma"], cfg.norm_eps)
            q, k, v = A.project_qkv(sp["attn"], hn, cfg,
                                    positions=pos[:, None])
            kp, vp = A.update_cache_paged(kp, vp, k, v, table, pos, adv)
            att = A.attend_decode_paged(q, kp, vp, table, pos,
                                        impl=cfg.attn_impl)
            h = h + A.out_proj(sp["attn"], att)
            h = _mlp_block(sp, h, cfg)
            return h, (st, cv, kp, vp)

        x, (sts, cvs, ks, vs) = jax.lax.scan(
            block_body, x,
            (params["blocks"], cache["blocks"]["state"],
             cache["blocks"]["conv"], cache["shared_kv"]["k"],
             cache["shared_kv"]["v"]))
        new_cache["blocks"] = {"state": sts, "conv": cvs}
        new_cache["shared_kv"] = {"k": ks, "v": vs}
        if "tail" in cache:
            x, (sts, cvs) = jax.lax.scan(
                mamba_body, x,
                (params["tail"], cache["tail"]["state"],
                 cache["tail"]["conv"]))
            new_cache["tail"] = {"state": sts, "conv": cvs}
    else:
        raise NotImplementedError(f"paged decode for family {fam!r}")

    x = L.rms_norm(x, params["final_norm"]["gamma"], cfg.norm_eps)
    logits = L.unembed(params["embed"], x, cfg.tie_embeddings)
    new_cache["pos"] = jnp.where(adv, pos + 1, pos)
    return logits, new_cache


def encode_for_decode(params, cfg: ModelConfig, frame_embeds: jax.Array,
                      cache: PyTree) -> PyTree:
    """encdec: run the encoder once, fill per-layer cross K/V caches."""
    dt = _dtype(cfg)
    enc = _dense_trunk({"layers": params["enc_layers"]}, cfg,
                       frame_embeds.astype(dt), positions=None, causal=False,
                       remat=False)
    enc = L.rms_norm(enc, params["enc_norm"]["gamma"], cfg.norm_eps)

    def proj(lp):
        k = jnp.einsum("bsd,dhk->bshk", enc, lp["xattn"]["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", enc, lp["xattn"]["wv"].astype(dt))
        return k, v

    def body(_, lp):
        return None, proj(lp)

    _, (xk, xv) = jax.lax.scan(body, None, params["layers"])
    return {**cache, "xk": xk, "xv": xv}


def _decode_attn_layer(lp, x, cfg, kc, vc, pos, window):
    h = L.rms_norm(x, lp["ln1"]["gamma"], cfg.norm_eps)
    q, k, v = A.project_qkv(lp["attn"], h, cfg, positions=pos[:, None])
    kc, vc = A.update_cache(kc, vc, k, v, pos)
    att = A.attend_decode(q, kc, vc, pos, window=window, impl=cfg.attn_impl)
    x = x + A.out_proj(lp["attn"], att)
    return x, kc, vc


def decode_step(params: PyTree, cfg: ModelConfig, cache: PyTree,
                batch: Dict[str, jax.Array]) -> Tuple[jax.Array, PyTree]:
    """One-token decode. batch = {tokens: (B, 1)} (+ mrope_positions for vlm).

    Returns (logits (B, 1, V), new cache). ``cache['pos']`` is the write
    index for this step (the number of tokens already in the cache).
    """
    dt = _dtype(cfg)
    fam = cfg.family
    pos = cache["pos"]
    x = L.embed(params["embed"], batch["tokens"], dt)
    x = shard_act(x, ("batch", None, None))
    new_cache = dict(cache)

    if fam in ("dense", "vlm"):
        n = cfg.num_layers
        windows = _window_schedule(cfg, n)

        def body(h, xs):
            lp, kc, vc, win = xs
            h, kc, vc = _decode_attn_layer(lp, h, cfg, kc, vc, pos, win)
            h = _mlp_block(lp, h, cfg)
            return h, (kc, vc)

        x, (ks, vs) = jax.lax.scan(
            body, x, (params["layers"], cache["kv"]["k"], cache["kv"]["v"],
                      windows))
        new_cache["kv"] = {"k": ks, "v": vs}

    elif fam == "moe":
        if cfg.first_dense_layers:
            def dbody(h, xs):
                lp, kc, vc = xs
                h, kc, vc = _decode_attn_layer(lp, h, cfg, kc, vc, pos,
                                               jnp.int32(0))
                h = _mlp_block(lp, h, cfg)
                return h, (kc, vc)
            x, (ks, vs) = jax.lax.scan(
                dbody, x, (params["dense_layers"], cache["kv_dense"]["k"],
                           cache["kv_dense"]["v"]))
            new_cache["kv_dense"] = {"k": ks, "v": vs}

        def body(h, xs):
            lp, kc, vc = xs
            h, kc, vc = _decode_attn_layer(lp, h, cfg, kc, vc, pos, jnp.int32(0))
            h2, _ = _moe_block(lp, h, cfg)
            return h2, (kc, vc)

        x, (ks, vs) = jax.lax.scan(
            body, x, (params["layers"], cache["kv"]["k"], cache["kv"]["v"]))
        new_cache["kv"] = {"k": ks, "v": vs}

    elif fam == "hybrid":
        sp = params["shared"]

        def mamba_body(h, xs):
            lp, st, cv = xs
            hn = L.rms_norm(h, lp["ln"]["gamma"], cfg.norm_eps)
            out, nc = M.decode_mamba2(lp["mamba"], hn, {"state": st, "conv": cv},
                                      cfg)
            return h + out, (nc["state"], nc["conv"])

        def block_body(h, xs):
            bp, st, cv, kc, vc = xs
            h, (st, cv) = jax.lax.scan(mamba_body, h, (bp, st, cv))
            hn = L.rms_norm(h, sp["ln1"]["gamma"], cfg.norm_eps)
            q, k, v = A.project_qkv(sp["attn"], hn, cfg, positions=pos[:, None])
            kc, vc = A.update_cache(kc, vc, k, v, pos)
            att = A.attend_decode(q, kc, vc, pos, impl=cfg.attn_impl)
            h = h + A.out_proj(sp["attn"], att)
            h = _mlp_block(sp, h, cfg)
            return h, (st, cv, kc, vc)

        x, (sts, cvs, ks, vs) = jax.lax.scan(
            block_body, x,
            (params["blocks"], cache["blocks"]["state"], cache["blocks"]["conv"],
             cache["shared_kv"]["k"], cache["shared_kv"]["v"]))
        new_cache["blocks"] = {"state": sts, "conv": cvs}
        new_cache["shared_kv"] = {"k": ks, "v": vs}
        if "tail" in cache:
            x, (sts, cvs) = jax.lax.scan(
                mamba_body, x,
                (params["tail"], cache["tail"]["state"], cache["tail"]["conv"]))
            new_cache["tail"] = {"state": sts, "conv": cvs}

    elif fam == "ssm":
        def body(h, xs):
            lp, wkv, tt, tc = xs
            st = {"wkv": wkv, "tok_t": tt, "tok_c": tc}
            hn = L.rms_norm(h, lp["ln1"]["gamma"], cfg.norm_eps)
            out, st = R.decode_tmix(lp["tmix"], hn, cfg, st)
            h = h + out
            hn = L.rms_norm(h, lp["ln2"]["gamma"], cfg.norm_eps)
            out, st = R.decode_cmix(lp["cmix"], hn, cfg, st)
            return h + out, (st["wkv"], st["tok_t"], st["tok_c"])

        x, (wkvs, tts, tcs) = jax.lax.scan(
            body, x, (params["layers"], cache["wkv"], cache["tok_t"],
                      cache["tok_c"]))
        new_cache.update({"wkv": wkvs, "tok_t": tts, "tok_c": tcs})

    elif fam == "encdec":
        def body(h, xs):
            lp, kc, vc, xk, xv = xs
            h, kc, vc = _decode_attn_layer(lp, h, cfg, kc, vc, pos, jnp.int32(0))
            hn = L.rms_norm(h, lp["lnx"]["gamma"], cfg.norm_eps)
            q = jnp.einsum("bsd,dhk->bshk", hn, lp["xattn"]["wq"].astype(dt))
            enc_len = jnp.full((h.shape[0],), xk.shape[1], jnp.int32)
            att = A.attend_decode(q, xk, xv, enc_len - 1, impl=cfg.attn_impl)
            h = h + A.out_proj(lp["xattn"], att)
            h = _mlp_block(lp, h, cfg)
            return h, (kc, vc)

        x, (ks, vs) = jax.lax.scan(
            body, x, (params["layers"], cache["kv"]["k"], cache["kv"]["v"],
                      cache["xk"], cache["xv"]))
        new_cache["kv"] = {"k": ks, "v": vs}
    else:
        raise ValueError(fam)

    x = L.rms_norm(x, params["final_norm"]["gamma"], cfg.norm_eps)
    logits = L.unembed(params["embed"], x, cfg.tie_embeddings)
    new_cache["pos"] = pos + 1
    return logits, new_cache

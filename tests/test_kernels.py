"""Pallas kernel sweeps: shapes x dtypes vs pure-jnp oracles (interpret
mode on CPU), per the per-kernel allclose requirement."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_ref)
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.rwkv6 import rwkv6_ref, rwkv6_scan
from repro.kernels.ssd_scan import ssd_ref, ssd_scan

RNG = np.random.default_rng(0)


def _arr(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape), dtype)


FLASH_CASES = [
    # B, H, KV, Sq, Sk, D, causal, window, blk_q, blk_k
    (2, 4, 4, 128, 128, 64, True, 0, 64, 64),
    (1, 8, 2, 256, 256, 64, True, 0, 128, 64),     # GQA
    (1, 4, 1, 128, 128, 32, True, 32, 32, 32),     # MQA + sliding window
    (2, 2, 2, 96, 96, 16, True, 0, 64, 64),        # ragged tails
    (1, 4, 4, 64, 64, 128, False, 0, 64, 64),      # bidirectional
    (1, 2, 2, 100, 100, 24, True, 16, 32, 64),     # ragged + window
]


FLASH_TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _flash_inputs(case, dtype, rng=RNG):
    B, H, KV, Sq, Sk, D = case[:6]
    draw = lambda shape: jnp.asarray(rng.normal(size=shape), dtype)
    return draw((B, Sq, H, D)), draw((B, Sk, KV, D)), draw((B, Sk, KV, D))


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(case, dtype):
    causal, win, bq, bk = case[6:]
    q, k, v = _flash_inputs(case, dtype)
    out = flash_attention(q, k, v, causal=causal, window=win,
                          blocks=(bq, bk), interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=win)
    tol = FLASH_TOL[dtype]
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32), atol=tol, rtol=tol)


def _grads(fn, q, k, v, do):
    """d/d(q, k, v) of sum(fn(q, k, v) * do), in f32."""
    loss = lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * do)
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_grad(case, dtype):
    """The custom-VJP backward (dK/dV and dQ kernels) against autodiff of
    the oracle, at the forward's tolerances."""
    causal, win, bq, bk = case[6:]
    rng = np.random.default_rng(FLASH_CASES.index(case))
    q, k, v = _flash_inputs(case, dtype, rng)
    do = jnp.asarray(rng.normal(size=q.shape), jnp.float32)
    got = _grads(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, window=win, blocks=(bq, bk),
        interpret=True), q, k, v, do)
    want = _grads(lambda q, k, v: attention_ref(
        q, k, v, causal=causal, window=win), q, k, v, do)
    tol = FLASH_TOL[dtype]
    for name, g, w in zip("qkv", got, want):
        assert g.dtype == dtype, name
        np.testing.assert_allclose(g.astype(jnp.float32),
                                   w.astype(jnp.float32), atol=tol,
                                   rtol=tol, err_msg=f"d{name}")


def test_flash_attention_traced_window():
    """gemma3 scans per-layer windows: the same jitted kernel must serve
    traced window values without retracing."""
    q = _arr((1, 64, 2, 32), jnp.float32)
    k = v = _arr((1, 64, 2, 32), jnp.float32)

    @jax.jit
    def f(win):
        return flash_attention(q, k, v, window=win, blocks=(32, 32),
                               interpret=True)
    for w in (0, 8, 32):
        np.testing.assert_allclose(
            f(jnp.int32(w)), attention_ref(q, k, v, window=w), atol=2e-5)


def test_flash_attention_grad_traced_window():
    """The backward takes the traced window too: one jitted gradient
    serves every window."""
    rng = np.random.default_rng(7)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 64, 4, 32)), jnp.float32)
               for _ in range(3))
    k, v = k[:, :, :2], v[:, :, :2]
    do = jnp.asarray(rng.normal(size=q.shape), jnp.float32)

    @jax.jit
    def f(win):
        return _grads(lambda q, k, v: flash_attention(
            q, k, v, window=win, blocks=(32, 32), interpret=True),
            q, k, v, do)
    for w in (0, 8, 32):
        want = _grads(lambda q, k, v: attention_ref(q, k, v, window=w),
                      q, k, v, do)
        for g, r in zip(f(jnp.int32(w)), want):
            np.testing.assert_allclose(g, r, atol=2e-5, rtol=2e-5)


DECODE_CASES = [
    (2, 8, 2, 512, 64, 128),
    (4, 4, 1, 1024, 128, 256),
    (1, 16, 16, 300, 32, 128),
    (3, 4, 4, 64, 16, 64),
]


@pytest.mark.parametrize("case", DECODE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(case, dtype):
    B, H, KV, S, D, bk = case
    q = _arr((B, H, D), dtype)
    k, v = _arr((B, KV, S, D), dtype), _arr((B, KV, S, D), dtype)
    lengths = jnp.asarray(RNG.integers(1, S + 1, size=(B,)), jnp.int32)
    out = decode_attention(q, k, v, lengths, blk_k=bk, interpret=True)
    ref = decode_attention_ref(q, k, v, lengths)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32), atol=tol, rtol=tol)


def test_decode_attention_window():
    B, H, KV, S, D = 2, 4, 2, 256, 32
    q, k, v = _arr((B, H, D), jnp.float32), _arr((B, KV, S, D), jnp.float32), \
        _arr((B, KV, S, D), jnp.float32)
    lengths = jnp.asarray([200, 77], jnp.int32)
    for w in (16, 64):
        out = decode_attention(q, k, v, lengths, window=w, blk_k=64,
                               interpret=True)
        ref = decode_attention_ref(q, k, v, lengths, window=w)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


SSD_CASES = [
    (2, 4, 64, 16, 16, 16),
    (1, 8, 256, 64, 64, 64),
    (2, 2, 128, 32, 16, 128),    # single chunk
    (1, 1, 32, 8, 8, 8),
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_scan(case):
    B, H, S, P, N, Q = case
    xdt = _arr((B, H, S, P), jnp.float32)
    Bc, Cc = _arr((B, S, N), jnp.float32), _arr((B, S, N), jnp.float32)
    dA = -jnp.asarray(RNG.uniform(0.01, 0.5, size=(B, H, S)), jnp.float32)
    out = ssd_scan(xdt, Bc, Cc, dA, chunk=Q, interpret=True)
    ref = ssd_ref(xdt, Bc, Cc, dA)
    scale = float(jnp.max(jnp.abs(ref)))
    np.testing.assert_allclose(out, ref, atol=2e-5 * max(1, scale))


RWKV_CASES = [
    (2, 4, 64, 16, 16),
    (1, 2, 128, 64, 32),
    (2, 1, 96, 32, 32),
    (1, 8, 64, 64, 64),          # single chunk
]


@pytest.mark.parametrize("case", RWKV_CASES)
def test_rwkv6_scan(case):
    B, H, S, D, L = case
    r, k, v = (_arr((B, H, S, D), jnp.float32) for _ in range(3))
    # include pathologically fast decays — the log-space chunking must hold
    w = jnp.asarray(np.exp(-np.exp(RNG.uniform(-8, 4, size=(B, H, S, D)))),
                    jnp.float32)
    u = _arr((H, D), jnp.float32)
    out, st = rwkv6_scan(r, k, v, w, u, chunk=L, interpret=True)
    ref, st_ref = rwkv6_ref(r, k, v, w, u)
    scale = float(jnp.max(jnp.abs(ref)))
    np.testing.assert_allclose(out, ref, atol=2e-5 * max(1, scale))
    np.testing.assert_allclose(st, st_ref, atol=2e-5 * max(
        1, float(jnp.max(jnp.abs(st_ref)))))


def test_rwkv6_initial_state_continuity():
    """Running [0:S] in one call == running [0:S/2] then [S/2:S] with the
    carried state — the chunked kernel's state handoff is exact."""
    B, H, S, D = 1, 2, 64, 16
    r, k, v = (_arr((B, H, S, D), jnp.float32) for _ in range(3))
    w = jnp.asarray(np.exp(-np.exp(RNG.uniform(-4, 1, size=(B, H, S, D)))),
                    jnp.float32)
    u = _arr((H, D), jnp.float32)
    o_full, s_full = rwkv6_scan(r, k, v, w, u, chunk=16, interpret=True)
    h = S // 2
    o1, s1 = rwkv6_scan(r[:, :, :h], k[:, :, :h], v[:, :, :h], w[:, :, :h],
                        u, chunk=16, interpret=True)
    o2, s2 = rwkv6_scan(r[:, :, h:], k[:, :, h:], v[:, :, h:], w[:, :, h:],
                        u, s1, chunk=16, interpret=True)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], axis=2), o_full,
                               atol=1e-4)
    np.testing.assert_allclose(s2, s_full, atol=1e-4)


def test_model_xla_vs_pallas_forward():
    """End-to-end: reduced models produce the same logits on both impls."""
    from repro.config import get_config
    from repro.data.pipeline import make_batch
    from repro.models import layers as ML
    from repro.models.builder import build_model

    for arch, impls in [
        ("qwen2.5-14b", {"attn_impl": "pallas"}),
        ("gemma3-27b", {"attn_impl": "pallas"}),
        ("zamba2-1.2b", {"ssm_impl": "pallas"}),
        ("rwkv6-7b", {"rwkv_impl": "pallas"}),
    ]:
        cfg_x = get_config(arch, reduced=True).replace(dtype="float32")
        cfg_p = cfg_x.replace(**impls)
        mx, mp = build_model(cfg_x), build_model(cfg_p)
        params = ML.unbox(mx.init(jax.random.key(0)))
        batch = make_batch(cfg_x, 2, 64)
        ox, _ = mx.apply(params, batch, remat=False)
        op, _ = mp.apply(params, batch, remat=False)
        scale = float(jnp.max(jnp.abs(ox)))
        assert float(jnp.max(jnp.abs(ox - op))) < 1e-4 * max(1, scale), arch


@pytest.mark.parametrize("arch", ["starcoder2-3b", "gemma3-27b"])
def test_train_step_pallas_matches_xla(arch):
    """One full-remat train step through the flash kernel's backward gives
    the XLA path's loss and gradients (gemma3: traced per-layer windows).
    With momentum from zero and no decay or clip, the new momentum is the
    gradient."""
    from repro.config import OptimizerConfig, TrainConfig, get_config
    from repro.data.pipeline import make_batch
    from repro.models.builder import build_model
    from repro.train.step import init_state, make_train_step

    tcfg = TrainConfig(optimizer=OptimizerConfig(weight_decay=0.0,
                                                 grad_clip=0.0),
                       remat="full")
    cfg = get_config(arch, reduced=True).replace(dtype="float32")
    state = init_state(build_model(cfg), tcfg, jax.random.key(0))
    batch = make_batch(cfg, 2, 64)
    out = {}
    for impl in ("xla", "pallas"):
        step = jax.jit(make_train_step(build_model(cfg.replace(
            attn_impl=impl)), tcfg))
        new, metrics = step(state, batch)
        out[impl] = (metrics["loss"], new.opt["mu"])
    (loss_x, g_x), (loss_p, g_p) = out["xla"], out["pallas"]
    np.testing.assert_allclose(loss_p, loss_x, rtol=1e-5)
    for (path, gx), gp in zip(jax.tree_util.tree_leaves_with_path(g_x),
                              jax.tree.leaves(g_p)):
        scale = max(1.0, float(jnp.max(jnp.abs(gx))))
        np.testing.assert_allclose(gp, gx, atol=1e-4 * scale,
                                   err_msg=jax.tree_util.keystr(path))

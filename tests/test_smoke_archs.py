"""Per-arch smoke tests (required by the spec): REDUCED config of the same
family — one forward + one train step + one decode step on CPU, asserting
output shapes and no NaNs."""
import jax
import jax.numpy as jnp
import pytest

from repro.config import ASSIGNED_ARCHS, TrainConfig, get_config
from repro.data.pipeline import make_batch
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.builder import build_model
from repro.train.step import init_state, make_serve_step, make_train_step

TCFG = TrainConfig(checkpoint_every=0)
B, S = 2, 32


@pytest.fixture(scope="module")
def built():
    out = {}
    for arch in ASSIGNED_ARCHS + ("resnet32-cifar10",):
        cfg = get_config(arch, reduced=True)
        model = build_model(cfg)
        params = L.unbox(model.init(jax.random.key(0)))
        out[arch] = (cfg, model, params)
    return out


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_forward_shapes_finite(built, arch):
    cfg, model, params = built[arch]
    batch = make_batch(cfg, B, S)
    logits, aux = model.apply(params, batch, remat=False)
    exp_s = batch["labels"].shape[1] if "labels" in batch else S
    assert logits.shape == (B, exp_s, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    assert all(bool(jnp.isfinite(v)) for v in aux.values())


def test_resnet_forward(built):
    cfg, model, params = built["resnet32-cifar10"]
    batch = make_batch(cfg, B, 0)
    logits, _ = model.apply(params, batch, remat=False)
    assert logits.shape == (B, cfg.num_classes)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_train_step(built, arch):
    cfg, model, params = built[arch]
    state = init_state(model, TCFG, jax.random.key(0), unboxed_params=params)
    step = jax.jit(make_train_step(model, TCFG))
    batch = make_batch(cfg, B, S)
    new_state, m = step(state, batch)
    assert bool(jnp.isfinite(m["loss"]))
    assert bool(jnp.isfinite(m["grad_norm"]))
    assert int(new_state.step) == 1
    # at least one parameter changed
    changed = jax.tree.reduce(
        lambda a, b: a or b,
        jax.tree.map(lambda p, q: bool(jnp.any(p != q)),
                     state.params, new_state.params))
    assert changed


# latent attention has no decode cache yet (test_latent_decode_is_refused)
DECODE_ARCHS = tuple(a for a in ASSIGNED_ARCHS
                     if not get_config(a, reduced=True).kv_lora_rank)


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_decode_step(built, arch):
    cfg, model, params = built[arch]
    if cfg.family == "encdec":
        cache = model.init_cache(B, 16, enc_len=8)
        fe = jnp.zeros((B, 8, cfg.d_model), jnp.dtype(cfg.dtype))
        cache = T.encode_for_decode(params, cfg, fe, cache)
    else:
        cache = model.init_cache(B, 16)
    serve = jax.jit(make_serve_step(model))
    tok = jnp.ones((B, 1), jnp.int32)
    for i in range(3):
        tok, cache = serve(params, cache, tok)
    assert tok.shape == (B, 1)
    assert int(cache["pos"][0]) == 3
    assert bool(jnp.all((tok >= 0) & (tok < cfg.vocab_size)))


@pytest.mark.parametrize("arch", ["starcoder2-3b", "rwkv6-7b", "zamba2-1.2b"])
def test_prefill_decode_consistency(built, arch):
    """Prefill-by-forward and step-by-step decode agree on the next token."""
    cfg, model, params = built[arch]
    cfg32 = cfg.replace(dtype="float32")
    model32 = build_model(cfg32)
    toks = jax.random.randint(jax.random.key(3), (1, 8), 0, cfg.vocab_size)
    logits, _ = model32.apply(params, {"tokens": toks}, remat=False)

    cache = model32.init_cache(1, 16)
    last = None
    for i in range(8):
        last, cache = jax.jit(model32.decode)(params, cache,
                                              {"tokens": toks[:, i:i + 1]})
    assert jnp.allclose(logits[:, -1], last[:, -1], atol=2e-3), (
        float(jnp.max(jnp.abs(logits[:, -1] - last[:, -1]))))


def test_latent_decode_is_refused(built):
    """Decode and paged decode of latent attention raise, naming the
    latent cache they lack, instead of running a wrong cache."""
    cfg, model, params = built["moonlight-16b-a3b"]
    assert cfg.kv_lora_rank
    with pytest.raises(NotImplementedError, match="latent KV cache"):
        model.init_cache(B, 16)
    with pytest.raises(NotImplementedError, match="latent KV cache"):
        model.init_paged_cache(B, 16, page_size=4, num_pages=8)
    with pytest.raises(NotImplementedError, match="latent KV cache"):
        model.decode(params, {}, {"tokens": jnp.ones((B, 1), jnp.int32)})
    with pytest.raises(NotImplementedError, match="latent KV cache"):
        model.decode_paged(params, {}, {"tokens": jnp.ones((B, 1),
                                                           jnp.int32)})

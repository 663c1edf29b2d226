"""Serving holds the weights the model casts before every use in
``cfg.dtype`` (``Model.init_for_serving``). That must change bytes, not
results: decode logits are bit-identical to float32-held weights, on the
dense and the paged cache, and so are the engine's tokens."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config
from repro.models import layers as L
from repro.models.builder import build_model
from repro.serving import Request, ServeEngine

B, MAX_LEN, PAGE = 2, 16, 4


def _decode_logits(model, params, cache_impl, tokens):
    """Feed ``tokens`` (B, T) one position per step; stack the logits."""
    if cache_impl == "dense":
        cache = model.init_cache(B, MAX_LEN)
        step = jax.jit(lambda p, c, t: model.decode(p, c, {"tokens": t}))
    else:
        lp = MAX_LEN // PAGE
        cache = model.init_paged_cache(B, MAX_LEN, page_size=PAGE,
                                       num_pages=B * lp)
        cache["page_table"] = jnp.arange(B * lp, dtype=jnp.int32
                                         ).reshape(B, lp)
        step = jax.jit(lambda p, c, t: model.decode_paged(
            p, c, {"tokens": t}))
    out = []
    for t in range(tokens.shape[1]):
        logits, cache = step(params, cache, tokens[:, t:t + 1])
        out.append(np.asarray(logits.astype(jnp.float32)))
    return np.stack(out)


@pytest.mark.parametrize("cache_impl", ["dense", "paged"])
@pytest.mark.parametrize("arch", ["starcoder2-3b", "rwkv6-7b"])
def test_held_weights_give_bit_identical_logits(arch, cache_impl):
    cfg = get_config(arch, reduced=True)
    assert cfg.dtype == "bfloat16"
    model = build_model(cfg)
    boxed = model.init(jax.random.key(0))
    full = L.unbox(boxed)
    held = model.init_for_serving(jax.random.key(0))

    # held is the float32 tree with exactly the cast leaves narrowed
    for b, f, h in zip(jax.tree.leaves(boxed, is_leaf=L.is_boxed),
                       jax.tree.leaves(full), jax.tree.leaves(held)):
        assert h.dtype == (jnp.bfloat16 if b.cast else f.dtype)
        np.testing.assert_array_equal(np.asarray(h),
                                      np.asarray(f.astype(h.dtype)))
    assert any(b.cast for b in jax.tree.leaves(boxed, is_leaf=L.is_boxed))

    tokens = jax.random.randint(jax.random.key(1), (B, 10), 1,
                                cfg.vocab_size, jnp.int32)
    want = _decode_logits(model, full, cache_impl, tokens)
    got = _decode_logits(model, held, cache_impl, tokens)
    np.testing.assert_array_equal(got, want)

    def serve(params):
        rng = np.random.default_rng(0)
        reqs = [Request(rid=i, prompt=rng.integers(
                    1, cfg.vocab_size, size=(5,)).tolist(),
                    max_new_tokens=6) for i in range(3)]
        eng = ServeEngine(model, params, max_batch=B, max_len=MAX_LEN,
                          cache_impl=cache_impl, page_size=PAGE)
        for r in reqs:
            eng.submit(r)
        eng.run_to_completion()
        assert all(r.done for r in reqs)
        return [r.generated for r in reqs]

    assert serve(held) == serve(full)

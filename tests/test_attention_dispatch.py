"""Which path attention takes. ``attn_impl="auto"`` (the default) takes the
flash kernel only where the program compiles for a TPU, so on the CPU
every path, full attention and decode alike, traces exactly the program
``"xla"`` traces; decode and paged decode ignore ``"auto"`` everywhere.
The described-TPU side is in ``test_tpu_compile.py``."""
import jax
import jax.numpy as jnp
import pytest

from repro.config import get_config
from repro.data.pipeline import make_batch
from repro.launch.mesh import single_device_mesh
from repro.models import attention as A
from repro.models import layers as ML
from repro.models.builder import build_model
from repro.sharding import use_mesh
from repro.train.step import make_paged_serve_step, make_serve_step

F32 = jnp.float32


def _jaxpr(fn, *args) -> str:
    return str(jax.make_jaxpr(fn)(*args))


def _cfg(impl, arch="starcoder2-3b"):
    return get_config(arch, reduced=True).replace(attn_impl=impl)


@pytest.mark.parametrize("mesh", [False, True])
def test_auto_attend_is_the_scan_off_tpu(mesh):
    # shapes that tile the kernel's blocks: only the platform keeps it off
    q = jnp.ones((2, 256, 4, 128), F32)
    k = jnp.ones((2, 256, 2, 128), F32)
    with use_mesh(single_device_mesh() if mesh else None, "fsdp"):
        text = {impl: _jaxpr(lambda q, k, v: A.attend(q, k, v, _cfg(impl)),
                             q, k, k)
                for impl in ("auto", "xla")}
    assert text["auto"] == text["xla"]
    assert "pallas_call" not in text["auto"]
    assert "shard_map" not in text["auto"]


@pytest.mark.parametrize("arch", ["starcoder2-3b", "gemma3-27b"])
def test_auto_model_forward_is_the_xla_forward(arch):
    assert get_config(arch, reduced=True).attn_impl == "auto"
    models = {i: build_model(_cfg(i, arch)) for i in ("auto", "xla")}
    params = ML.unbox(models["xla"].init(jax.random.key(0)))
    batch = make_batch(models["xla"].cfg, 2, 64)
    fwd = {i: (lambda p, b, m=m: m.apply(p, b, remat=False)[0])
           for i, m in models.items()}
    assert _jaxpr(fwd["auto"], params, batch) == _jaxpr(fwd["xla"], params,
                                                        batch)
    assert bool(jnp.all(fwd["auto"](params, batch)
                        == fwd["xla"](params, batch)))


@pytest.mark.parametrize("cache_impl", ["dense", "paged"])
def test_decode_ignores_auto(cache_impl):
    text = {}
    for impl in ("auto", "xla"):
        model = build_model(_cfg(impl))
        params = jax.eval_shape(model.init_for_serving, jax.random.key(0))
        tokens = jax.ShapeDtypeStruct((2, 1), jnp.int32)
        if cache_impl == "dense":
            cache = jax.eval_shape(lambda: model.init_cache(2, 64))
            text[impl] = _jaxpr(make_serve_step(model), params, cache,
                                tokens)
        else:
            cache = jax.eval_shape(lambda: model.init_paged_cache(
                2, 64, page_size=16, num_pages=8))
            active = jax.ShapeDtypeStruct((2,), jnp.bool_)
            text[impl] = _jaxpr(make_paged_serve_step(model), params, cache,
                                tokens, active)
    assert text["auto"] == text["xla"]
    assert "pallas_call" not in text["auto"]


def test_forced_kernel_keeps_the_scan_for_kv_len():
    q = jnp.ones((1, 128, 4, 128), F32)
    k = jnp.ones((1, 128, 2, 128), F32)
    text = _jaxpr(lambda q, k, v: A.attend(q, k, v, _cfg("pallas"),
                                           causal=False,
                                           kv_len=jnp.int32(100)), q, k, k)
    assert "pallas_call" not in text
    assert "pallas_call" in _jaxpr(
        lambda q, k, v: A.attend(q, k, v, _cfg("pallas")), q, k, k)


@pytest.mark.parametrize("chunks", [2, 4])
def test_chunked_scan_matches_one_block_with_unequal_v(chunks):
    """The q-chunk scan, with v narrower than q/k as latent attention has
    it, gives one block's output and gradients, whether each chunk's
    scores are held for the backward pass (2 chunks) or made again there
    (more than 2)."""
    S = 64
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (2, S, 4, 24), F32)
    k = jax.random.normal(ks[1], (2, S, 4, 24), F32)
    v = jax.random.normal(ks[2], (2, S, 4, 16), F32)

    def loss(cfg):
        return lambda q, k, v: jnp.sum(jnp.sin(A.attend(q, k, v, cfg)))

    one = _cfg("xla").replace(attn_chunk=S)
    many = one.replace(attn_chunk=S // chunks)
    want = jax.value_and_grad(loss(one), argnums=(0, 1, 2))(q, k, v)
    got = jax.value_and_grad(loss(many), argnums=(0, 1, 2))(q, k, v)
    assert ("remat" in _jaxpr(loss(many), q, k, v)) == (
        chunks > 2)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert jnp.allclose(a, b, rtol=1e-5, atol=1e-5)

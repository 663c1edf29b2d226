"""Compile the main path for a TPU v5e chip that is described, not attached.

Interpret mode accepts kernels the chip's compiler refuses (unaligned
blocks, boolean selects and transposes, in-kernel cumulative sums), and
only a compile at real widths shows whether a program fits the chip's
memory. Each test hands ``jax.jit(...).lower(...).compile()`` shapes
placed on one described v5e device; nothing runs.

The topology is described inside a module fixture, never at import:
loading the TPU compiler takes a process-wide lock, and every test worker
imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rwkv6 import rwkv6_scan
from repro.kernels.ssd_scan import ssd_scan
from repro.models.builder import build_model
from repro.train.step import make_paged_serve_step, make_serve_step

# The compiler's own limit on one v5e chip, as it reports it when a
# program does not fit ("... of 15.75G hbm").
V5E_HBM_BYTES = 15.75 * 2 ** 30
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_cases():
    sc = get_config("starcoder2-3b")
    H, KV, D = sc.num_heads, sc.num_kv_heads, sc.head_dim
    zb = get_config("zamba2-1.2b")
    P, N, Hs = zb.ssm_head_dim, zb.ssm_state, zb.ssm_heads
    rw = get_config("rwkv6-7b")
    Hr, Dr = rw.d_model // rw.rwkv_head_dim, rw.rwkv_head_dim
    S = 1024
    return {
        # starcoder2-3b decode: 8 rows against a 1024-position cache
        "decode_attention": (
            lambda q, k, v, n: decode_attention(q, k, v, n),
            [((8, H, D), BF16), ((8, KV, S, D), BF16),
             ((8, KV, S, D), BF16), ((8,), I32)]),
        # starcoder2-3b causal prefill of one 1024-token prompt
        "flash_attention": (
            lambda q, k, v: flash_attention(q, k, v, causal=True),
            [((1, H, S, D), BF16), ((1, KV, S, D), BF16),
             ((1, KV, S, D), BF16)]),
        # zamba2-1.2b Mamba2 SSD scan at its own chunk length
        "ssd_scan": (
            lambda x, b, c, a: ssd_scan(x, b, c, a, chunk=zb.ssm_chunk),
            [((1, Hs, S, P), BF16), ((1, S, N), BF16), ((1, S, N), BF16),
             ((1, Hs, S), F32)]),
        # rwkv6-7b WKV recurrence at the model's chunk of 64
        "rwkv6": (
            lambda r, k, v, w, u: rwkv6_scan(r, k, v, w, u, chunk=64),
            [((1, Hr, S, Dr), F32)] * 4 + [((Hr, Dr), F32)]),
    }


@pytest.mark.parametrize("kernel", ["decode_attention", "flash_attention",
                                    "ssd_scan", "rwkv6"])
def test_kernel_compiles_for_v5e(one_chip, kernel):
    fn, shapes = _kernel_cases()[kernel]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("cache_impl", ["dense", "paged"])
def test_full_width_decode_step_fits_one_v5e(one_chip, cache_impl):
    """starcoder2-3b at published widths, weights as ``init_for_serving``
    holds them, a cache for 8 rows of 1024 positions: the one-token
    serving step compiles for one chip and fits its memory."""
    model = build_model(get_config("starcoder2-3b"))
    params = _on(one_chip, jax.eval_shape(model.init_for_serving,
                                          jax.random.key(0)))
    tokens = _on(one_chip, jax.ShapeDtypeStruct((8, 1), I32))
    if cache_impl == "dense":
        cache = jax.eval_shape(lambda: model.init_cache(8, 1024))
        compiled = _compile(make_serve_step(model), params,
                            _on(one_chip, cache), tokens)
    else:
        cache = jax.eval_shape(lambda: model.init_paged_cache(
            8, 1024, page_size=16, num_pages=8 * 1024 // 16))
        active = _on(one_chip, jax.ShapeDtypeStruct((8,), jnp.bool_))
        compiled = _compile(make_paged_serve_step(model), params,
                            _on(one_chip, cache), tokens, active)
    mem = compiled.memory_analysis()
    weight_bytes = sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(params))
    # every >=2-D weight held in bf16: about 2 bytes per parameter
    assert weight_bytes < 2.01 * model.cfg.param_count() + 2 ** 20
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, total

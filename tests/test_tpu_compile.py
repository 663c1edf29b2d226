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
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.config import TrainConfig, get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rwkv6 import rwkv6_scan
from repro.kernels.ssd_scan import ssd_scan
from repro.launch.specs import batch_shardings
from repro.models import attention as A
from repro.models.builder import build_model
from repro.sharding import param_shardings, use_mesh
from repro.train.step import (TrainState, init_state, make_paged_serve_step,
                              make_serve_step, make_train_step)

# The compiler's own limit on one v5e chip, as it reports it when a
# program does not fit ("... of 15.75G hbm").
V5E_HBM_BYTES = 15.75 * 2 ** 30
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The benchmark's fsdp mesh: 4 x 1 over a v5e:2x2 host."""
    return Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


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
            [((1, S, H, D), BF16), ((1, S, KV, D), BF16),
             ((1, S, KV, D), BF16)]),
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


# ---------------------------------------------------------------------------
# Attention dispatch and the full-width fsdp train step on four chips
# ---------------------------------------------------------------------------

def _attend_jaxpr(mesh, B=4, S=2048, D=128, kv_len=None, impl="auto",
                  Dv=None):
    cfg = get_config("starcoder2-3b").replace(attn_impl=impl)
    q = jax.ShapeDtypeStruct((B, S, cfg.num_heads, D), BF16)
    kv = jax.ShapeDtypeStruct((B, S, cfg.num_kv_heads, D), BF16)
    v = jax.ShapeDtypeStruct((B, S, cfg.num_kv_heads, Dv or D), BF16)
    with use_mesh(mesh, "fsdp"):
        return str(jax.make_jaxpr(lambda q, k, v: A.attend(
            q, k, v, cfg, kv_len=kv_len))(q, kv, v))


def test_auto_takes_the_kernel_per_shard_on_v5e(four_chips):
    text = _attend_jaxpr(four_chips)
    assert "pallas_call" in text and "shard_map" in text


@pytest.mark.parametrize("case", [
    dict(S=2000),                    # positions do not tile the blocks
    dict(D=64),                      # head_dim off the lanes
    dict(B=2),                       # batch does not divide the data axes
    dict(kv_len=jnp.int32(5)),       # masked kv lengths
    dict(Dv=64),                     # v's head dim is not q's
    dict(D=192, Dv=128),             # latent attention's q/k and v heads
])
def test_auto_falls_back_to_the_scan(four_chips, case):
    text = _attend_jaxpr(four_chips, **case)
    assert "pallas_call" not in text and "shard_map" not in text
    assert text == _attend_jaxpr(four_chips, impl="xla", **case)


@pytest.fixture(scope="module")
def fsdp_steps(four_chips):
    """starcoder2-3b at published widths, the benchmark's fsdp train step
    (1 x 2,048 tokens a chip, full remat) compiled under ``"auto"`` and
    ``"xla"``."""
    mesh = four_chips
    tcfg = TrainConfig(remat="full", layout="fsdp")
    rep = NamedSharding(mesh, P())
    out = {}
    for impl in ("auto", "xla"):
        model = build_model(get_config("starcoder2-3b").replace(
            attn_impl=impl))
        shard = param_shardings(model.abstract_params(), model.cfg, mesh,
                                layout="fsdp")
        state_shard = TrainState(params=shard, opt={"mu": shard}, step=rep)
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            jax.eval_shape(lambda k: init_state(model, tcfg, k),
                           jax.random.key(0)), state_shard)
        tokens = jax.ShapeDtypeStruct((4, 2048), I32)
        bshard = batch_shardings({"tokens": tokens, "labels": tokens}, mesh,
                                 "fsdp")
        batch = {k: jax.ShapeDtypeStruct(tokens.shape, I32, sharding=sh)
                 for k, sh in bshard.items()}
        step = jax.jit(make_train_step(model, tcfg, param_shardings=shard),
                       in_shardings=(state_shard, bshard, rep),
                       out_shardings=(state_shard, None), donate_argnums=(0,))
        with use_mesh(mesh, "fsdp"):
            out[impl] = step.lower(state, batch, _on(rep, jax.ShapeDtypeStruct(
                (), F32))).compile()
    return out


def _all_gathers(text):
    """The all-gathers a compiled program issues: each ``all-gather`` op
    outside fused computations, and each asynchronous collective start
    whose computation gathers. The TPU compiler copies an asynchronous
    gather into every compute fusion it overlaps, so counting the text's
    ``all-gather`` ops would count its schedule too."""
    comps, fused, cur = {}, set(), None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            cur = line.split()[1 if line.startswith("ENTRY") else 0]
            comps[cur.lstrip("%")] = []
            continue
        if cur is not None:
            comps[cur.lstrip("%")].append(line)
        fused.update(re.findall(r"calls=%?([\w.\-]+)", line))
    gathers = re.compile(r" all-gather(?:-start)?\(")
    n = 0
    for name, lines in comps.items():
        if name in fused:
            continue
        for line in lines:
            n += bool(gathers.search(line))
            start = re.search(r"= .* fusion\(.*calls=%?([\w.\-]+)", line)
            if start and "async-collective-start" in line.split("=")[0]:
                n += any(gathers.search(x)
                         for x in comps.get(start.group(1), ()))
    return n


def _hbm(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_fsdp_step_runs_the_kernel_without_new_gathers(fsdp_steps):
    auto, xla = (fsdp_steps[i].as_text() for i in ("auto", "xla"))
    assert "tpu_custom_call" in auto and "tpu_custom_call" not in xla
    assert _all_gathers(auto) == _all_gathers(xla) > 0


def test_fsdp_step_with_the_kernel_fits_one_v5e(fsdp_steps):
    auto, xla = _hbm(fsdp_steps["auto"]), _hbm(fsdp_steps["xla"])
    assert auto < V5E_HBM_BYTES and auto <= xla, (auto, xla)


def test_fsdp_forward_gathers_the_next_layer_asynchronously(fsdp_steps):
    """The forward scan gathers the next layer's weights under
    ``fsdp.prefetch`` as asynchronous collectives beside this layer's
    compute (at least q, o and both MLP weights), and moves no
    activations: its MLP runs on whole weights, not tensor-parallel."""
    text = fsdp_steps["auto"].as_text()
    fwd = "/jvp(layers)/while/body/"
    done = re.findall(r'async-collective-done[\w.]* = [^\n]*op_name="([^"]*)"',
                      text)
    assert sum(fwd in n and "/fsdp.prefetch/" in n for n in done) >= 4, done
    acts = re.findall(
        r'= bf16\[4,2048,3072\][^\n]* all-gather\([^\n]*op_name="([^"]*)"',
        text)
    assert not [n for n in acts if fwd in n], acts


def test_kernels_carry_the_attn_region_and_their_pass(fsdp_steps):
    """``region_share.attn`` and ``pass_share.*`` read the kernels' op_name:
    the forward, the recomputed forward and the backward each sit under
    ``attn`` and say which pass they are. The prefetching forward scan
    runs two layers a loop body, so its kernel appears twice."""
    names = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in fsdp_steps["auto"].as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert names and all("/attn/kernel.flash_attention.pallas/" in n
                         for n in names)
    recompute = [n for n in names if "rematted_computation" in n]
    bwd = [n for n in names if "transpose(" in n and n not in recompute]
    fwd = [n for n in names if "transpose(" not in n]
    assert len(fwd) == 2 and len(recompute) == 1 and len(bwd) == 2, names


# ---------------------------------------------------------------------------
# Moonlight-16B-A3B: one chip's share of an 8-way expert-parallel layer
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def moonlight_step(topo):
    """The benchmark cell's step at published widths: the dense layer and
    5 expert layers holding 8 of 64 experts, a 20,480-row vocabulary
    slice, 4 x 4,096 tokens, full remat, f32 momentum state, fsdp on one
    chip."""
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    tcfg = TrainConfig(remat="full", layout="fsdp")
    rep = NamedSharding(mesh, P())
    model = build_model(get_config("moonlight-16b-a3b").replace(
        num_layers=6, vocab_size=20480, experts_held=8))
    shard = param_shardings(model.abstract_params(), model.cfg, mesh,
                            layout="fsdp")
    state_shard = TrainState(params=shard, opt={"mu": shard}, step=rep)
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(lambda k: init_state(model, tcfg, k),
                       jax.random.key(0)), state_shard)
    tokens = jax.ShapeDtypeStruct((4, 4096), I32)
    bshard = batch_shardings({"tokens": tokens, "labels": tokens}, mesh,
                             "fsdp")
    batch = {k: jax.ShapeDtypeStruct(tokens.shape, I32, sharding=sh)
             for k, sh in bshard.items()}
    step = jax.jit(make_train_step(model, tcfg, param_shardings=shard),
                   in_shardings=(state_shard, bshard, rep),
                   out_shardings=(state_shard, None), donate_argnums=(0,))
    with use_mesh(mesh, "fsdp"):
        return step.lower(state, batch, _on(rep, jax.ShapeDtypeStruct(
            (), F32))).compile()


def test_moonlight_step_fits_one_v5e(moonlight_step):
    assert _hbm(moonlight_step) < V5E_HBM_BYTES, _hbm(moonlight_step)


def test_moonlight_step_runs_grouped_matmuls_in_the_expert_regions(
        moonlight_step):
    """The held experts' grouped matmuls compile for v5e (``ragged_dot``)
    and, with the routing and sorting, carry the ``router`` and
    ``experts`` regions inside ``mlp`` that ``region_share`` reads; the
    latent attention keeps the XLA scan (no kernel in the step)."""
    text = moonlight_step.as_text()
    assert "ragged-dot" in text and "flash" not in text
    names = re.findall(r'op_name="([^"]*)"', text)
    for inner in ("router", "experts"):
        assert any(f"/mlp/{inner}/" in n for n in names), inner
    assert any("/attn/kernel.attention.xla_scan/" in n for n in names)

"""Config registry: every assigned arch present, dims exact, counts sane."""
import pytest

from repro.config import (ASSIGNED_ARCHS, SHAPES, get_config, list_archs,
                          shape_applicable)

EXPECTED_DIMS = {
    # arch: (layers, d_model, heads, kv, d_ff, vocab)
    "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000),
    "qwen2.5-14b": (48, 5120, 40, 8, 13824, 152064),
    "granite-20b": (52, 6144, 48, 1, 24576, 49152),
    "gemma3-27b": (62, 5376, 32, 16, 21504, 262144),
    "starcoder2-3b": (30, 3072, 24, 2, 12288, 49152),
    "moonlight-16b-a3b": (27, 2048, 16, 16, 11264, 163840),
    "arctic-480b": (35, 7168, 56, 8, 4864, 32000),
    "rwkv6-7b": (32, 4096, 64, 64, 14336, 65536),   # attn-free: 64 wkv heads
    "qwen2-vl-7b": (28, 3584, 28, 4, 18944, 152064),
}

PARAM_BOUNDS = {                       # (min, max) in billions
    "zamba2-1.2b": (1.0, 2.2),
    "qwen2.5-14b": (13.5, 16.0),
    "granite-20b": (18.5, 22.0),
    "gemma3-27b": (25.0, 29.0),
    "starcoder2-3b": (2.8, 3.6),
    "moonlight-16b-a3b": (15.5, 16.5),
    "arctic-480b": (450.0, 500.0),
    "rwkv6-7b": (6.0, 8.0),
    "qwen2-vl-7b": (7.0, 8.5),
}


def test_all_assigned_registered():
    archs = list_archs()
    for a in ASSIGNED_ARCHS:
        assert a in archs
    assert "resnet32-cifar10" in archs      # the paper's own model


@pytest.mark.parametrize("arch", sorted(EXPECTED_DIMS))
def test_exact_dims(arch):
    L, d, H, KV, f, V = EXPECTED_DIMS[arch]
    c = get_config(arch)
    n_layers = c.num_layers or (c.enc_layers + c.dec_layers)
    assert n_layers == L
    assert c.d_model == d
    assert c.num_heads == H
    assert c.num_kv_heads == KV
    assert c.d_ff == f
    assert c.vocab_size == V


def test_seamless_encdec_dims():
    c = get_config("seamless-m4t-large-v2")
    assert c.family == "encdec"
    # assigned "24L" enc-dec: 24 text-encoder + 24 decoder layers
    assert (c.enc_layers, c.dec_layers) == (24, 24)
    assert c.d_model == 1024 and c.d_ff == 8192 and c.vocab_size == 256206


@pytest.mark.parametrize("arch", sorted(PARAM_BOUNDS))
def test_param_counts(arch):
    lo, hi = PARAM_BOUNDS[arch]
    n = get_config(arch).param_count() / 1e9
    assert lo <= n <= hi, f"{arch}: {n:.2f}B outside [{lo}, {hi}]"


def test_moe_active_counts():
    arctic = get_config("arctic-480b")
    assert arctic.active_param_count() < 0.06 * arctic.param_count()
    moon = get_config("moonlight-16b-a3b")
    assert moon.active_param_count() < 0.35 * moon.param_count()


def test_long500k_gating():
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        ok, reason = shape_applicable(arch, SHAPES["long_500k"], cfg.family)
        if arch in ("zamba2-1.2b", "rwkv6-7b"):
            assert ok
        else:
            assert not ok and "quadratic" in reason


def test_reduced_configs_small():
    for arch in ASSIGNED_ARCHS:
        r = get_config(arch, reduced=True)
        assert r.param_count() < 50e6, arch


def test_moonlight_latent_and_expert_widths():
    c = get_config("moonlight-16b-a3b")
    assert (c.kv_lora_rank, c.head_dim, c.qk_rope_head_dim,
            c.v_head_dim) == (512, 128, 64, 128)
    assert (c.num_experts, c.top_k, c.num_shared_experts) == (64, 6, 2)
    assert (c.expert_ff, c.d_ff, c.first_dense_layers) == (1408, 11264, 1)
    assert (c.router_scoring, c.routed_scaling) == ("sigmoid", 2.446)
    assert c.held_experts == 64 and c.dense_ff == 0
    # the expert width defaults to d_ff where unset
    assert get_config("arctic-480b").expert_ff == 4864


@pytest.mark.parametrize("held", [0, 4])
def test_param_count_is_the_built_tree(held):
    """The analytic count is exact for what `build_model` makes, latent
    attention and a held share of the experts included."""
    import jax
    import numpy as np
    from repro.models.builder import build_model
    cfg = get_config("moonlight-16b-a3b", reduced=True).replace(
        experts_held=held)
    tree = build_model(cfg).abstract_params()
    n = sum(int(np.prod(b.value.shape)) for b in jax.tree.leaves(
        tree, is_leaf=lambda x: hasattr(x, "axes")))
    # the analytic count leaves out each layer's two RMS gains and the
    # final norm's (it keeps the latent's)
    gains = (2 * cfg.num_layers + 1) * cfg.d_model
    assert cfg.param_count() == n - gains

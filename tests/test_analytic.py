"""Analytic FLOPs/bytes model invariants (the roofline's numerator)."""
import jax
import pytest

from repro import analytic
from repro.config import SHAPES, ShapeConfig, TrainConfig, get_config
from repro.launch.mesh import single_device_mesh
from repro.models.builder import build_model


def test_fwd_flops_linear_in_batch():
    cfg = get_config("qwen2.5-14b")
    f1 = analytic.fwd_flops(cfg, 1, 4096)
    f4 = analytic.fwd_flops(cfg, 4, 4096)
    assert f4 == pytest.approx(4 * f1, rel=1e-9)


def test_train_flops_exceed_prefill():
    cfg = get_config("granite-20b")
    shape = SHAPES["train_4k"]
    tr = analytic.step_flops(cfg, shape, remat="full")
    pf = analytic.fwd_flops(cfg, shape.global_batch, shape.seq_len)
    assert tr == pytest.approx(4 * pf, rel=1e-9)       # fwd+bwd+remat
    assert analytic.step_flops(cfg, shape, remat="none") == \
        pytest.approx(3 * pf, rel=1e-9)


def test_fwd_flops_close_to_6nd_heuristic():
    """For a big dense model at moderate seq, matmul flops ~ 2 N D."""
    for arch in ("qwen2.5-14b", "granite-20b", "rwkv6-7b"):
        cfg = get_config(arch)
        T = 256 * 4096
        got = analytic.fwd_flops(cfg, 256, 4096)
        ideal = 2.0 * cfg.active_param_count() * T
        assert 0.8 < got / ideal < 1.6, (arch, got / ideal)


def test_moe_flops_track_active_params():
    cfg = get_config("arctic-480b")
    got = analytic.fwd_flops(cfg, 8, 4096)
    dense_equiv = 2.0 * cfg.param_count() * 8 * 4096
    active_equiv = 2.0 * cfg.active_param_count() * 8 * 4096
    assert got < 0.2 * dense_equiv                     # far from dense
    assert got == pytest.approx(active_equiv, rel=0.6)


def test_decode_flops_much_smaller_than_prefill():
    cfg = get_config("gemma3-27b")
    pf = analytic.step_flops(cfg, SHAPES["prefill_32k"])
    dc = analytic.step_flops(cfg, SHAPES["decode_32k"])
    assert dc < pf / 100


def test_sliding_window_reduces_attn_flops():
    cfg = get_config("gemma3-27b")                     # 5:1 local:global
    full = cfg.replace(sliding_window=0, global_every=0)
    assert analytic.fwd_flops(cfg, 1, 32768) < \
        analytic.fwd_flops(full, 1, 32768)


def test_sharded_param_bytes_layouts():
    mesh = single_device_mesh()
    cfg = get_config("starcoder2-3b", reduced=True)
    model = build_model(cfg)
    full = analytic.sharded_param_bytes(model, cfg, mesh, 4)
    # 1-device mesh: nothing shards; both layouts give the whole model
    assert analytic.sharded_param_bytes(model, cfg, mesh, 4,
                                        layout="fsdp") == full
    assert full == pytest.approx(cfg.param_count() * 4, rel=0.01)


def test_memory_breakdown_decode_dominated_by_weights_or_kv():
    mesh = single_device_mesh()
    cfg = get_config("qwen2.5-14b", reduced=True)
    model = build_model(cfg)
    mb = analytic.step_hbm_bytes(model, cfg, SHAPES["decode_32k"], mesh,
                                 tcfg=TrainConfig())
    assert mb.total > 0
    assert mb.params + mb.kv_cache > 0.5 * mb.total


def test_remat_flag_changes_memory_model():
    mesh = single_device_mesh()
    cfg = get_config("starcoder2-3b", reduced=True)
    model = build_model(cfg)
    with_remat = analytic.step_hbm_bytes(
        model, cfg, SHAPES["train_4k"], mesh, tcfg=TrainConfig(remat="full"))
    without = analytic.step_hbm_bytes(
        model, cfg, SHAPES["train_4k"], mesh, tcfg=TrainConfig(remat="none"))
    assert without.activations < with_remat.activations
    with pytest.raises(ValueError, match="remat"):
        TrainConfig(remat="selective")           # "none" and "full" only

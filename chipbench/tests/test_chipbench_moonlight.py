"""The Moonlight-16B-A3B share at a small size on the CPU: the program
against the plain reference (``chipbench/configs/moonlight_reference.py``)
on the benchmark's seeded weights, the expert shares adding up to the
uncut layer, no token dropped, and the configuration file the cell runs."""
import copy
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, model as M, weights as W
from chipbench.configs import moonlight_reference as R
from repro.config import TrainConfig, get_config
from repro.models import ffn as F
from repro.models.builder import build_model
from repro.train.step import loss_fn

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "moonlight-train-4k"


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def config_file():
    return harness.load_cell(ROOT, CELL)["config"]


def small(**kw):
    return get_config("moonlight-16b-a3b", reduced=True).replace(**kw)


def sizes_of(cfg):
    return {"norm_epsilon": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "num_attention_heads": cfg.num_heads}


def seeded(cfg, seed=2**33 + 7):
    """The benchmark's weights for ``cfg``, from its per-leaf law."""
    boxed, raw = M.param_tree(build_model(cfg))
    return W.make(boxed, raw, seed)


def batch(cfg, B=2, S=64, seed=1):
    ids = jax.random.randint(jax.random.key(seed), (B, S + 1), 0,
                             cfg.vocab_size)
    return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}


def test_reference_constants_are_the_config_files():
    c = config_file()
    assert R.TOP_K == c["num_experts_per_tok"]
    assert R.ROUTED_SCALING == c["routed_scaling_factor"]
    assert c["norm_topk_prob"] is True        # program and ref normalise
    assert R.FIRST_HELD == c["first_held_expert"]
    assert R.AUX_COEF == c["assumed"]["aux_loss_alpha"]
    assert c["scoring_func"] == "sigmoid" and c["topk_method"] == "noaux_tc"
    assert c["bias_update_speed"] == 0.0      # left out, program and ref


def test_build_accepts_the_config_file():
    c = config_file()
    model, sizes = M.build(c)
    cfg = model.cfg
    assert sizes["num_hidden_layers"] == cfg.num_layers == 6
    assert (cfg.num_experts, cfg.held_experts, cfg.first_held_expert) == (
        c["n_routed_experts"], c["experts_held"], c["first_held_expert"])
    assert (cfg.top_k, cfg.num_shared_experts, cfg.expert_ff) == (
        c["num_experts_per_tok"], c["n_shared_experts"],
        c["moe_intermediate_size"])
    assert (cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.head_dim) == (c["kv_lora_rank"], c["qk_rope_head_dim"],
                              c["v_head_dim"], c["qk_nope_head_dim"])
    assert cfg.routed_scaling == c["routed_scaling_factor"]
    assert cfg.first_dense_layers == c["first_k_dense_replace"]
    assert cfg.router_aux_coef == c["assumed"]["aux_loss_alpha"]
    boxed, raw = M.param_tree(model)
    wi = raw["layers"]["moe"]["wi"]
    assert wi.shape == (5, 8, 2048, 1408)       # 5 expert layers, 8 held
    assert raw["layers"]["moe"]["router"].shape == (5, 2048, 64)
    assert raw["embed"]["out"].shape == (2048, 20480)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(raw))
    assert 667e6 < n < 671e6                   # 166.9 M + 5 x 100.4 M


@pytest.mark.parametrize("held", [0, 4])
def test_program_loss_and_gradients_match_the_reference(held):
    """In float32 the program and the reference do the same arithmetic in
    another order (the program's sorted, grouped matmuls against one
    dense pass an expert): loss within 1e-5 and every leaf's gradient
    within 1e-4 of its norm, where bfloat16 compute would miss by more
    than 1e-3."""
    cfg = small(experts_held=held, dtype="float32")
    model = build_model(cfg)
    p = seeded(cfg)
    b = batch(cfg)
    tcfg = TrainConfig(remat="full")
    (_, got), g = jax.value_and_grad(
        lambda q: loss_fn(model, q, b, tcfg), has_aux=True)(p)
    want, gr = jax.value_and_grad(R.loss)(p, b["tokens"], b["labels"],
                                          sizes_of(cfg))
    assert float(got["loss"]) == pytest.approx(float(want), rel=1e-5)
    for (path, a), r in zip(jax.tree_util.tree_flatten_with_path(g)[0],
                            jax.tree.leaves(gr)):
        a, r = np.asarray(a), np.asarray(r)
        gap = np.linalg.norm(a - r) / max(np.linalg.norm(r), 1e-30)
        assert gap < 1e-4, (jax.tree_util.keystr(path), gap)


def _layer_input(cfg, seed=3):
    return jax.random.normal(jax.random.key(seed), (2, 24, cfg.d_model),
                             jnp.float32)


def _moe_params(p, layer=0):
    return jax.tree.map(lambda a: a[layer], p["layers"]["moe"])


def test_shares_add_up_to_the_uncut_layer():
    """8 chips of 2 experts each: their routed parts, plus the shared
    experts counted once, give what the uncut reference layer gives."""
    cfg = small(dtype="float32")                 # 16 experts, 6 a token
    m = _moe_params(seeded(cfg))
    x = _layer_input(cfg)
    x2 = x.reshape(-1, cfg.d_model)
    with jax.default_matmul_precision("highest"):
        idx, w, _ = F.route_sigmoid(m, x2, cfg)
        total = F.apply_mlp(m["shared"], x2)
        for share in range(8):
            lo = 2 * share
            part = dict(m, **{k: m[k][lo:lo + 2] for k in ("wi", "wg", "wo")})
            y, sizes = F.held_experts(
                part, x2, idx, w,
                cfg.replace(experts_held=2, first_held_expert=lo))
            total = total + y
        want, _ = R._experts(x, m, quantize=False)
    np.testing.assert_allclose(np.asarray(total).reshape(want.shape),
                               np.asarray(want), rtol=2e-5, atol=2e-5)


def test_no_token_is_dropped_when_every_token_routes_to_one_expert():
    """A bias that sends every token to expert 5: the layer computes all
    of them (a capacity of 1.25 times the mean load would keep a sixth),
    as the reference does, on the chip that holds expert 5 and on one
    that does not."""
    cfg = small(dtype="float32")
    m = _moe_params(seeded(cfg))
    m["router_bias"] = m["router_bias"].at[5].set(100.0)
    x = _layer_input(cfg)
    T = x.shape[0] * x.shape[1]
    with jax.default_matmul_precision("highest"):
        out, aux = F.apply_held_moe(m, x, cfg)
        want, _ = R._experts(x, m, quantize=False)
        idx, w, _ = F.route_sigmoid(m, x.reshape(T, -1), cfg)
        assert bool(jnp.all(jnp.any(idx == 5, axis=-1)))
        part = dict(m, **{k: m[k][4:6] for k in ("wi", "wg", "wo")})
        _, sizes = F.held_experts(part, x.reshape(T, -1), idx, w,
                                  cfg.replace(experts_held=2,
                                              first_held_expert=4))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert int(sizes[1]) == T              # expert 5: every token
    assert float(aux["moe_held_share"]) == 1.0
    assert float(aux["moe_load_max"]) > 2.0


def test_tiny_cell_is_correct_against_the_reference(tmp_path):
    """The training driver at a small size on one CPU device, checked
    against the reference under the cell's own limits. The program
    computes in float32 here: the limits are set for the cell's sizes on
    the chip, and a tiny model's bfloat16 round-off reads above them."""
    config = copy.deepcopy(config_file())
    cfg = small()
    config.update(num_hidden_layers=cfg.num_layers, hidden_size=cfg.d_model,
                  intermediate_size=cfg.d_ff, num_attention_heads=4,
                  num_key_value_heads=4, vocab_size=cfg.vocab_size)
    config["assumed"] = dict(config["assumed"], head_dim=cfg.head_dim)
    config["program"] = {"arch": "moonlight-16b-a3b", "reduced": True,
                         "overrides": {"experts_held": 4,
                                       "dtype": "float32"}}
    cell = {"name": "tiny-moonlight", "chips": 1, "root": str(tmp_path),
            "end_to_end": [{"name": "train_tokens_per_s",
                            "unit": "tokens/s"}],
            "per_layer": [], "config": config,
            "workload": load(ROOT, "chipbench", "workloads", CELL + ".json"),
            "mix": load(HERE, "data", "tiny-batches.json")}
    dev = harness.Device("cpu", "cpu", 1, {"bf16_flops_per_s": 1e12},
                         jax.devices()[:1])
    run = harness.run_cell(cell, dev, seed=2**34 + 11, seconds=0.2,
                           trace=False, t_start=time.monotonic())
    assert run["correct"], run["checks"]

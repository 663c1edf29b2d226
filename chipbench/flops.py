"""Operations the algorithm needs, from the configuration's sizes.

These count the work of the model's equations, not what a program
happens to execute: no recomputation, no padding, no masked positions.
A program that does less than this for the same tokens is not doing the
model's work; one that does more shows a lower share of the peak.

``sizes`` is ``chipbench.model.sizes(config)``: ``num_hidden_layers``,
``hidden_size``, ``intermediate_size``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``vocab_size``.
"""
from __future__ import annotations

from typing import Mapping


def layer_matmul_params(s: Mapping) -> int:
    """Weights one layer multiplies each token by (non-gated MLP)."""
    d, H, KV, Dh, ff = (s["hidden_size"], s["num_attention_heads"],
                        s["num_key_value_heads"], s["head_dim"],
                        s["intermediate_size"])
    return d * H * Dh + 2 * d * KV * Dh + H * Dh * d + 2 * d * ff


def matmul_params(s: Mapping, logits: bool) -> int:
    """Weights a token is multiplied by through the whole model; the
    output head only where the logits are needed."""
    n = s["num_hidden_layers"] * layer_matmul_params(s)
    return n + (s["hidden_size"] * s["vocab_size"] if logits else 0)


def attention_flops(s: Mapping, keys: int) -> int:
    """Scores and weighted values of one query token over ``keys``
    positions, every layer: 2 * H * Dh each for QK^T and PV."""
    return 4 * s["num_hidden_layers"] * s["num_attention_heads"] \
        * s["head_dim"] * keys


def train_flops_per_token(s: Mapping, seq_len: int) -> float:
    """Forward and backward FLOPs a token needs: 6 per weight it meets
    (output head included), plus causal attention's 3 x 4 * L * H * Dh
    over the ``(seq_len + 1) / 2`` keys a token sees on average."""
    return 6.0 * matmul_params(s, logits=True) \
        + 3.0 * attention_flops(s, 1) * (seq_len + 1) / 2.0

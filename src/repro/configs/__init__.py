"""Assigned architecture configs (one module per arch) + the paper's model.

Importing this package registers every config with ``repro.config``.
Module names are sanitized arch ids (``--arch zamba2-1.2b`` maps to
``zamba2_1p2b.py``).
"""
from repro.configs import (  # noqa: F401
    zamba2_1p2b,
    qwen2_5_14b,
    granite_20b,
    gemma3_27b,
    starcoder2_3b,
    moonlight_16b_a3b,
    arctic_480b,
    seamless_m4t_large_v2,
    rwkv6_7b,
    qwen2_vl_7b,
    resnet32_cifar10,
)

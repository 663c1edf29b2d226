"""Training batches for a step, made on the device from the seed.

A mix (``"kind": "train_batches"``) gives ``global_batch`` rows of
``seq_len`` tokens. Step ``i`` of seed ``s`` draws ``seq_len + 1``
uniform token ids a row from ``fold_in(key(s), i)``: the tokens are the
first ``seq_len``, the labels the last ``seq_len`` (next-token). Every
row of every step differs; the same seed and step give the same batch.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from chipbench.weights import seed_key


def make_fn(mix: Dict[str, Any], vocab_size: int,
            sharding: Optional[Any] = None):
    """A jitted ``(key, step) -> {"tokens", "labels"}``, each (B, S)."""
    B, S = int(mix["global_batch"]), int(mix["seq_len"])

    def gen(key, step):
        k = jax.random.fold_in(key, step)
        ids = jax.random.randint(k, (B, S + 1), 0, vocab_size, jnp.int32)
        return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}

    out = None if sharding is None else {"tokens": sharding,
                                         "labels": sharding}
    return jax.jit(gen, out_shardings=out)


def key(seed: int) -> jax.Array:
    return jax.random.fold_in(seed_key(seed), 0x7EA1)

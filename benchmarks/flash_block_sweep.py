"""Time the flash-attention kernels' block sizes on one TPU chip.

Shapes are one chip's share of the ``sc2-train-fsdp4`` cell: starcoder2-3b
(24 q-heads, 2 kv-heads of 128), 1 x 2,048 positions, bf16, causal. Each
kernel (forward, dK/dV, dQ) runs alone at every (blk_q, blk_k) in
{256, 512, 1024}^2; the XLA q-chunk path (forward, and forward plus
backward) is timed beside them. Times are ms per call, which is one layer
and one pass. Prints one JSON line per measurement and writes them all to
``artifacts/bench/flash_block_sweep.jsonl``; refuses to run without a TPU.
Each kernel's result is also compared with the f32 oracle's (``err``: the
largest absolute gap over the oracle's largest magnitude).

    python3 benchmarks/flash_block_sweep.py
"""
from __future__ import annotations

import itertools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.config import get_config  # noqa: E402
from repro.kernels.flash_attention import attention_ref  # noqa: E402
from repro.kernels.flash_attention import kernel as K  # noqa: E402
from repro.models.attention import _attend_xla  # noqa: E402

SIZES = (256, 512, 1024)
S = 2048


def _ms(fn, *args, calls: int = 10, repeats: int = 5) -> float:
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        t = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t) / calls * 1e3)
    return statistics.median(times)


def _err(got, want) -> float:
    got = got.astype(jnp.float32).reshape(want.shape)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("flash_block_sweep: no TPU", file=sys.stderr)
        return 3
    cfg = get_config("starcoder2-3b")
    H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G, scale = H // KV, D ** -0.5
    keys = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(keys[0], (1, S, H * D), jnp.bfloat16)
    k = jax.random.normal(keys[1], (1, S, KV * D), jnp.bfloat16)
    v = jax.random.normal(keys[2], (1, S, KV * D), jnp.bfloat16)
    do = jax.random.normal(keys[3], (1, S, H * D), jnp.bfloat16)
    win = jnp.zeros((1,), jnp.int32)
    kw = dict(D=D, group=G, sm_scale=scale, interpret=False)
    rows = []

    def emit(**row):
        row["device"] = jax.devices()[0].device_kind
        rows.append(row)
        print(json.dumps(row), flush=True)

    q4, k4, v4 = (x.reshape(1, S, -1, D) for x in (q, k, v))
    with jax.default_matmul_precision("highest"):
        ref = lambda q, k, v: attention_ref(q, k, v).astype(jnp.float32)
        f32 = [x.astype(jnp.float32) for x in (q4, k4, v4)]
        ref_o, vjp = jax.vjp(ref, *f32)
        ref_dq, ref_dk, ref_dv = vjp(do.astype(jnp.float32).reshape(
            ref_o.shape))
    xla = jax.jit(lambda q, k, v: _attend_xla(q, k, v, cfg, causal=True,
                                              window=0, kv_len=None))
    emit(kernel="xla_scan", pass_="fwd", ms=_ms(xla, q4, k4, v4))
    xla_grad = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        xla(q, k, v).astype(jnp.float32)), argnums=(0, 1, 2)))
    emit(kernel="xla_scan", pass_="fwd+bwd", ms=_ms(xla_grad, q4, k4, v4))

    o, lse = None, None
    for bq, bk in itertools.product(SIZES, SIZES):
        t = K._tiles(True, S, S, (bq, bk))
        fwd = jax.jit(lambda q, k, v, w, t=t: K._attend_fwd(q, k, v, w, t=t,
                                                           **kw))
        o_b, lse_b = fwd(q, k, v, win)
        emit(kernel="fwd", blk_q=bq, blk_k=bk, ms=_ms(fwd, q, k, v, win),
             err=_err(o_b, ref_o))
        if o is None:
            o, lse = o_b, lse_b
    di = jnp.sum(o.astype(jnp.float32).reshape(1, S, H, D)
                 * do.astype(jnp.float32).reshape(1, S, H, D), axis=-1)
    di = jnp.transpose(di, (0, 2, 1))[:, :, None, :]
    args = (q, k, v, do, lse, di, win)
    for name, call, want in (("dkv", K._attend_dkv, (ref_dk, ref_dv)),
                             ("dq", K._attend_dq, (ref_dq,))):
        for bq, bk in itertools.product(SIZES, SIZES):
            t = K._tiles(True, S, S, (bq, bk))
            fn = jax.jit(lambda *a, t=t, call=call: call(*a, t=t, **kw))
            got = fn(*args)
            got = got if isinstance(got, (list, tuple)) else (got,)
            emit(kernel=name, blk_q=bq, blk_k=bk, ms=_ms(fn, *args),
                 err=max(_err(g, w) for g, w in zip(got, want)))
    out = os.path.join(os.path.dirname(__file__), "..", "artifacts", "bench")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "flash_block_sweep.jsonl"), "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())

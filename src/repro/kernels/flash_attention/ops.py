"""Model-facing wrapper: where the kernel runs, its blocks, and per shard.

The kernel reads the model's (B, S, H, D) layout directly, so there is no
transpose on either side. Under an active mesh (``repro.sharding``) a
Mosaic custom call is not partitioned by GSPMD, so the kernel runs under
``jax.shard_map``: batch over the layout's data axes, heads over
``model`` where both H and KV divide it. Where the mesh divides neither
way, ``attention`` returns None and the caller keeps its XLA path — it
never runs a replicated kernel.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.flash_attention.kernel import flash_attention
from repro.obs.profiling import annotate_span
from repro.sharding import current_layout, current_mesh, data_axes

# blk_q = blk_k of all three kernels: each kernel was fastest at 1024 of
# {256, 512, 1024}^2 on a TPU v5e at starcoder2-3b's training shapes
# (benchmarks/flash_block_sweep.py). A sequence shorter than a block, or
# not a multiple of it, takes the largest block of at least MIN_BLOCK
# (halving) that divides it.
BLOCK = 1024
MIN_BLOCK = 128


def platform() -> str:
    """The platform the traced program compiles for: the active mesh's
    devices where there is one (a described TPU compiles the TPU path on
    a CPU host), else JAX's default backend."""
    mesh = current_mesh()
    if mesh is not None:
        return mesh.devices.flat[0].platform
    return jax.default_backend()


def tiles(seq_q: int, seq_k: int, head_dim: int) -> bool:
    """True where the shapes tile the kernel's blocks on a TPU."""
    return (seq_q % MIN_BLOCK == 0 and seq_k % MIN_BLOCK == 0
            and head_dim % 128 == 0)


def _fit(n: int) -> int:
    b = BLOCK
    while b > MIN_BLOCK and n % b:
        b //= 2
    return min(b, n)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window=0) -> Optional[jax.Array]:
    """q: (B, Sq, H, D); k/v: (B, Sk, KV, D) -> (B, Sq, H, D), or None
    where the active mesh cannot hold the kernel per shard."""
    B, Sq, H, _ = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    blocks = (_fit(Sq), _fit(Sk))
    interpret = platform() == "cpu"
    win = jnp.asarray(window, jnp.int32)

    def run(q, k, v, win):
        return flash_attention(q, k, v, causal=causal, window=win,
                               blocks=blocks, interpret=interpret)

    mesh = current_mesh()
    with annotate_span("kernel.flash_attention.pallas"):
        if mesh is None:
            return run(q, k, v, win)
        dax = data_axes(mesh, current_layout())
        dsz = 1
        for a in dax:
            dsz *= mesh.shape[a]
        heads = None
        if "model" in mesh.axis_names and "model" not in dax:
            m = mesh.shape["model"]
            if H % m or KV % m:
                return None
            heads = "model" if m > 1 else None
        if B % dsz:
            return None
        spec = P(dax if len(dax) > 1 else dax[0], None, heads, None)
        return jax.shard_map(run, mesh=mesh, in_specs=(spec, spec, spec, P()),
                             out_specs=spec, check_vma=False)(q, k, v, win)

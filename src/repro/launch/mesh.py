"""Production mesh construction.

Kept as FUNCTIONS (never module-level constants) so importing this module
never initializes jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init,
and smoke tests must keep seeing the single real CPU device.

Axis semantics (DESIGN.md §2):
  pod    inter-pod data parallelism over DCI links — the *transient
         revocation domain*: one pod = one revocable capacity block.
  data   intra-pod data parallelism + FSDP/ZeRO-1 shard axis.
  model  tensor parallelism (heads / d_ff / experts / vocab / ssm dims).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType

from repro.config import MeshConfig


def _auto_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with ``Auto`` axes: the model's ``shard_act``
    constraints and the GSPMD param shardings assume automatic axes, and
    ``jax.make_mesh`` defaults to ``Explicit`` ones."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(cfg: MeshConfig) -> jax.sharding.Mesh:
    """Arbitrary mesh from a MeshConfig (elastic sizes, tests)."""
    return _auto_mesh(cfg.shape, cfg.axis_names)


def single_device_mesh() -> jax.sharding.Mesh:
    """A (1, 1) mesh over the one real device (smoke tests under a mesh)."""
    return _auto_mesh((1, 1), ("data", "model"))


def survivor_mesh(n_pods_alive: int, *, data: int = 16, model: int = 16
                  ) -> jax.sharding.Mesh:
    """Mesh over the surviving pods after a revocation (elastic remesh).

    jax.make_mesh re-selects from *all* visible devices; in a real
    deployment the caller passes the surviving slice's devices explicitly —
    the shape logic is what the dry-run exercises.
    """
    if n_pods_alive < 1:
        raise ValueError("no pods alive")
    if n_pods_alive == 1:
        return _auto_mesh((data, model), ("data", "model"))
    return _auto_mesh((n_pods_alive, data, model), ("pod", "data", "model"))

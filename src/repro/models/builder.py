"""Model builder: family dispatch + abstract (allocation-free) init.

``build_model(cfg)`` returns a :class:`Model` with a uniform callable
surface, so the train/serve/dryrun layers never branch on family.
``abstract_params`` gives the Boxed tree with ShapeDtypeStruct leaves
(via ``jax.eval_shape``) used to derive shardings without allocating
anything — the dry-run path at 512 fake devices depends on this.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import layers as L
from repro.models import resnet, transformer

PyTree = Any


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[[jax.Array], PyTree]                # key -> Boxed tree
    apply: Callable[..., Tuple[jax.Array, jax.Array]]  # (raw_params, batch)
    init_cache: Optional[Callable[..., PyTree]] = None
    decode: Optional[Callable[..., Tuple[jax.Array, PyTree]]] = None
    init_paged_cache: Optional[Callable[..., PyTree]] = None
    decode_paged: Optional[Callable[..., Tuple[jax.Array, PyTree]]] = None

    def abstract_params(self) -> PyTree:
        """Boxed tree whose .value leaves are ShapeDtypeStructs."""
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        return jax.eval_shape(self.init, key)

    def init_for_serving(self, key: jax.Array) -> PyTree:
        """Raw params for serving, with the ``cast`` leaves held in
        ``cfg.dtype`` (:func:`repro.models.layers.unbox_for_compute`):
        the same logits at half the weight bytes. Built as one jitted
        program, so no eager per-layer trees stay alive beside the
        stacked ones and no float32 copy outlives the program."""
        return jax.jit(lambda k: L.unbox_for_compute(
            self.init(k), self.cfg.dtype))(key)


def cache_batch_axes(model: Model, max_len: int = 8,
                     enc_len: int = 0) -> PyTree:
    """Per-leaf batch-axis index of the decode cache, derived from the
    cache *layout* itself: the cache is shaped abstractly (``eval_shape``,
    no allocation) at two different batch sizes and the one axis whose
    extent scales with batch is the batch axis. Unlike shape matching
    against ``max_batch``, this cannot misfire when a non-batch dimension
    (layer count, heads, block size) happens to coincide with the batch
    size — both probes must differ on the batch axis and only there.
    """
    if model.init_cache is None:
        raise ValueError(f"{model.cfg.name}: family {model.cfg.family!r} "
                         "has no decode cache")
    b1, b2 = 3, 5            # coprime probes; any non-batch dim is constant
    c1 = jax.eval_shape(lambda: model.init_cache(b1, max_len,
                                                 enc_len=enc_len))
    c2 = jax.eval_shape(lambda: model.init_cache(b2, max_len,
                                                 enc_len=enc_len))

    def axis(a, b):
        diffs = [i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                 if x != y]
        if len(diffs) != 1:
            raise ValueError(f"cannot derive batch axis: shapes {a.shape} "
                             f"vs {b.shape} differ on axes {diffs}")
        return diffs[0]

    return jax.tree.map(axis, c1, c2)


def paged_cache_axes(model: Model, max_len: int = 8, *,
                     page_size: int = 4, num_pages: int = 8,
                     enc_len: int = 0) -> PyTree:
    """Per-leaf batch-axis of the PAGED decode cache. Same two-probe
    derivation as :func:`cache_batch_axes`, except leaves whose shape
    does NOT scale with batch — the physical page pools, which are
    shared across rows — map to the sentinel ``-1``
    (``repro.serving.paging.POOL_AXIS_SENTINEL``). Per-row leaves
    (page table, pos, recurrent states) still must differ on exactly
    one axis.
    """
    if model.init_paged_cache is None:
        raise ValueError(f"{model.cfg.name}: family {model.cfg.family!r} "
                         "has no paged decode cache")
    b1, b2 = 3, 5
    c1 = jax.eval_shape(lambda: model.init_paged_cache(
        b1, max_len, page_size=page_size, num_pages=num_pages,
        enc_len=enc_len))
    c2 = jax.eval_shape(lambda: model.init_paged_cache(
        b2, max_len, page_size=page_size, num_pages=num_pages,
        enc_len=enc_len))

    def axis(a, b):
        diffs = [i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                 if x != y]
        if not diffs:
            return -1                      # pool leaf: no batch axis
        if len(diffs) != 1:
            raise ValueError(f"cannot derive batch axis: shapes {a.shape} "
                             f"vs {b.shape} differ on axes {diffs}")
        return diffs[0]

    return jax.tree.map(axis, c1, c2)


def build_model(cfg: ModelConfig) -> Model:
    if cfg.family == "resnet":
        return Model(
            cfg=cfg,
            init=lambda key: resnet.init_params(cfg, key),
            apply=lambda p, batch, remat=False: resnet.forward(p, cfg, batch,
                                                               remat=remat),
        )
    return Model(
        cfg=cfg,
        init=lambda key: transformer.init_params(cfg, key),
        apply=lambda p, batch, remat=True: transformer.forward(p, cfg, batch,
                                                               remat=remat),
        init_cache=lambda batch, max_len, enc_len=0: transformer.init_decode_cache(
            cfg, batch, max_len, enc_len=enc_len),
        decode=lambda p, cache, batch: transformer.decode_step(p, cfg, cache,
                                                               batch),
        init_paged_cache=lambda batch, max_len, *, page_size, num_pages,
        enc_len=0: transformer.init_paged_decode_cache(
            cfg, batch, max_len, page_size=page_size, num_pages=num_pages,
            enc_len=enc_len),
        decode_paged=lambda p, cache, batch, advance=None:
        transformer.decode_step_paged(p, cfg, cache, batch, advance=advance),
    )

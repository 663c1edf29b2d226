"""Where the entry points keep JAX's persistent compilation cache.

A later process finds what an earlier one compiled only in the same
directory, so it is a fixed path: ``.jax_cache/`` at the checkout root. Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing here overrides it. Entry points
(``launch.serve``, ``launch.train``, ``chip_smoke.py``) call
:func:`use_compile_cache` before their first compile; importing this
module changes nothing.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir,
    ".jax_cache"))


def use_compile_cache() -> str:
    """Turn the persistent cache on at :data:`CACHE_DIR`, unless the
    environment already names a directory; returns the one in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR

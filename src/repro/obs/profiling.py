"""``jax.profiler`` bridge — device traces aligned with sim events.

``annotate_span(name)`` is the seam kernel dispatch and train steps wrap:
inside a jit trace its ``jax.named_scope`` puts the name into the HLO, so
it shows up on device timelines; at op-dispatch time its
``jax.profiler.TraceAnnotation`` marks the host thread under the same
name. The package's core modules stay jax-free: only code that already
runs jax imports this one.
"""
from __future__ import annotations

import contextlib
from typing import Iterator

import jax


@contextlib.contextmanager
def annotate_span(name: str) -> Iterator[None]:
    """Name a region for device profiling."""
    with jax.named_scope(name), jax.profiler.TraceAnnotation(name):
        yield

"""``jax.profiler`` bridge — names that reach the compiled program.

``jax.named_scope`` puts its name into the op_name metadata of every
instruction traced inside it, and that metadata survives into the
optimized HLO the device runs, so a trace reduction can attribute each
device op to the scope that produced it. Under ``jit`` it is the only
naming that does: a host ``TraceAnnotation`` opened while tracing marks
the trace, not the dispatch.

``region(name)`` names one of the closed set ``REGIONS`` of the train
step (``chipbench/regions.py`` splits device time by them);
``annotate_span(name)`` names anything else, such as a kernel
(``kernel.<name>.<impl>``). The package's core modules stay jax-free:
only code that already runs jax imports this one.
"""
from __future__ import annotations

import contextlib

import jax

# The train step's regions, a closed set as events.py's EV_* names are:
# model and train code name them through ``region``, and the benchmark
# reads device time by them.
REGIONS = {
    "embed": "token embedding",
    "layers": "the trunk's scan over stacked layers",
    "attn": "norm, q/k/v projections, attention and out-projection",
    "mlp": "norm and the dense or expert MLP",
    "head": "final norm, unembedding and the loss",
    "update": "gradient cast and clip, schedule, optimizer and apply",
}


def annotate_span(name: str) -> contextlib.AbstractContextManager:
    """Name a region of traced code in the compiled program's metadata."""
    return jax.named_scope(name)


def region(name: str) -> contextlib.AbstractContextManager:
    """``annotate_span`` for one of ``REGIONS``; any other name raises."""
    if name not in REGIONS:
        raise ValueError(f"unknown region {name!r} (known: "
                         f"{sorted(REGIONS)})")
    return annotate_span(name)

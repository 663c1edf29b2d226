"""Kernel perf trajectory: wall time per kernel x shape x impl, normalized
against the analytic roofline (``repro.roofline.kernel_roofline``), plus
pallas-vs-jnp-ref speedup. Emits ``BENCH_kernels.json`` beside the table
goldens via the same ``emit(stats=)`` side channel.

    PYTHONPATH=src python -m benchmarks.kernel_bench           # full sweep
    PYTHONPATH=src python -m benchmarks.kernel_bench --smoke   # CI subset

Numbers are recorded **per device kind** (``stats["meta"]``): on this CPU
container the pallas impls run in interpret mode, so absolute wall times
mean nothing across machines — which is why every entry also carries
``norm_wall`` = wall / calib, where ``calib`` is a fixed matmul timed in
the same process. The trajectory regression test
(``tests/test_bench_trajectory.py``) compares ``norm_wall`` against the
committed baseline with a 25% tolerance band, so "this kernel got slower
relative to this machine's raw matmul throughput" fails CI while machine-
to-machine speed differences cancel out. ``roofline_frac`` (t_bound /
measured) is the cross-device figure of merit the DeviceProfile
calibration will eventually consume.
"""
from __future__ import annotations

import functools
import os
import sys
import time
from typing import Callable, Dict, List, Tuple

import numpy as np

from benchmarks.common import emit

import jax
import jax.numpy as jnp

from repro import obs
from repro.obs.export import perf_entry
from repro.roofline import kernel_roofline

REPS = 5
DTYPE = jnp.float32
_RNG = np.random.default_rng(0)


def _arr(shape):
    return jnp.asarray(_RNG.normal(size=shape), DTYPE)


def _time(fn: Callable[[], jax.Array], reps: int = REPS) -> float:
    """Best-of-reps wall seconds; first call (compile/trace) discarded."""
    out = fn()
    jax.tree.map(lambda x: x.block_until_ready(), out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        jax.tree.map(lambda x: x.block_until_ready(), out)
        best = min(best, time.perf_counter() - t0)
    return best


@functools.lru_cache(maxsize=1)
def calibration_s() -> float:
    """Fixed fp32 matmul workload timed in-process: the machine-speed
    yardstick every entry's ``norm_wall`` divides by."""
    a = _arr((512, 512))
    b = _arr((512, 512))
    f = jax.jit(lambda x, y: x @ y)
    return _time(lambda: f(a, b))


# ---------------------------------------------------------------------------
# Cases: (label, pallas thunk, ref thunk, analytic flops, hbm bytes)
# ---------------------------------------------------------------------------
# FLOP models count the two MXU contractions per attention variant
# (QK^T + PV; halved under a causal mask), the three per-chunk
# contractions of the rwkv6 kernel, and the intra-chunk + state terms of
# the SSD dual form. HBM bytes are mandatory traffic: inputs + outputs
# once each (the kernels stream KV through VMEM exactly once).

Case = Tuple[str, Callable[[], jax.Array], Callable[[], jax.Array],
             float, float]


def _flash_case(B, H, KV, S, D, causal=True, blk=128) -> Case:
    from repro.kernels.flash_attention import attention_ref, flash_attention
    q = _arr((B, S, H, D))
    k, v = _arr((B, S, KV, D)), _arr((B, S, KV, D))
    flops = 4.0 * B * H * S * S * D * (0.5 if causal else 1.0)
    bytes_ = (q.size + 2 * k.size + q.size) * q.dtype.itemsize
    interp = jax.default_backend() == "cpu"
    pallas = lambda: flash_attention(q, k, v, causal=causal,
                                     blocks=(blk, blk), interpret=interp)
    ref_f = jax.jit(functools.partial(attention_ref, causal=causal))
    ref = lambda: ref_f(q, k, v)
    return (f"flash/B{B}H{H}KV{KV}S{S}D{D}", pallas, ref, flops, bytes_)


def _decode_case(B, H, KV, S, D, blk_k=256) -> Case:
    from repro.kernels.decode_attention import (decode_attention,
                                                decode_attention_ref)
    q = _arr((B, H, D))
    k, v = _arr((B, KV, S, D)), _arr((B, KV, S, D))
    lengths = jnp.full((B,), S, jnp.int32)
    flops = 4.0 * B * H * S * D
    bytes_ = (q.size + 2 * k.size + q.size) * q.dtype.itemsize
    interp = jax.default_backend() == "cpu"
    pallas = lambda: decode_attention(q, k, v, lengths, blk_k=blk_k,
                                      interpret=interp)
    ref_f = jax.jit(decode_attention_ref)
    ref = lambda: ref_f(q, k, v, lengths)
    return (f"decode/B{B}H{H}KV{KV}S{S}D{D}", pallas, ref, flops, bytes_)


def _ssd_case(B, H, S, P, N, Q) -> Case:
    from repro.kernels.ssd_scan import ssd_ref, ssd_scan
    xdt = _arr((B, H, S, P))
    Bc, Cc = _arr((B, S, N)), _arr((B, S, N))
    dA = -jnp.asarray(_RNG.uniform(0.01, 0.5, size=(B, H, S)), DTYPE)
    # per chunk: C@B^T (Q*Q*N), (C@B)@x (Q*Q*P), state in/out (2*Q*N*P)
    flops = 2.0 * B * H * S * (Q * N + Q * P + 2 * N * P)
    bytes_ = (xdt.size * 2 + Bc.size + Cc.size + dA.size) * xdt.dtype.itemsize
    interp = jax.default_backend() == "cpu"
    pallas = lambda: ssd_scan(xdt, Bc, Cc, dA, chunk=Q, interpret=interp)
    ref_f = jax.jit(ssd_ref)
    ref = lambda: ref_f(xdt, Bc, Cc, dA)
    return (f"ssd/B{B}H{H}S{S}P{P}N{N}Q{Q}", pallas, ref, flops, bytes_)


def _rwkv_case(B, H, S, D, L) -> Case:
    from repro.kernels.rwkv6 import rwkv6_ref, rwkv6_scan
    r, k, v = (_arr((B, H, S, D)) for _ in range(3))
    w = jnp.asarray(np.exp(-np.exp(_RNG.uniform(-4, 1, size=(B, H, S, D)))),
                    DTYPE)
    u = _arr((H, D))
    # per chunk: pairwise A (L*L*D), r@S + state update (2*L*D*D)
    flops = 2.0 * B * H * S * (L * D + 2 * D * D)
    bytes_ = (4 * r.size + r.size + B * H * D * D) * 4  # fp32 in/out + state
    interp = jax.default_backend() == "cpu"
    pallas = lambda: rwkv6_scan(r, k, v, w, u, chunk=L, interpret=interp)
    ref_f = jax.jit(rwkv6_ref)
    ref = lambda: ref_f(r, k, v, w, u)
    return (f"rwkv6/B{B}H{H}S{S}D{D}L{L}", pallas, ref, flops, bytes_)


def _cases(smoke: bool) -> List[Case]:
    if smoke:
        return [
            _flash_case(1, 2, 2, 128, 32, blk=64),
            _decode_case(2, 4, 2, 256, 32, blk_k=128),
            _ssd_case(1, 2, 128, 16, 16, 32),
            _rwkv_case(1, 2, 64, 16, 16),
        ]
    return [
        _flash_case(1, 4, 4, 256, 64),
        _flash_case(1, 8, 2, 512, 64),          # GQA
        _decode_case(4, 8, 2, 1024, 64),
        _decode_case(2, 16, 16, 2048, 64),
        _ssd_case(1, 4, 512, 64, 64, 64),
        _rwkv_case(1, 4, 256, 64, 64),
    ]


def collect(smoke: bool, recorder=None) -> Tuple[List[Dict], Dict]:
    rec = recorder if recorder is not None else obs.NULL
    calib = calibration_s()
    dev = jax.devices()[0]
    meta = {
        "backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", "unknown"),
        "interpret": jax.default_backend() == "cpu",
        "calib_ms": calib * 1e3,
        "smoke": smoke,
    }
    rows: List[Dict] = []
    entries: Dict[str, Dict] = {}
    for label, pallas, ref, flops, hbm_bytes in _cases(smoke):
        roof = kernel_roofline(flops, hbm_bytes)
        t_ref = _time(ref)
        t_pal = _time(pallas)
        for impl, wall in (("pallas", t_pal), ("ref", t_ref)):
            entries[f"{label}/{impl}"] = perf_entry(
                wall, calib, flops=flops, hbm_bytes=hbm_bytes,
                roofline_s=roof.t_bound,
                roofline_frac=roof.achieved_fraction(wall),
                bottleneck=roof.bottleneck,
                speedup_vs_ref=t_ref / wall)
            if rec.enabled:
                # best-of-reps wall as a span: the timeline shows each
                # case's measured kernel time, not the harness overhead
                t_now = rec.now()
                rec.span_at(f"kernel.{label}.{impl}", cat=obs.CAT_BENCH,
                            track=label.split("/")[0], t_wall=t_now,
                            dur_wall=wall, norm_wall=wall / calib,
                            roofline_frac=roof.achieved_fraction(wall))
                rec.metrics.histogram("kernel_wall_ms",
                                      impl=impl).observe(wall * 1e3)
        rows.append({
            "kernel": label,
            "ref_ms": f"{t_ref*1e3:.3f}",
            "pallas_ms": f"{t_pal*1e3:.3f}",
            "speedup": f"{t_ref/t_pal:.2f}x",
            "roofline_ms": f"{roof.t_bound*1e3:.4f}",
            "roof_frac(pallas)": f"{roof.achieved_fraction(t_pal):.2e}",
            "bound": roof.bottleneck,
        })
    return rows, {"meta": meta, "entries": entries}


def run(smoke: bool = False, events: str = None) -> dict:
    smoke = smoke or os.environ.get("KERNEL_BENCH_SMOKE", "") == "1"
    rec = obs.Recorder(meta={"bench": "kernels", "smoke": smoke}) \
        if events else None
    rows, stats = collect(smoke, recorder=rec)
    mode = "smoke" if smoke else "full"
    notes = (f"[{mode}] backend={stats['meta']['backend']} "
             f"interpret={stats['meta']['interpret']} "
             f"calib={stats['meta']['calib_ms']:.3f}ms — pallas wall times "
             "are interpret-mode on CPU (semantics, not speed); "
             "roofline_frac is vs the v5e-class analytic bound")
    if rec is not None:
        rec.flush(events)
        from repro.obs.export import write_chrome_trace
        write_chrome_trace(rec.events, events + ".trace.json", clock="wall",
                           meta=rec.meta)
        print(f"[obs] events -> {events}; timeline -> {events}.trace.json")
    return emit("BENCH_kernels", rows, notes=notes, stats=stats)


def _cli_events(argv) -> str:
    if "--events" in argv:
        return argv[argv.index("--events") + 1]
    return None


if __name__ == "__main__":
    run(smoke="--smoke" in sys.argv, events=_cli_events(sys.argv))

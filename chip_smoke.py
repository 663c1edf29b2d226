#!/usr/bin/env python3
"""Drive the main path once on a TPU and check what comes out.

    python3 chip_smoke.py [--seed N]            # one chip
    python3 chip_smoke.py --chips 4 [--seed N]  # four chips: sharded step

One chip, in one process:

- ``weights``: starcoder2-3b at its published widths, random weights
  from ``--seed``, built as ``launch.serve --full`` builds them (the
  weights every layer casts to bf16 are held in bf16).
- ``serve_dense``, ``serve_paged``: 16 requests (prompts of 128-512
  tokens, 64 new tokens each) through ``ServeEngine`` as ``launch.serve``
  builds it, 8 slots of 1024 positions, first on the dense cache, then
  on the paged one (16 positions a page). Every request completes, and
  the paged engine emits the dense engine's tokens.
- ``logits``: on one cache state (8 prompts prefilled through the
  engine's compiled prefill), the next-token logits of the Pallas
  decode-attention kernel against the XLA path, and of prefill-then-
  decode against one full forward (``model.apply``) of the same prompts.
- ``train``: resnet32-cifar10 at its published size (the paper's
  workload), batch 128: 20 ``Trainer`` steps with ``CheckpointManager``
  saves, then a restore. Losses are finite, and the restored state
  equals the saved one bit for bit.

Four chips (``--chips 4``): one momentum step of ``make_train_step``
with ``param_shardings`` under ``use_mesh``, starcoder2-3b widths cut to
2 layers, batch 8 of 512 tokens; layout ``tp`` on a 2x2 mesh and
``fsdp`` over all 4 chips, each against the same step on one chip.

Each phase prints one JSON line: wall seconds, compile seconds (trace,
lowering and compile or cache read, as JAX reports them), persistent-
cache hits and misses, and each device's ``peak_bytes_in_use``. The
last line is ``{"ok": true, "device": {...}}``. Without a TPU the
script exits non-zero before any work and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

# Both sides of each logits comparison compute in bf16 (8 significant
# bits, unit roundoff 2**-9 ~ 2e-3) but round at different points: the
# XLA decode path rounds scores and probabilities to bf16 where the
# kernel keeps them in f32, and the full forward attends over the whole
# prompt in one einsum where decode reads a cache. Each of the 30
# residual layers adds a few roundoffs of disagreement, so about 1e-2
# relative (L2 over the logits) bounds rounding; a wrong mask, position
# or cache row moves the logits by order 1.
LOGITS_REL_TOL = 3e-2
# Sharded and one-chip steps run the same bf16 matmuls with the
# contractions split across chips and summed in another order; the
# gradient is then clipped and applied by momentum SGD, linearly. One
# step's update agrees to a few bf16 roundoffs per layer (5.6e-3 fsdp,
# 7.1e-3 tp on four host CPU devices at these widths with a 4096 vocab);
# a sharding that drops or double-counts a shard is off by order 1.
STEP_REL_TOL = 3e-2


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (a persistent
    cache read counts as the compile), and the cache's hits and misses
    (a miss is written back only if it took over a second to compile)."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def rel_l2(a, b) -> float:
    """||a - b|| / ||b|| over all leaves of two matching trees."""
    num = den = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x = np.asarray(x, np.float64)
        y = np.asarray(y, np.float64)
        num += float(np.sum((x - y) ** 2))
        den += float(np.sum(y ** 2))
    return float(np.sqrt(num / den))


def run_phase(name, fn, clock, failures):
    """Run one phase; print its JSON line; return what it returned."""
    c0, h0, m0 = clock.snapshot()
    t0 = time.monotonic()
    line = {"phase": name}
    out = None
    try:
        out = fn()
        line.update(out[0] if isinstance(out, tuple) else out)
        line["ok"] = True
    except Exception as e:                     # report, then fail at the end
        traceback.print_exc()
        line.update(ok=False, error=f"{type(e).__name__}: {e}"[:2000])
        failures.append(name)
    c1, h1, m1 = clock.snapshot()
    line.update(wall_s=time.monotonic() - t0, compile_s=c1 - c0,
                cache_hits=h1 - h0, cache_misses=m1 - m0,
                peak_bytes_in_use=[d.memory_stats()["peak_bytes_in_use"]
                                   for d in jax.local_devices()])
    print(json.dumps(line), flush=True)
    return out


# ---------------------------------------------------------------------------
# One chip: serving
# ---------------------------------------------------------------------------

N_REQUESTS, NEW_TOKENS, MAX_BATCH, MAX_LEN, PAGE = 16, 64, 8, 1024, 16


def serve_args(seed: int, cache_impl: str):
    from repro.launch import serve
    return serve.build_parser().parse_args([
        "--full", "--arch", "starcoder2-3b", "--seed", str(seed),
        "--max-batch", str(MAX_BATCH), "--max-len", str(MAX_LEN),
        "--cache-impl", cache_impl, "--page-size", str(PAGE)])


def weights_phase(seed):
    from repro.launch import serve
    model, params = serve.load_model(serve_args(seed, "dense"))
    jax.block_until_ready(params)
    leaves = jax.tree.leaves(params)
    return ({"arch": model.cfg.name, "params": sum(x.size for x in leaves),
             "weight_bytes": sum(x.nbytes for x in leaves),
             "dtypes": sorted({str(x.dtype) for x in leaves})},
            model, params)


def prompts_for(seed, vocab):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=int(rng.integers(128, 513))).tolist()
            for _ in range(N_REQUESTS)]


def serve_phase(model, params, prompts, cache_impl, seed):
    from repro.launch import serve
    from repro.serving import FIFOQueue, Request, ServeEngine
    eng = ServeEngine(model, params, queue=FIFOQueue(),
                      **serve.engine_kwargs(serve_args(seed, cache_impl)))
    reqs = [Request(rid=i, prompt=p, max_new_tokens=NEW_TOKENS)
            for i, p in enumerate(prompts)]
    for r in reqs:
        check(eng.submit(r), f"request {r.rid} rejected at submit")
    steps = eng.run_to_completion()
    done = [r for r in reqs if r.done and len(r.generated) == NEW_TOKENS]
    check(len(done) == len(reqs),
          f"{len(done)} of {len(reqs)} requests completed")
    return ({"cache_impl": cache_impl, "requests": len(reqs),
             "completed": len(done), "engine_steps": steps,
             "tokens_decoded": eng.tokens_decoded,
             "prompt_tokens": sum(len(p) for p in prompts)},
            eng, [r.generated for r in reqs])


def logits_phase(model, params, prompts, dense):
    """Prefill prompt[:-1] of 8 prompts through the dense engine's
    compiled prefill, then compare the logits for prompt[-1]."""
    from repro.serving import ServeEngine
    from repro.serving.engine import with_impls
    rows = prompts[:MAX_BATCH]
    eng = ServeEngine(model, params, max_batch=MAX_BATCH, max_len=MAX_LEN,
                      prefill_block=dense.prefill_block,
                      shared_fns=dense.shared_fns)
    T = eng.prefill_block
    ctx = [p[:-1] for p in rows]
    cache = eng.cache
    for start in range(0, max(map(len, ctx)), T):
        block = np.zeros((MAX_BATCH, T), np.int32)
        n_valid = np.zeros((MAX_BATCH,), np.int32)
        for i, c in enumerate(ctx):
            k = max(0, min(T, len(c) - start))
            block[i, :k] = c[start:start + k]
            n_valid[i] = k
        # Wait for each block as the engine does: the step programs do
        # not donate the cache, so every call dispatched ahead holds a
        # cache of its own (32 in flight came to 13.9 GB on the chip).
        cache = jax.block_until_ready(eng.prefill_fn(
            params, cache, jnp.asarray(block), jnp.asarray(n_valid)))
    last = jnp.asarray([[p[-1]] for p in rows], jnp.int32)

    def decode_logits(m):
        fn = jax.jit(lambda p, c, t: m.decode(p, c, {"tokens": t})[0])
        return np.asarray(fn(params, cache, last)[:, 0].astype(jnp.float32))

    xla = decode_logits(model)
    pallas = decode_logits(with_impls(model, attn_impl="pallas"))
    S = max(map(len, rows))
    padded = np.zeros((MAX_BATCH, S), np.int32)
    for i, p in enumerate(rows):
        padded[i, :len(p)] = p
    full = jax.jit(lambda p, t: model.apply(p, {"tokens": t}, remat=False)[0])(
        params, jnp.asarray(padded))
    ref = np.stack([np.asarray(full[i, len(p) - 1].astype(jnp.float32))
                    for i, p in enumerate(rows)])
    out = {"rows": len(rows), "tol": LOGITS_REL_TOL,
           "pallas_vs_xla": rel_l2(pallas, xla),
           "decode_vs_forward": rel_l2(xla, ref),
           "argmax_agree_pallas_xla": int((pallas.argmax(-1)
                                           == xla.argmax(-1)).sum()),
           "argmax_agree_decode_forward": int((xla.argmax(-1)
                                               == ref.argmax(-1)).sum())}
    for k in ("pallas_vs_xla", "decode_vs_forward"):
        check(np.isfinite(out[k]) and out[k] < LOGITS_REL_TOL,
              f"{k}: relative L2 {out[k]} >= {LOGITS_REL_TOL}")
    return out


# ---------------------------------------------------------------------------
# One chip: training
# ---------------------------------------------------------------------------

def train_phase(seed):
    from repro.config import (OptimizerConfig, ScheduleConfig, TrainConfig,
                              get_config)
    from repro.core.checkpoint import CheckpointManager
    from repro.data.pipeline import ShardedDataset
    from repro.models.builder import build_model
    from repro.train.trainer import Trainer

    cfg = get_config("resnet32-cifar10")
    model = build_model(cfg)
    tcfg = TrainConfig(optimizer=OptimizerConfig(name="momentum", lr=0.1),
                       schedule=ScheduleConfig(kind="step"),
                       checkpoint_every=10, seed=seed)
    ds = ShardedDataset(cfg, global_batch=128, seq_len=0, seed=seed)
    with tempfile.TemporaryDirectory() as d:
        ckpt = CheckpointManager(d)
        trainer = Trainer(model, tcfg, ds, ckpt, log_every=1)
        state = trainer.init_or_restore(jax.random.key(seed))
        state = trainer.fit(state, 20)
        step, restored, _ = ckpt.restore_latest()
    losses = [m["loss"] for m in trainer.metrics_log]
    check(len(losses) == 20 and np.all(np.isfinite(losses)),
          f"losses not all finite: {losses}")
    check(step == 20 == int(state.step), f"restored step {step}")
    same = jax.tree.map(
        lambda a, b: a.dtype == b.dtype and np.array_equal(
            np.asarray(a), np.asarray(b)), state, restored)
    check(all(jax.tree.leaves(same)), "restored state differs from saved")
    return {"arch": cfg.name, "params": cfg.param_count(), "batch": 128,
            "steps": 20, "loss_first": losses[0], "loss_last": losses[-1],
            "restored_step": step, "restored_bitwise_equal": True}


# ---------------------------------------------------------------------------
# Four chips: the sharded train step
# ---------------------------------------------------------------------------

def sharded_setup(seed: int = 0):
    """The 2-layer starcoder2-3b model, its momentum config, and a batch
    of 8 x 512 tokens."""
    from repro.config import (OptimizerConfig, ScheduleConfig, TrainConfig,
                              get_config)
    from repro.data.pipeline import ShardedDataset
    from repro.models.builder import build_model
    cfg = get_config("starcoder2-3b").replace(num_layers=2)
    tcfg = TrainConfig(optimizer=OptimizerConfig(name="momentum", lr=0.1),
                       schedule=ScheduleConfig(kind="constant",
                                               warmup_steps=1),
                       checkpoint_every=0, seed=seed)
    batch = ShardedDataset(cfg, global_batch=8, seq_len=512,
                           seed=seed).global_batch_at(0)
    return build_model(cfg), tcfg, batch


def sharded_step(model, tcfg, boxed, batch, mesh, layout):
    """``make_train_step`` with ``param_shardings`` for ``layout``,
    jitted with the state and batch placed on ``mesh``."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.specs import batch_shardings
    from repro.sharding import param_shardings
    from repro.train.step import TrainState, make_train_step
    tcfg = dataclasses.replace(tcfg, layout=layout)
    shard = param_shardings(boxed, model.cfg, mesh, layout=layout)
    rep = NamedSharding(mesh, P())
    state_shard = TrainState(params=shard, opt={"mu": shard}, step=rep)
    batch_shard = batch_shardings(batch, mesh, layout)
    step = jax.jit(make_train_step(model, tcfg, param_shardings=shard),
                   in_shardings=(state_shard, batch_shard, rep),
                   out_shardings=(state_shard, None))
    return step, state_shard, batch_shard


def four_chip_phase(seed):
    from repro.config import MeshConfig
    from repro.launch.mesh import make_mesh
    from repro.models import layers as L
    from repro.sharding import use_mesh
    from repro.train.step import init_state, make_train_step

    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, need 4")
    model, tcfg, batch = sharded_setup(seed)
    boxed = jax.jit(model.init)(jax.random.key(seed))
    state0 = init_state(model, tcfg, None, unboxed_params=L.unbox(boxed))
    one, m1 = jax.jit(make_train_step(model, tcfg))(state0, batch)
    upd1 = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                        one.params, state0.params)
    out = {"arch": model.cfg.name, "layers": model.cfg.num_layers,
           "batch": [8, 512], "tol": STEP_REL_TOL,
           "one_chip": {"loss": float(m1["loss"]),
                        "grad_norm": float(m1["grad_norm"])}}
    for layout, mesh_cfg in (("tp", MeshConfig(data=2, model=2)),
                             ("fsdp", MeshConfig(data=4, model=1))):
        mesh = make_mesh(mesh_cfg)
        step, state_shard, batch_shard = sharded_step(
            model, tcfg, boxed, batch, mesh, layout)
        with use_mesh(mesh, layout):
            new, m = step(jax.device_put(state0, state_shard),
                          jax.device_put(batch, batch_shard),
                          jnp.float32(1.0))
        upd = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                           new.params, state0.params)
        wi = new.params["layers"]["mlp"]["wi"]
        res = {"mesh": dict(mesh.shape),
               "loss": float(m["loss"]), "grad_norm": float(m["grad_norm"]),
               "loss_rel": abs(float(m["loss"]) / float(m1["loss"]) - 1),
               "update_rel_l2": rel_l2(upd, upd1),
               "wi_devices": len(wi.sharding.device_set),
               "wi_shard_shape": list(wi.addressable_shards[0].data.shape)}
        out[layout] = res
        check(res["wi_devices"] == 4, f"{layout}: wi on {res['wi_devices']} "
              "devices")
        check(res["loss_rel"] < STEP_REL_TOL and
              res["update_rel_l2"] < STEP_REL_TOL,
              f"{layout} differs from one chip: {res}")
    return out


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    clock = CompileClock()
    failures = []
    if args.chips == 4:
        run_phase("sharded_train_4chips",
                  lambda: four_chip_phase(args.seed), clock, failures)
    else:
        got = run_phase("weights", lambda: weights_phase(args.seed), clock,
                        failures)
        if got is not None:
            _, model, params = got
            prompts = prompts_for(args.seed, model.cfg.vocab_size)
            dense = run_phase("serve_dense", lambda: serve_phase(
                model, params, prompts, "dense", args.seed), clock, failures)

            def paged_phase():
                line, eng, toks = serve_phase(model, params, prompts,
                                              "paged", args.seed)
                same = sum(a == b for a, b in zip(toks, dense[2]))
                line["matches_dense"] = same
                check(same == len(toks),
                      f"paged tokens differ from dense in "
                      f"{len(toks) - same} of {len(toks)} requests")
                return line

            if dense is not None:
                run_phase("serve_paged", paged_phase, clock, failures)
                run_phase("logits", lambda: logits_phase(
                    model, params, prompts, dense[1]), clock, failures)
            del params, got, dense
        run_phase("train", lambda: train_phase(args.seed), clock, failures)

    if failures:
        print(f"chip_smoke: failed phases: {failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

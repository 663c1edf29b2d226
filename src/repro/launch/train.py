"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

Single-process end-to-end training with the full transient runtime wired
in: sharded deterministic data pipeline, masked elastic membership
(sparse mapping), adaptive LR, master-less checkpointing, and an optional
revocation trace (either a file of events or Monte-Carlo lifetimes drawn
from the paper-calibrated distributions).

``--elastic`` runs ``ElasticRuntime``, otherwise the static ``Trainer``;
both run the one train step (``make_train_step``) through the same
per-step code (``Trainer.run_step``). Here the mesh is the host CPU and
reduced configs make the loop runnable in seconds.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.config import (OptimizerConfig, ScheduleConfig, TrainConfig,
                          get_config, list_archs)
from repro.core import (CheckpointManager, ElasticRuntime, RevocationEvent,
                        SparseCluster)
from repro.core.transient import LIFETIMES
from repro.data.pipeline import ShardedDataset
from repro.launch.compile_cache import use_compile_cache
from repro.launch.obs_args import (add_obs_args, finalize_recorder,
                                   recorder_from_args)
from repro.models.builder import build_model
from repro.train.step import init_state
from repro.train.trainer import Trainer


def build_trace(args, rng: np.random.Generator):
    """Revocation/join events: explicit schedule or sampled lifetimes."""
    events = []
    if args.join_every:
        for i in range(1, args.slots):
            events.append(RevocationEvent(step=i * args.join_every, slot=i,
                                          kind="join"))
    if args.revoke_at is not None:
        events.append(RevocationEvent(step=max(0, args.revoke_at - 1),
                                      slot=0, kind="warn"))
        events.append(RevocationEvent(step=args.revoke_at, slot=0,
                                      kind="revoke"))
    if args.monte_carlo:
        # sample a lifetime per initially-active slot; convert to steps via
        # the configured steps/sec so traces match the paper's timescales
        life = LIFETIMES[args.server_kind]
        for s in range(args.initial_workers):
            t_s = life.sample(rng, 1)[0]
            step = int(t_s * args.steps_per_sec)
            if step < args.steps:
                events.append(RevocationEvent(step=max(0, step - 1), slot=s,
                                              kind="warn"))
                events.append(RevocationEvent(step=step, slot=s,
                                              kind="revoke"))
    return events


def run_gym(args) -> None:
    """The ``--gym --trace ...`` path: replay a market trace end-to-end.

    A ``TransientGym`` plans the fleet against the trace (with the chosen
    online policy replanning at decision epochs), then trains the
    realized membership timeline with the masked elastic runtime and
    reports the ledger — the same schema the MC engine summarizes to,
    which is what ``gym/validate.py`` pins the two against.
    """
    from repro.core.policy import (GreedyCheapest, LookaheadMC,
                                   PolicyDecision, StaticPolicy)
    from repro.gym import TransientGym
    from repro.traces import load_trace

    trace = load_trace(args.trace, seed=args.seed)
    if args.policy == "static":
        policy = StaticPolicy(PolicyDecision(args.server_kind,
                                             args.initial_workers))
    elif args.policy == "greedy":
        policy = GreedyCheapest(n_workers=args.initial_workers)
    else:
        policy = LookaheadMC(seed=args.seed)
    rec = recorder_from_args(
        args, meta={"driver": "gym", "trace": args.trace,
                    "policy": args.policy, "arch": args.arch})
    gym = TransientGym(trace, policy, total_steps=args.gym_total_steps,
                       epoch_s=args.gym_epoch_s, refill=args.policy != "static",
                       seed=args.seed, recorder=rec)
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    t0 = time.monotonic()
    ledger = gym.run(arch=args.arch, train_steps=args.steps,
                     seq_len=args.seq_len,
                     async_updates=args.gym_async_updates, ckpt=ckpt)
    out = ledger.to_dict()
    out["wall_s"] = round(time.monotonic() - t0, 2)
    del out["epochs"], out["schedule"]          # keep stdout scannable
    out["n_epochs"] = len(ledger.epochs)
    out["n_events"] = len(ledger.schedule)
    out.update(finalize_recorder(args, rec, clock="sim"))
    print(json.dumps(out, indent=1))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="starcoder2-3b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "momentum"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=100)
    # elastic / transient options
    ap.add_argument("--elastic", action="store_true",
                    help="use slot-masked elastic runtime (sparse mapping)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--initial-workers", type=int, default=1)
    ap.add_argument("--join-every", type=int, default=0,
                    help="fill one slot every N steps (paper Fig 5)")
    ap.add_argument("--revoke-at", type=int, default=None)
    ap.add_argument("--monte-carlo", action="store_true",
                    help="sample revocations from paper lifetime CDFs")
    ap.add_argument("--server-kind", default="K80")
    ap.add_argument("--steps-per-sec", type=float, default=4.5)
    ap.add_argument("--naive-lr", action="store_true",
                    help="disable adaptive LR (paper's TF default)")
    ap.add_argument("--seed", type=int, default=0)
    # gym: trace-driven end-to-end replay (market trace -> real training)
    ap.add_argument("--gym", action="store_true",
                    help="replay a market trace through the training gym")
    ap.add_argument("--trace", default="calm",
                    help="trace file (.jsonl/.npz) or synthetic name "
                         "(calm|volatile|bursty)")
    ap.add_argument("--policy", default="static",
                    choices=["static", "greedy", "lookahead"])
    ap.add_argument("--gym-total-steps", type=int, default=64_000,
                    help="virtual workload the trace replay simulates "
                         "(--steps real steps are trained against it)")
    ap.add_argument("--gym-epoch-s", type=float, default=1800.0)
    ap.add_argument("--gym-async-updates", type=int, default=0,
                    help=">0: also replay through the async-PS simulator "
                         "for the staleness histogram")
    add_obs_args(ap)
    args = ap.parse_args()
    use_compile_cache()

    if args.gym:
        run_gym(args)
        return

    cfg = get_config(args.arch, reduced=args.reduced)
    model = build_model(cfg)
    tcfg = TrainConfig(
        optimizer=OptimizerConfig(name=args.optimizer, lr=args.lr,
                                  adaptive_lr=not args.naive_lr,
                                  base_workers=1),
        schedule=ScheduleConfig(kind="cosine", warmup_steps=20,
                                total_steps=args.steps),
        checkpoint_every=args.checkpoint_every,
        seed=args.seed)
    ds = ShardedDataset(cfg, global_batch=args.global_batch,
                        seq_len=args.seq_len, seed=args.seed)
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

    rec = recorder_from_args(
        args, meta={"driver": "elastic" if args.elastic else "trainer",
                    "arch": args.arch, "steps": args.steps})
    t0 = time.monotonic()
    if args.elastic:
        cluster = SparseCluster(max_slots=args.slots)
        for s in range(args.initial_workers):
            cluster.fill_and_activate(s, 0, kind=args.server_kind)
        rt = ElasticRuntime(model, tcfg, ds, cluster, ckpt, recorder=rec)
        rt.add_events(build_trace(args, np.random.default_rng(args.seed)))
        state = init_state(model, tcfg, jax.random.key(args.seed))
        state = rt.run(state, args.steps)
        log = rt.metrics_log
    else:
        trainer = Trainer(model, tcfg, ds, ckpt, recorder=rec)
        state = trainer.init_or_restore()
        metrics = {}
        state = trainer.fit(state, args.steps,
                            on_step=lambda s, m: metrics.update(m))
        log = trainer.metrics_log

    wall = time.monotonic() - t0
    first, last = log[0], log[-1]
    out = {
        "arch": args.arch, "steps": args.steps, "wall_s": round(wall, 2),
        "loss_first": round(float(first["loss"]), 4),
        "loss_last": round(float(last["loss"]), 4),
        "elastic": args.elastic,
        "final_step": int(state.step) if hasattr(state, "step") else None,
    }
    out.update(finalize_recorder(args, rec, clock="sim"))
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()

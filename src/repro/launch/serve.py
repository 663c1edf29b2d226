"""Serving driver: ``python -m repro.launch.serve --arch <id> [...]``.

Batched decode with the slot-based continuous-batching engine. Two
workload modes:

- default: ``--requests N`` synthetic prompts submitted up front (waves:
  more requests than slots) — the original admission/retire exercise;
- ``--trace``: replay a seeded request trace (``serve-diurnal`` /
  ``serve-bursty`` from ``traces.requests``, or a ``.jsonl`` path) on an
  accelerated virtual clock, with SLO-aware queueing and optionally a
  mid-trace revocation (``--revoke-at FRAC`` fires ``revoke_slot``;
  ``--warn-at FRAC`` begins a graceful drain instead).

With ``--replicas N`` (or ``--autoscale`` / ``--monitor`` / ``--report``)
the driver runs a ``ServeCluster`` instead of a single engine: replicas
share compiled steps, revocations warn/fire whole replicas (drain +
page-ship/replay migration onto survivors), ``--monitor`` attaches the
SLO burn-rate monitor whose alerts ``--autoscale`` consumes as a
first-class scale-up signal, and ``--report`` renders the run's
time-series + alerts + per-replica summary as a self-contained HTML ops
report (``--series-out`` exports the raw sampled series as JSONL).

Throughput, TTFT/TPOT percentiles, attainment, alerts, and artifact
paths print as JSON.
"""
from __future__ import annotations

import argparse
import json
import math
import time
from typing import Tuple

import jax
import numpy as np

from repro.config import get_config, list_archs
from repro.launch.compile_cache import use_compile_cache
from repro.launch.obs_args import (add_obs_args, finalize_recorder,
                                   recorder_from_args)
from repro.models.builder import Model, build_model
from repro.obs.slo import SLOMonitor, SLOSpec
from repro.obs.timeseries import TimeSeriesSampler, attach_serve_cluster
from repro.serving import FIFOQueue, Request, ServeEngine, SLOQueue
from repro.serving.autoscale import ReplicaAutoscaler, ServeLoad
from repro.serving.cluster import ServeCluster
from repro.traces.requests import RequestTrace, synthetic_request_trace


def _pct(xs, q):
    return round(float(np.percentile(xs, q)), 4) if xs else None


def _load_request_trace(spec: str, seed: int) -> RequestTrace:
    if spec.endswith(".jsonl"):
        return RequestTrace.from_jsonl(spec)
    if spec == "serve-diurnal":
        return synthetic_request_trace("serve-diurnal", seed=seed)
    if spec == "serve-bursty":
        return synthetic_request_trace(
            "serve-bursty", seed=seed,
            bursts=((0.4, 0.55, 3.0),))
    raise SystemExit(f"unknown request trace {spec!r}: expected a .jsonl "
                     "path, 'serve-diurnal', or 'serve-bursty'")


def _replay_trace(args, engine: ServeEngine, trace: RequestTrace,
                  clock_state: dict, rng) -> list:
    """Replay arrivals on the virtual clock: between arrivals the engine
    steps (each step advances the clock by ``--step-cost-s``), and the
    revocation (if any) fires at its fractional position in the trace."""
    vocab = engine.model.cfg.vocab_size
    reqs = []
    warn_done = revoke_done = False
    t_warn = args.warn_at * trace.horizon_s if args.warn_at else None
    t_revoke = args.revoke_at * trace.horizon_s if args.revoke_at else None
    def mid_decode(req):
        return req is not None and req.generated \
            and req.remaining_tokens > args.grace_tokens

    def maybe_revoke():
        # revocations are deferred until a decode is genuinely in flight
        # (a warn/fire on an idle or prefill-only replica displaces no
        # decoded work and demonstrates nothing)
        nonlocal warn_done, revoke_done
        if t_warn is not None and not warn_done \
                and clock_state["t"] >= t_warn \
                and any(mid_decode(r) for r in engine.slots):
            migrated = engine.begin_drain(grace_tokens=args.grace_tokens)
            # single-engine driver: the replacement replica IS this engine
            # reopened, so migrated work prefix-replays right back in
            engine.draining = False
            for m in migrated:
                engine.submit(m)
            warn_done = True
        if t_revoke is not None and not revoke_done \
                and clock_state["t"] >= t_revoke \
                and engine.slots[0] is not None \
                and engine.slots[0].generated:
            engine.revoke_slot(0)
            revoke_done = True

    for ev in trace.events:
        while clock_state["t"] < ev.t_s and engine.has_work():
            engine.step()
            clock_state["t"] += args.step_cost_s
            maybe_revoke()
        clock_state["t"] = max(clock_state["t"], ev.t_s)
        req = Request(rid=ev.rid,
                      prompt=rng.integers(
                          1, vocab, size=(ev.prompt_len,)).tolist(),
                      max_new_tokens=ev.max_new_tokens,
                      arrival_s=ev.t_s, priority=ev.priority,
                      deadline_s=ev.t_s + ev.deadline_rel_s, slo=ev.slo)
        reqs.append(req)
        engine.submit(req)
    while engine.has_work():
        engine.step()
        clock_state["t"] += args.step_cost_s
        maybe_revoke()
    return reqs


def _replay_trace_cluster(args, cluster: ServeCluster, trace: RequestTrace,
                          clock_state: dict, rng, vocab: int,
                          on_tick) -> list:
    """Cluster replay: arrivals route through the least-loaded picker,
    the warn/fire revocation hits a whole replica mid-decode (drain +
    page-ship/replay migration onto survivors), and ``on_tick`` runs the
    live-telemetry loop (sampler, monitor, autoscaler) after every
    virtual-clock advance."""
    reqs = []
    warn_done = revoke_done = False
    t_warn = args.warn_at * trace.horizon_s if args.warn_at else None
    t_revoke = args.revoke_at * trace.horizon_s if args.revoke_at else None

    def mid_decode(eng):
        return any(r is not None and r.generated
                   and r.remaining_tokens > args.grace_tokens
                   for r in eng.slots)

    def victim():
        # a replica with decoded work in flight, and at least one other
        # live replica to migrate onto (warn/fire with no survivor would
        # strand the fleet, not demonstrate migration)
        live = [i for i, e in enumerate(cluster.replicas) if not e.draining]
        if len(live) < 2:
            return None
        return next((i for i in live
                     if mid_decode(cluster.replicas[i])), None)

    def maybe_revoke():
        nonlocal warn_done, revoke_done
        if t_warn is not None and not warn_done \
                and clock_state["t"] >= t_warn:
            idx = victim()
            if idx is not None:
                cluster.warn(idx, grace_tokens=args.grace_tokens)
                warn_done = True
        if t_revoke is not None and not revoke_done \
                and clock_state["t"] >= t_revoke:
            idx = victim()
            if idx is not None:
                cluster.revoke(idx)
                revoke_done = True

    def tick():
        maybe_revoke()
        on_tick()

    for ev in trace.events:
        while clock_state["t"] < ev.t_s and cluster.has_work():
            cluster.step()
            clock_state["t"] += args.step_cost_s
            tick()
        clock_state["t"] = max(clock_state["t"], ev.t_s)
        tick()
        req = Request(rid=ev.rid,
                      prompt=rng.integers(
                          1, vocab, size=(ev.prompt_len,)).tolist(),
                      max_new_tokens=ev.max_new_tokens,
                      arrival_s=ev.t_s, priority=ev.priority,
                      deadline_s=ev.t_s + ev.deadline_rel_s, slo=ev.slo)
        reqs.append(req)
        cluster.submit(req)
    while cluster.has_work():
        cluster.step()
        clock_state["t"] += args.step_cost_s
        tick()
    return reqs


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="starcoder2-3b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="published widths (weights held as "
                         "Model.init_for_serving holds them)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefill-mode", choices=("block", "token"),
                    default="block",
                    help="blocked prefill (one compiled scan per block) or "
                         "the legacy one-token-per-step fallback")
    ap.add_argument("--prefill-block", type=int, default=16,
                    help="max prompt tokens ingested per prefill dispatch")
    ap.add_argument("--cache-impl", choices=("dense", "paged"),
                    default="dense",
                    help="KV-cache layout: dense per-slot rows or a paged "
                         "pool with per-request page tables")
    ap.add_argument("--page-size", type=int, default=16,
                    help="positions per KV page (paged cache only)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="pool size in pages (paged cache only; default "
                         "is capacity-equivalent to the dense layout)")
    ap.add_argument("--queue", choices=("fifo", "slo"), default="fifo",
                    help="request queue discipline")
    ap.add_argument("--queue-capacity", type=int, default=None,
                    help="SLO queue backlog bound (admission control)")
    ap.add_argument("--trace", default=None, metavar="SPEC",
                    help="replay a request trace: 'serve-diurnal', "
                         "'serve-bursty', or a RequestTrace .jsonl path")
    ap.add_argument("--step-cost-s", type=float, default=0.05,
                    help="virtual seconds one engine step costs during "
                         "trace replay")
    ap.add_argument("--warn-at", type=float, default=None, metavar="FRAC",
                    help="begin a graceful drain (prefix-replay migration) "
                         "at this fraction of the trace horizon")
    ap.add_argument("--revoke-at", type=float, default=None, metavar="FRAC",
                    help="fire revoke_slot(0) at this fraction of the "
                         "trace horizon")
    ap.add_argument("--grace-tokens", type=int, default=4,
                    help="decodes within this many tokens of done finish "
                         "on a draining replica")
    # -- fleet / live telemetry ---------------------------------------------
    ap.add_argument("--replicas", type=int, default=1,
                    help="run a ServeCluster with this many replicas "
                         "(shared compiled steps); >1 enables replica-"
                         "level warn/fire revocation")
    ap.add_argument("--autoscale", action="store_true",
                    help="let ReplicaAutoscaler replan the replica count "
                         "(consumes SLO alerts when --monitor is on)")
    ap.add_argument("--min-replicas", type=int, default=1)
    ap.add_argument("--max-replicas", type=int, default=4)
    ap.add_argument("--target-util", type=float, default=0.75)
    ap.add_argument("--scale-interval-s", type=float, default=2.0,
                    help="virtual seconds between autoscaler decisions")
    ap.add_argument("--monitor", action="store_true",
                    help="attach the SLO burn-rate monitor (alerts print "
                         "in the summary and feed the autoscaler)")
    ap.add_argument("--slo-attainment", type=float, default=0.9,
                    help="SLO attainment target the burn rate burns "
                         "against")
    ap.add_argument("--slo-ttft-s", type=float, default=None,
                    help="per-request TTFT bound counted into attainment")
    ap.add_argument("--burn-threshold", type=float, default=2.0)
    ap.add_argument("--slo-window-s", type=float, default=30.0,
                    help="long burn window (short window = 1/6 of this)")
    ap.add_argument("--sample-interval-s", type=float, default=1.0,
                    help="virtual-clock cadence of the time-series "
                         "sampler")
    ap.add_argument("--series-out", default=None, metavar="PATH",
                    help="export sampled time-series as JSONL")
    ap.add_argument("--report", default=None, metavar="PATH",
                    help="render the HTML ops report (time-series + "
                         "alerts + per-replica summary) here")
    add_obs_args(ap)
    return ap


def load_model(args) -> Tuple[Model, dict]:
    """The model ``--arch``/``--full`` name, with random weights from
    ``--seed`` held as :meth:`Model.init_for_serving` holds them."""
    cfg = get_config(args.arch, reduced=args.reduced)
    if cfg.family == "encdec":
        raise SystemExit("serve driver targets decoder-only families; "
                         "seamless decode is exercised by the dry-run")
    model = build_model(cfg)
    return model, model.init_for_serving(jax.random.key(args.seed))


def engine_kwargs(args, *, recorder=None, clock=None) -> dict:
    """``ServeEngine`` keyword arguments from the parsed flags."""
    return dict(max_batch=args.max_batch, max_len=args.max_len,
                recorder=recorder, prefill=args.prefill_mode,
                prefill_block=args.prefill_block,
                cache_impl=args.cache_impl, page_size=args.page_size,
                num_pages=args.num_pages, clock=clock)


def main() -> None:
    args = build_parser().parse_args()
    use_compile_cache()
    model, params = load_model(args)
    cfg = model.cfg

    rng = np.random.default_rng(args.seed)
    rec = recorder_from_args(
        args, meta={"driver": "serve", "arch": args.arch,
                    "trace": args.trace, "queue": args.queue,
                    "prefill": args.prefill_mode,
                    "replicas": args.replicas})
    clock_state = {"t": 0.0}
    engine_clock = (lambda: clock_state["t"]) if args.trace else None
    use_cluster = bool(args.replicas > 1 or args.autoscale or args.monitor
                       or args.report or args.series_out)

    def make_queue():
        return SLOQueue(capacity=args.queue_capacity) \
            if args.queue == "slo" else FIFOQueue()

    kwargs = engine_kwargs(args, recorder=rec, clock=engine_clock)

    monitor = sampler = scaler = cluster = None
    if args.monitor:
        monitor = SLOMonitor(SLOSpec(
            attainment_target=args.slo_attainment,
            ttft_target_s=(args.slo_ttft_s if args.slo_ttft_s is not None
                           else math.inf),
            long_window_s=args.slo_window_s,
            short_window_s=args.slo_window_s / 6.0,
            burn_threshold=args.burn_threshold), recorder=rec)
    if args.autoscale:
        scaler = ReplicaAutoscaler(min_replicas=args.min_replicas,
                                   max_replicas=args.max_replicas,
                                   target_util=args.target_util)

    if use_cluster:
        shared = {}

        def make_engine():
            eng = ServeEngine(model, params, queue=make_queue(),
                              shared_fns=shared.get("fns"), **kwargs)
            shared.setdefault("fns", eng.shared_fns)
            return eng

        cluster = ServeCluster(make_engine, n_replicas=args.replicas,
                               clock=engine_clock, recorder=rec,
                               monitor=monitor)
        if args.report or args.series_out:
            sampler = TimeSeriesSampler(interval_s=args.sample_interval_s)
            attach_serve_cluster(sampler, cluster)
        last_scale = {"t": -math.inf}

        def on_tick():
            t = cluster.clock()
            if sampler is not None:
                sampler.maybe_sample(t)
            if monitor is not None:
                monitor.evaluate(now=t)
            if scaler is not None \
                    and t - last_scale["t"] >= args.scale_interval_s:
                last_scale["t"] = t
                live = sum(1 for e in cluster.replicas if not e.draining)
                dec = scaler.act(ServeLoad(
                    t_s=t, utilization=cluster.load,
                    queue_depth=cluster.queue_depth, n_replicas=live,
                    slots_per_replica=args.max_batch,
                    alerts=(monitor.recent_alerts(now=t)
                            if monitor is not None else ())))
                if dec.n_replicas != live:
                    cluster.scale_to(dec.n_replicas)
    else:
        engine = ServeEngine(model, params, queue=make_queue(), **kwargs)

    t0 = time.monotonic()
    if args.trace:
        trace = _load_request_trace(args.trace, args.seed)
        if use_cluster:
            reqs = _replay_trace_cluster(args, cluster, trace, clock_state,
                                         rng, cfg.vocab_size, on_tick)
        else:
            reqs = _replay_trace(args, engine, trace, clock_state, rng)
        steps = None
    else:
        sysobj = cluster if use_cluster else engine
        reqs = []
        for rid in range(args.requests):
            prompt = rng.integers(1, cfg.vocab_size,
                                  size=(args.prompt_len,)).tolist()
            req = Request(rid=rid, prompt=prompt,
                          max_new_tokens=args.max_new_tokens)
            reqs.append(req)
            sysobj.submit(req)
        if use_cluster:
            steps = 0
            while cluster.has_work() and steps < 10_000:
                cluster.step()
                steps += 1
                on_tick()
        else:
            steps = engine.run_to_completion()
    wall = time.monotonic() - t0

    stats = cluster if use_cluster else engine
    done = [r for r in reqs if r.done]
    ttfts = [r.timing.ttft_s for r in done if r.timing.ttft_s is not None]
    tpots = [t for t in (r.timing.tpot_s(len(r.generated)) for r in done)
             if t is not None]
    attained = [r for r in done if r.timing.t_complete <= r.deadline_s]
    out = {
        "arch": args.arch, "requests": len(reqs),
        "completed": len(done),
        "rejected": stats.requests_rejected,
        "engine_steps": steps, "tokens_decoded": stats.tokens_decoded,
        "tokens_lost": stats.tokens_lost,
        "tokens_replayed": stats.tokens_replayed,
        "wall_s": round(wall, 2),
        "tokens_per_s": round(stats.tokens_decoded / max(wall, 1e-9), 1),
        "ttft_p50_s": _pct(ttfts, 50), "ttft_p95_s": _pct(ttfts, 95),
        "tpot_p50_s": _pct(tpots, 50), "tpot_p95_s": _pct(tpots, 95),
        "attainment": round(len(attained) / len(reqs), 4) if reqs else None,
    }
    if use_cluster:
        out["replicas_spawned"] = cluster._next_rid
        out["replica_seconds"] = round(cluster.replica_seconds, 2)
        out["pages_shipped"] = cluster.pages_shipped
        out["requests_imported"] = cluster.requests_imported
    if monitor is not None:
        out["alerts"] = [a.to_json() for a in monitor.alerts]
    if sampler is not None and args.series_out:
        out["series"] = sampler.write_jsonl(args.series_out)
    if sampler is not None and args.report:
        from repro.obs.report import render_report, validate_report
        doc = render_report(
            series=sampler.series(),
            alerts=monitor.alerts if monitor is not None else [],
            replicas=cluster.replica_summaries(),
            summary={"arch": args.arch, "requests": len(reqs),
                     "completed": len(done),
                     "attainment": out["attainment"],
                     "tokens_decoded": stats.tokens_decoded,
                     "replica_seconds": out["replica_seconds"]},
            title=f"serve ops report · {args.arch}"
                  f"{' · ' + args.trace if args.trace else ''}")
        validate_report(doc)
        with open(args.report, "w") as f:
            f.write(doc)
        out["report"] = args.report
    # trace replays live on the virtual clock -> sim timeline; ad-hoc
    # runs keep the host-clock axis
    out.update(finalize_recorder(args, rec,
                                 clock="sim" if args.trace else "wall"))
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()

"""Training cells: the program's sharded train step, timed step by step.

Set-up (``setup_s``): the model from the configuration; the mesh and
``param_shardings`` of the configured layout from the program; the
benchmark's weights made on the chips, already sharded, in one jitted
call from the seed; the momentum state born sharded; the program's
``make_train_step`` jitted over those shardings (the state donated, as
a training loop hands each step's state to the next) and driven under
``use_mesh`` through its first ``check_steps`` steps on the window's own
feed, keeping the readings ``check_train`` compares.

Window: the same step object, on batch ``i`` of the seed for step ``i``
(every row differs), waiting for each step's loss. The window runs
whole steps until ``--seconds`` have passed; its rate is the tokens of
those steps over their time, both whole. With ``--trace 1`` the profiler
records ``trace_steps`` more steps after the window has closed, so that
its start and stop (seconds each) fall outside every timed step.

Check: after the window, with the program's state freed, the plain
reference runs the same first steps from the same weights and batches.
"""
from __future__ import annotations

import importlib
import math
import os
import shutil
import time
from typing import Any, Dict


def _train_config(train: Dict[str, Any]):
    from repro.config import OptimizerConfig, ScheduleConfig, TrainConfig
    o, s = train["optimizer"], train["schedule"]
    return TrainConfig(
        optimizer=OptimizerConfig(name=o["name"], lr=o["lr"],
                                  momentum=o["momentum"],
                                  weight_decay=o["weight_decay"],
                                  grad_clip=o["grad_clip"]),
        schedule=ScheduleConfig(kind=s["kind"], warmup_steps=s["warmup_steps"],
                                total_steps=s["total_steps"],
                                min_ratio=s["min_ratio"]),
        microbatches=int(train["microbatches"]), remat=train["remat"],
        layout=train["layout"], grad_dtype=train["grad_dtype"])


def build(cell, device, seed: int):
    """The program's sharded step and everything it runs on: ``(step,
    state, feed, ctx)``; ``ctx`` holds the mesh, layout, shardings and
    a function that makes the starting weights again."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from chipbench import model as M, weights as W
    from repro.config import MeshConfig
    from repro.launch.mesh import make_mesh
    from repro.launch.specs import batch_shardings
    from repro.sharding import param_shardings
    from repro.train.step import TrainState, make_train_step

    config, mix = cell["config"], cell["mix"]
    train = config["train"]
    gen = importlib.import_module(f"chipbench.traffic.{mix['kind']}")
    model, sizes = M.build(config)
    tcfg = _train_config(train)
    mesh = make_mesh(MeshConfig(**train["mesh"]))
    if mesh.size != cell["chips"]:
        raise ValueError(f"mesh of {mesh.size} chips for a cell of "
                         f"{cell['chips']}")
    boxed, raw = M.param_tree(model)
    shard = param_shardings(boxed, model.cfg, mesh, layout=tcfg.layout)
    rep = NamedSharding(mesh, P())
    state_shard = TrainState(params=shard, opt={"mu": shard}, step=rep)
    spec = {"tokens": jax.ShapeDtypeStruct(
        (mix["global_batch"], mix["seq_len"]), jnp.int32)}
    spec["labels"] = spec["tokens"]
    bshard = batch_shardings(spec, mesh, tcfg.layout)

    def params0():
        return W.make(boxed, raw, seed, shard)

    params = params0()
    mu = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                 out_shardings=shard)(params)
    state = TrainState(params=params, opt={"mu": mu},
                       step=jax.device_put(jnp.zeros((), jnp.int32), rep))
    step = jax.jit(make_train_step(model, tcfg, param_shardings=shard),
                   in_shardings=(state_shard, bshard, rep),
                   out_shardings=(state_shard, None), donate_argnums=(0,))
    feed_fn = gen.make_fn(mix, sizes["vocab_size"], bshard["tokens"])
    key = gen.key(seed)

    def feed(i: int):
        return feed_fn(key, i)

    ctx = {"mesh": mesh, "layout": tcfg.layout, "params0": params0,
           "sizes": sizes, "tcfg": tcfg, "gen": gen, "raw": raw,
           "one": jnp.float32(1.0)}
    return step, state, feed, ctx


def first_steps(step, state, feed, ctx, n: int):
    """Drive the step through its first ``n`` steps; return the state and
    the program's readings: losses, the first gradient's leaf norms as
    the optimizer got it, and the leaf norms of the change."""
    from chipbench import check_train
    from repro.sharding import use_mesh
    wd = ctx["tcfg"].optimizer.weight_decay
    losses, grad = [], None
    with use_mesh(ctx["mesh"], ctx["layout"]):
        for i in range(n):
            state, m = step(state, feed(i), ctx["one"])
            losses.append(float(m["loss"]))
            if i == 0:
                # g = mu1 - wd * p0, and p0 = p1 + lr0 * mu1
                lr0 = float(m["lr"])
                grad = check_train.leaf_norms(
                    state.opt["mu"], state.params,
                    fn=lambda mu, p: mu - wd * (p + lr0 * mu))
    upd = check_train.change_norms(state.params, ctx["params0"])
    return state, {"losses": losses, "grad": grad, "update": upd}


def run(cell, device, *, seed: int, seconds: float, trace: bool,
        t_start: float) -> Dict[str, Any]:
    import jax
    from jax.profiler import TraceAnnotation
    from chipbench import check_train, harness, model as M
    from chipbench import trace as T
    from repro.sharding import use_mesh

    wl, mix = cell["workload"], cell["mix"]
    n_check = int(wl["check_steps"])
    step, state, feed, ctx = build(cell, device, seed)
    state, prog = first_steps(step, state, feed, ctx, n_check)
    tokens_per_step = int(mix["global_batch"]) * int(mix["seq_len"])
    trace_dir = os.path.join(cell["root"], ".chipbench_cache", "trace",
                             cell["name"])
    trace_steps = int(wl.get("trace_steps", 3))

    setup_s = time.monotonic() - t_start

    def one_step(state, i):
        with TraceAnnotation("cb.step"):
            state, m = step(state, feed(i), ctx["one"])
            return state, float(m["loss"])

    losses = []
    with use_mesh(ctx["mesh"], ctx["layout"]):
        t0 = time.monotonic()
        while not losses or time.monotonic() - t0 < seconds:
            state, loss = one_step(state, n_check + len(losses))
            losses.append(loss)
        elapsed = time.monotonic() - t0
        n = len(losses)
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            with TraceAnnotation("cb.window"):
                for _ in range(trace_steps):
                    state, loss = one_step(state, n_check + len(losses))
                    losses.append(loss)
            jax.profiler.stop_trace()
    nonfinite = sum(not math.isfinite(x) for x in losses)
    peak = harness.peak_bytes(device.devices)
    del state

    ref = check_train.Reference(
        M.reference(cell["config"]), ctx["sizes"], cell["config"]["train"],
        _ref_mesh(device), ctx["raw"])
    rfeed = ctx["gen"].make_fn(mix, ctx["sizes"]["vocab_size"],
                               ref.rows)
    rkey = ctx["gen"].key(seed)
    refr = ref.readings(ctx["params0"], lambda j: rfeed(rkey, j), n_check)
    nums = check_train.compare(prog, refr)
    # the numbers the workload gives a limit are compared; one without an
    # upper reading to set a limit from is not
    checks = [(k, nums[k], float(lim)) for k, lim in wl["limits"].items()]
    run_rec = {
        "device": device, "setup_s": setup_s, "window": (0.0, elapsed),
        "sizes": ctx["sizes"],
        "peaks": device.peaks, "memory_peak_bytes": peak,
        "train": {"window_tokens": n * tokens_per_step,
                  "seq_len": int(mix["seq_len"]), "steps": n},
        "attempted": len(losses), "failed": nonfinite,
        "correct": nonfinite == 0 and all(v <= lim for _, v, lim in checks),
        "checks": checks + [("steps_nonfinite", nonfinite, 0)],
    }
    if trace:
        tr = T.load(T.find_xplane(trace_dir))
        summ = T.summarize(tr, cell["chips"])
        run_rec.update(busy_s=summ["busy_s"], window_s=summ["window_s"],
                       breakdown=summ["breakdown"], trace=tr)
    return run_rec


def _ref_mesh(device):
    import numpy as np
    from jax.sharding import AxisType, Mesh
    return Mesh(np.array(device.devices), ("rows",),
                axis_types=(AxisType.Auto,))

"""Drive the training driver at a tiny size on four host CPU devices,
with the timed step sound or broken underneath; print one JSON line per
case with ``correct`` and the numbers compared.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python -m chipbench.tests.fault_run [case ...]
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CASES = ("sound", "state_unchanged", "half_batch", "no_exchange",
         "control")


def cell():
    def load(*parts):
        with open(os.path.join(*parts)) as f:
            return json.load(f)
    return {"name": "sc2-train-fsdp4", "chips": 4, "root": ROOT,
            "end_to_end": [{"name": "train_tokens_per_s",
                            "unit": "tokens/s"}],
            "per_layer": [],
            "workload": load(ROOT, "chipbench", "workloads",
                             "sc2-train-fsdp4.json"),
            "config": load(HERE, "data", "tiny-train.json"),
            "mix": load(HERE, "data", "tiny-batches.json")}


def broken(case, c, real_make):
    """A ``make_train_step`` whose step carries the fault ``case``."""
    import jax
    import jax.numpy as jnp
    from chipbench import check_train, model as M

    if case == "state_unchanged":
        def make(model, tcfg, **kw):
            step = real_make(model, tcfg, **kw)

            def faulty(state, batch, lr_scale):
                _, m = step(state, batch, lr_scale)
                return state, m
            return faulty
        return make
    if case == "half_batch":
        def make(model, tcfg, **kw):
            step = real_make(model, tcfg, **kw)

            def faulty(state, batch, lr_scale):
                half = batch["tokens"].shape[0] // 2
                return step(state, {k: v[:half] for k, v in batch.items()},
                            lr_scale)
            return faulty
        return make
    if case in ("no_exchange", "control"):
        # the reference put in the step's place: with the exchange left
        # out, or computed with float8 operands (the check's control)
        def make(model, tcfg, **kw):
            from repro.train.step import TrainState
            from chipbench.drivers.train import _ref_mesh
            devs = jax.devices()[:4]
            mesh = _ref_mesh(type("D", (), {"devices": devs}))
            train = c["config"]["train"]
            _, raw = M.param_tree(model)
            how = ({"quantize": True} if case == "control" else
                   {"grad_fault": check_train.no_exchange(
                       check_train.row_shardings(raw, mesh), 4)})
            ref = check_train.Reference(
                M.reference(c["config"]), M.sizes(c["config"]), train, mesh,
                raw, **how)
            sch, opt = train["schedule"], train["optimizer"]

            def faulty(state, batch, lr_scale):
                t = state.step.astype(jnp.float32)
                warm = jnp.minimum(1.0, (t + 1.0) / sch["warmup_steps"])
                frac = jnp.clip((t - sch["warmup_steps"]) / (
                    sch["total_steps"] - sch["warmup_steps"]), 0, 1)
                lr = opt["lr"] * warm * (sch["min_ratio"] + (
                    1 - sch["min_ratio"]) * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
                p, mu, loss, _ = ref.step_fn(state.params, state.opt["mu"],
                                             batch, lr)
                return (TrainState(params=p, opt={"mu": mu},
                                   step=state.step + 1),
                        {"loss": loss, "lr": lr})
            return faulty
        return make
    raise ValueError(case)


def main(cases):
    import jax
    from chipbench import harness
    import repro.train.step as program_step
    real_make = program_step.make_train_step
    devs = jax.devices()[:4]
    dev = harness.Device("cpu", "cpu", len(devs), {}, devs)
    c = cell()
    for case in cases:
        program_step.make_train_step = real_make if case == "sound" \
            else broken(case, c, real_make)
        try:
            run = harness.run_cell(c, dev, seed=2**33 + 17, seconds=0.5,
                                   trace=False, t_start=time.monotonic())
        finally:
            program_step.make_train_step = real_make
        print(json.dumps({"case": case, "correct": run["correct"],
                          "checks": run["checks"]}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:] or CASES)

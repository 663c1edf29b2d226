"""The layer scan's weight prefetch against the plain scan, on four host
CPU devices; prints one JSON line per case (``test_layer_prefetch.py``).

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python tests/layer_prefetch_run.py numerics starcoder2-3b [layers]
    ... python tests/layer_prefetch_run.py unchanged zero1 4

The plain scan is the program with ``layer_gathers`` giving no plan,
which is what every layout but a sharded ``fsdp`` gets.
"""
import json
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType, Mesh, NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.config import TrainConfig, get_config  # noqa: E402
from repro.data.pipeline import make_batch  # noqa: E402
from repro.launch.specs import batch_shardings  # noqa: E402
from repro.models import transformer  # noqa: E402
from repro.models.builder import build_model  # noqa: E402
from repro.sharding import param_shardings, use_mesh  # noqa: E402
from repro.train.step import (TrainState, init_state, loss_fn,  # noqa: E402
                              make_train_step)

B, S = 8, 32


def mesh_of(n):
    return Mesh(np.array(jax.devices()[:n]).reshape(n, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


def setup(arch, mesh, layout, layers=None):
    cfg = get_config(arch, reduced=True).replace(dtype="float32")
    if layers:
        cfg = cfg.replace(num_layers=layers)
    model = build_model(cfg)
    tcfg = TrainConfig(remat="full", layout=layout)
    shard = param_shardings(model.abstract_params(), cfg, mesh,
                            layout=layout)
    rep = NamedSharding(mesh, P())
    state_shard = TrainState(params=shard, opt={"mu": shard}, step=rep)
    batches = [make_batch(cfg, B, S, seed=3, step=i) for i in range(2)]
    bshard = batch_shardings(jax.eval_shape(lambda: batches[0]), mesh,
                             layout)
    batches = [jax.device_put(b, bshard) for b in batches]
    init = jax.jit(lambda k: init_state(model, tcfg, k),
                   out_shardings=state_shard)
    step = jax.jit(make_train_step(model, tcfg, param_shardings=shard),
                   in_shardings=(state_shard, bshard, rep),
                   out_shardings=(state_shard, None))
    grads = jax.jit(jax.value_and_grad(
        lambda p, b: loss_fn(model, p, b, tcfg)[0]))
    return init, step, grads, batches


def plain(fn):
    """``fn()`` with the layer scan left as the plain scan."""
    real = transformer.layer_gathers
    transformer.layer_gathers = lambda *a: None
    try:
        return fn()
    finally:
        transformer.layer_gathers = real


def train(arch, mesh, layers):
    init, step, grads, batches = setup(arch, mesh, "fsdp", layers)
    with use_mesh(mesh, "fsdp"):
        state = init(jax.random.key(1))
        out = {"grad": grads(state.params, batches[0])}
        losses = []
        for b in batches:
            state, m = step(state, b, jnp.float32(1.0))
            losses.append(m["loss"])
        out["loss"] = jnp.stack(losses)
        out["params"] = state.params
        hlo = grads.lower(state.params, batches[0]).compile().as_text()
    return out, hlo


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def numerics(arch, layers=None):
    mesh = mesh_of(4)
    got, hlo = train(arch, mesh, layers)
    want, _ = plain(lambda: train(arch, mesh, layers))
    gaps = jax.tree.map(rel, got, want)
    return {"case": f"numerics/{arch}/{layers or ''}",
            "loss": max(jax.tree.leaves(gaps["loss"])),
            "grad": max(jax.tree.leaves(gaps["grad"])),
            "params": max(jax.tree.leaves(gaps["params"])),
            "leaves": len(jax.tree.leaves(gaps["grad"][1])),
            "prefetch_gathers": prefetch_gathers(hlo)}


def prefetch_gathers(hlo):
    """The compiled step's all-gathers named by the prefetch scope, split
    by the pass they sit in: the forward scan's, the backward's."""
    names = re.findall(r"all-gather(?:-start)?\([^\n]*op_name=\"([^\"]*)\"",
                       hlo)
    ours = [n for n in names if transformer.PREFETCH_SCOPE in n]
    return {"fwd": sum("/jvp(layers)/" in n for n in ours),
            "bwd": sum("transpose(" in n for n in ours)}


def lowered(arch, n, layout):
    mesh = mesh_of(n)
    init, step, _, batches = setup(arch, mesh, layout)
    with use_mesh(mesh, layout):
        state = jax.eval_shape(init, jax.random.key(1))
        return step.lower(state, batches[0], jnp.float32(1.0)).as_text()


def unchanged(n, layout):
    arch = "starcoder2-3b"
    same = lowered(arch, n, layout) == plain(lambda: lowered(arch, n, layout))
    return {"case": f"unchanged/{layout}/{n}", "same": same}


if __name__ == "__main__":
    kind, *args = sys.argv[1:]
    if kind == "numerics":
        print(json.dumps(numerics(args[0], *map(int, args[1:]))))
    else:
        print(json.dumps(unchanged(int(args[1]), args[0])))

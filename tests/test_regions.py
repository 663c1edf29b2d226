"""Named regions of the train step: every region of ``REGIONS`` reaches the
compiled program's op_name metadata, through remat and the backward pass,
and each program step shows on the profiler's clock."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.config import (OptimizerConfig, ScheduleConfig, TrainConfig,
                          get_config)
from repro.core import ElasticRuntime, SparseCluster
from repro.data.pipeline import ShardedDataset
from repro.models.builder import build_model
from repro.obs.profiling import REGIONS, region
from repro.train.step import init_state, make_train_step
from repro.train.trainer import Trainer

CFG = get_config("starcoder2-3b", reduced=True)
TCFG = TrainConfig(
    optimizer=OptimizerConfig(name="momentum", lr=1e-2, base_workers=1),
    schedule=ScheduleConfig(kind="constant", warmup_steps=1, total_steps=100),
    checkpoint_every=0, remat="full")
OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(scope="module")
def op_names():
    model = build_model(CFG)
    state = jax.eval_shape(lambda k: init_state(model, TCFG, k),
                           jax.random.key(0))
    batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32)
             for k in ("tokens", "labels")}
    step = jax.jit(make_train_step(model, TCFG))
    text = step.lower(state, batch, jnp.float32(1.0)).compile().as_text()
    return OP_NAME.findall(text)


def scopes(path):
    # "transpose(jvp(layers))" -> "layers"
    return {re.sub(r"^(?:[\w.]+\()*|\)*$", "", part)
            for part in path.split("/")}


def test_compiled_step_names_every_region(op_names):
    named = set().union(*(scopes(p) for p in op_names))
    assert set(REGIONS) <= named


@pytest.mark.parametrize("mark", ["rematted_computation", "transpose("])
def test_regions_reach_recompute_and_backward(op_names, mark):
    # the passes remat and the gradient add keep the region names
    paths = [p for p in op_names if mark in p]
    assert {"attn", "mlp"} <= set().union(*(scopes(p) for p in paths))


def test_region_rejects_unknown_name():
    with pytest.raises(ValueError, match="unknown region"):
        region("allreduce")
    with region("attn"):
        pass


def _host_steps(log_dir):
    """(name, step_num) of the profiler's step events on the host."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == "train":
                    out += [int(v) for k, v in e.stats if k == "step_num"]
    return sorted(out)


@pytest.mark.parametrize("loop", ["trainer", "elastic"])
def test_steps_on_the_profiler_clock(tmp_path, loop):
    model = build_model(CFG)
    ds = ShardedDataset(CFG, global_batch=4, seq_len=16)
    if loop == "trainer":
        tr = Trainer(model, TCFG, ds)
        state = tr.init_or_restore()
        run = lambda: tr.fit(state, 3)                       # noqa: E731
    else:
        state = init_state(model, TCFG, jax.random.key(0))
        cluster = SparseCluster(2)
        cluster.fill_and_activate(0, 0)
        rt = ElasticRuntime(model, TCFG, ds, cluster)
        run = lambda: rt.run(state, 3)                       # noqa: E731
    run()                                  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(run())
    finally:
        jax.profiler.stop_trace()
    assert _host_steps(str(tmp_path)) == [0, 1, 2]

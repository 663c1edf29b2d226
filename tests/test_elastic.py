"""Elastic runtime invariants: adaptive LR, masking, restart-equivalence,
and heterogeneity-aware (ragged) slot batches, all through the one train
step with membership as host data (row weights and the LR scale)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import (OptimizerConfig, ScheduleConfig, TrainConfig,
                          get_config)
from repro.core import (CheckpointManager, ElasticRuntime, RevocationEvent,
                        SparseCluster)
from repro.core.elastic import lr_scale, slot_batch, slot_counts
from repro.data.pipeline import ShardedDataset
from repro.models import layers as L
from repro.models.builder import build_model
from repro.train.step import init_state, make_train_step

CFG = get_config("starcoder2-3b", reduced=True)
TCFG = TrainConfig(
    optimizer=OptimizerConfig(name="adamw", lr=1e-3, adaptive_lr=True,
                              base_workers=1),
    schedule=ScheduleConfig(kind="constant", warmup_steps=1, total_steps=100),
    checkpoint_every=0)


@pytest.fixture(scope="module")
def setup():
    model = build_model(CFG)
    params = L.unbox(model.init(jax.random.key(0)))
    state = init_state(model, TCFG, jax.random.key(0), unboxed_params=params)
    ds = ShardedDataset(CFG, global_batch=8, seq_len=16)
    return model, state, ds


PER = 2                                   # 8 rows over 4 slots


def _masked(tcfg, ds, cluster, step=0):
    """The masked runtime's step inputs: the slot batch and LR scale."""
    counts = slot_counts(cluster, PER)
    return slot_batch(ds, step, counts), lr_scale(tcfg, counts)


def test_adaptive_lr_tracks_active_count(setup):
    model, state, ds = setup
    step = jax.jit(make_train_step(model, TCFG))
    cluster = SparseCluster(4)
    cluster.fill_and_activate(0, 0)
    _, m1 = step(state, *_masked(TCFG, ds, cluster))
    cluster.fill_and_activate(1, 0)
    cluster.fill_and_activate(2, 0)
    _, m3 = step(state, *_masked(TCFG, ds, cluster))
    assert float(m3["lr"]) == pytest.approx(3 * float(m1["lr"]), rel=1e-5)


def test_naive_lr_ignores_active_count(setup):
    model, state, ds = setup
    tcfg = TCFG.replace(optimizer=TCFG.optimizer.replace(adaptive_lr=False)) \
        if hasattr(TCFG, "replace") else None
    import dataclasses
    tcfg = dataclasses.replace(
        TCFG, optimizer=dataclasses.replace(TCFG.optimizer,
                                            adaptive_lr=False))
    step = jax.jit(make_train_step(model, tcfg))
    cluster = SparseCluster(4)
    cluster.fill_and_activate(0, 0)
    _, m = step(state, *_masked(tcfg, ds, cluster))
    # naive rule scales by CONFIGURED slots (4), not active (1) — the bug
    # the paper measures as a 1.17% accuracy loss (Fig 5)
    expected = 1e-3 * 4
    assert float(m["lr"]) == pytest.approx(expected, rel=1e-5)


def test_inactive_slots_do_not_affect_update(setup):
    """Poisoning an inactive slot's data must not change the step."""
    model, state, ds = setup
    step = jax.jit(make_train_step(model, TCFG))
    cluster = SparseCluster(4)
    cluster.fill_and_activate(0, 0)
    cluster.fill_and_activate(1, 0)
    batch, scale = _masked(TCFG, ds, cluster)
    s1, m1 = step(state, batch, scale)
    poisoned = dict(batch)
    poisoned["tokens"] = _slots(batch["tokens"]).at[3].set(0) \
        .reshape(batch["tokens"].shape)                   # slot 3 inactive
    poisoned["labels"] = _slots(batch["labels"]).at[3].set(0) \
        .reshape(batch["labels"].shape)
    s2, m2 = step(state, poisoned, scale)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), abs=1e-6)
    same = jax.tree.map(lambda a, b: bool(jnp.allclose(a, b, atol=1e-7)),
                        s1.params, s2.params)
    assert all(jax.tree.leaves(same))


def test_elastic_run_with_events(setup):
    model, state, ds = setup
    cluster = SparseCluster(4)
    cluster.fill_and_activate(0, 0)
    rt = ElasticRuntime(model, TCFG, ds, cluster)
    rt.add_events([
        RevocationEvent(step=2, slot=1, kind="join"),
        RevocationEvent(step=4, slot=0, kind="revoke"),
        RevocationEvent(step=6, slot=2, kind="join"),
    ])
    out = rt.run(state, 8)
    actives = [m["active"] for m in rt.metrics_log]
    assert actives == [1, 1, 2, 2, 1, 1, 2, 2]
    assert all(np.isfinite(m["loss"]) for m in rt.metrics_log)


def test_no_workers_raises(setup):
    model, state, ds = setup
    cluster = SparseCluster(2)
    cluster.fill_and_activate(0, 0)
    rt = ElasticRuntime(model, TCFG, ds, cluster)
    rt.add_events([RevocationEvent(step=1, slot=0, kind="revoke")])
    with pytest.raises(RuntimeError, match="no active workers"):
        rt.run(state, 3)


def test_elastic_recorder_event_ordering(setup):
    """Each revocation emits warn (step-1) then fire, and joins land at
    their scheduled step — the event log mirrors the injected timeline."""
    from repro import obs
    model, state, ds = setup
    cluster = SparseCluster(4)
    cluster.fill_and_activate(0, 0)
    rec = obs.Recorder(deterministic=True)
    rt = ElasticRuntime(model, TCFG, ds, cluster, recorder=rec)
    rt.add_events([
        RevocationEvent(step=2, slot=1, kind="join"),
        RevocationEvent(step=3, slot=1, kind="warn"),
        RevocationEvent(step=4, slot=1, kind="revoke"),
        RevocationEvent(step=6, slot=2, kind="join"),
    ])
    rt.run(state, 8)
    from repro.obs import (EV_REVOKE_FIRE, EV_REVOKE_WARN, EV_SLOT_JOIN,
                           EV_STEP)
    seq = [(e.name, e.t_sim) for e in rec.events
           if e.name in (EV_REVOKE_WARN, EV_REVOKE_FIRE, EV_SLOT_JOIN)]
    assert seq == [(EV_SLOT_JOIN, 2.0), (EV_REVOKE_WARN, 3.0),
                   (EV_REVOKE_FIRE, 4.0), (EV_SLOT_JOIN, 6.0)]
    steps = [e for e in rec.events if e.name == EV_STEP]
    assert len(steps) == 8
    assert [e.args["n_active"] for e in steps] == [1, 1, 2, 2, 1, 1, 2, 2]
    st = rec.metrics.to_stats()
    assert st["steps_total{mode=masked}"] == 8
    assert rec.metrics.total("revocations_total") == 1
    # no CheckpointManager -> the warn cannot trigger a fast save
    assert "fast_saves_total" not in st
    assert st["step_latency_ms/count"] == 8


def _slots(x):
    """A slot-major leaf as (slots, per_slot, ...)."""
    return x.reshape((-1, PER) + x.shape[1:])


def _tree_allclose(a, b, atol=1e-7):
    same = jax.tree.map(lambda x, y: bool(jnp.allclose(x, y, atol=atol)),
                        a, b)
    return all(jax.tree.leaves(same))


def test_hetero_step_collapses_to_masked(setup):
    """counts = per_slot * mask and lr_ratio = n_active/base reproduce the
    homogeneous masked step exactly — hetero membership is a strict
    generalization, not a fork."""
    model, state, ds = setup
    step = jax.jit(make_train_step(model, TCFG))
    cluster = SparseCluster(4)
    cluster.fill_and_activate(0, 0)
    cluster.fill_and_activate(2, 0)
    s_m, m_m = step(state, *_masked(TCFG, ds, cluster))
    counts = np.asarray([1.0, 0.0, 1.0, 0.0], np.float32) * PER
    s_h, m_h = step(state, slot_batch(ds, 0, counts),
                    lr_scale(TCFG, counts,
                             2.0 / TCFG.optimizer.base_workers))
    assert float(m_m["loss"]) == pytest.approx(float(m_h["loss"]), abs=1e-6)
    assert float(m_m["lr"]) == pytest.approx(float(m_h["lr"]), rel=1e-6)
    assert _tree_allclose(s_m.params, s_h.params)


def test_hetero_rows_beyond_counts_are_masked(setup):
    """Poisoning rows past a slot's allocated count must not change the
    step — the ragged-batch contract that makes allocation runtime data."""
    model, state, ds = setup
    step = jax.jit(make_train_step(model, TCFG))
    counts = np.asarray([2.0, 1.0, 0.0, 0.0], np.float32)  # ragged allocation
    batch = slot_batch(ds, 0, counts)
    scale = lr_scale(TCFG, counts, 2.0)
    s1, m1 = step(state, batch, scale)
    poisoned = dict(batch)
    # slot 0 rows >= 2, slot 1 rows >= 1, all of slots 2-3
    for k in ("tokens", "labels"):
        poisoned[k] = _slots(batch[k]).at[0, 2:].set(0) \
            .at[1, 1:].set(0).at[2:].set(0).reshape(batch[k].shape)
    s2, m2 = step(state, poisoned, scale)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), abs=1e-6)
    assert _tree_allclose(s1.params, s2.params)
    live = _slots(batch["row_weight"])
    assert float(live.sum()) == 3.0                      # examples
    assert int((live.sum(axis=1) > 0).sum()) == 2        # active slots


def test_hetero_lr_scales_with_throughput_ratio(setup):
    """The adaptive-LR multiplier is the aggregate-throughput ratio — the
    step's runtime scalar, so doubling the ratio exactly doubles the LR."""
    model, state, ds = setup
    step = jax.jit(make_train_step(model, TCFG))
    counts = np.asarray([2.0, 0.0, 0.0, 0.0], np.float32)
    batch = slot_batch(ds, 0, counts)
    _, m1 = step(state, batch, lr_scale(TCFG, counts, 1.0))
    _, m2 = step(state, batch, lr_scale(TCFG, counts, 2.0))
    assert float(m2["lr"]) == pytest.approx(2 * float(m1["lr"]), rel=1e-5)


def test_elastic_runtime_with_allocator(setup):
    """Mixed-kind cluster through ElasticRuntime + DynamicBatchAllocator:
    V100 slots carry more examples than K80 slots, the allocation re-solves
    on membership changes, and training stays finite throughout."""
    from repro.hetero import DynamicBatchAllocator
    model, state, ds = setup
    cluster = SparseCluster(4)
    cluster.fill_and_activate(0, 0, kind="K80")
    cluster.fill_and_activate(1, 0, kind="V100")
    alloc = DynamicBatchAllocator(cluster, global_batch=5, cap_per_slot=2,
                                  base_workers=2, base_kind="K80")
    rt = ElasticRuntime(model, TCFG, ds, cluster, allocator=alloc)
    rt.add_events([RevocationEvent(step=2, slot=2, kind="join",
                                   server_kind="V100")])
    rt.run(state, 4)
    a = alloc.allocation()
    assert a.counts[1] >= a.counts[0]            # V100 >= K80 share
    assert alloc.solve_count == 2                # initial + join re-solve
    assert all(np.isfinite(m["loss"]) for m in rt.metrics_log)
    actives = [m["active"] for m in rt.metrics_log]
    assert actives == [2, 2, 3, 3]


def test_restart_equivalence(setup, tmp_path):
    """Checkpoint + restore replays to an identical final state (C3):
    the deterministic pipeline + step-in-payload make restarts lossless."""
    model, _, ds = setup
    import dataclasses
    tcfg = dataclasses.replace(TCFG, checkpoint_every=3)

    def fresh():
        return init_state(model, tcfg, jax.random.key(1))

    # uninterrupted run: 6 steps
    cluster = SparseCluster(2)
    cluster.fill_and_activate(0, 0)
    cluster.fill_and_activate(1, 0)
    rt = ElasticRuntime(model, tcfg, ds, cluster)
    ref = rt.run(fresh(), 6)

    # interrupted run: 4 steps (ckpt lands at step 3), "crash", restore
    ck = CheckpointManager(str(tmp_path))
    cluster2 = SparseCluster(2)
    cluster2.fill_and_activate(0, 0)
    cluster2.fill_and_activate(1, 0)
    rt2 = ElasticRuntime(model, tcfg, ds, cluster2, ck)
    rt2.run(fresh(), 4)
    step, restored, _ = ck.restore_latest()
    assert step == 3
    rt3 = ElasticRuntime(model, tcfg, ds, cluster2)
    final = rt3.run(restored, 3, start_step=3)

    diffs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        ref.params, final.params)
    assert max(jax.tree.leaves(diffs)) < 1e-5


def test_row_weight_of_ones_is_the_unweighted_step(setup):
    """A batch whose rows all weigh 1 takes the step without weights."""
    model, state, ds = setup
    step = jax.jit(make_train_step(model, TCFG))
    batch = ds.global_batch_at(0)
    s1, m1 = step(state, batch, jnp.float32(1.0))
    s2, m2 = step(state, dict(batch, row_weight=jnp.ones((8,), jnp.float32)),
                  jnp.float32(1.0))
    assert float(m2["loss"]) == pytest.approx(float(m1["loss"]), rel=1e-6)
    assert _tree_allclose(s1.params, s2.params)


def test_weighted_microbatches_equal_one_weighted_pass():
    """Split in two, rows weighing 3.5 and 1 per half, the accumulated
    step equals the single-pass weighted step: each half's gradient is
    scaled to its share of the whole batch's weights."""
    import dataclasses
    cfg = CFG.replace(dtype="float32")
    model = build_model(cfg)
    tcfg = dataclasses.replace(
        TCFG, optimizer=OptimizerConfig(name="momentum", lr=0.1))
    state = init_state(model, tcfg, jax.random.key(0))
    batch = ShardedDataset(cfg, global_batch=8, seq_len=16).global_batch_at(0)
    batch["row_weight"] = jnp.asarray([1, 0.5, 1, 1, 0, 1, 0, 0], jnp.float32)
    one = jax.jit(make_train_step(model, tcfg))
    two = jax.jit(make_train_step(
        model, dataclasses.replace(tcfg, microbatches=2)))
    s1, m1 = one(state, batch, jnp.float32(1.0))
    s2, m2 = two(state, batch, jnp.float32(1.0))
    assert float(m2["loss"]) == pytest.approx(float(m1["loss"]), rel=1e-5)
    assert float(m2["grad_norm"]) == pytest.approx(float(m1["grad_norm"]),
                                                   rel=1e-5)
    close = jax.tree.map(
        lambda a, b: bool(jnp.allclose(a, b, rtol=1e-5, atol=1e-7)),
        s1.params, s2.params)
    assert all(jax.tree.leaves(close))


# ElasticRuntime's losses over 8 steps of each run below, recorded from the
# runtime before it trained through the one train step (separate masked and
# hetero step programs with their own loss and update).
PINNED_LOSSES = {
    "masked": [7.007393836975098, 6.763243675231934, 6.567569255828857,
               6.578218936920166, 6.736972332000732, 6.560690402984619,
               6.7038350105285645, 6.945281505584717],
    "hetero": [7.007393836975098, 6.763288497924805, 6.567414283752441,
               6.548410892486572, 6.816356658935547, 6.643126487731934,
               6.615638256072998, 6.904084205627441],
}


def test_runtime_losses_are_pinned():
    """Masked (2 slots, a join at 3, a revoke at 5) and mixed-kind hetero
    (K80 + V100 under the allocator, a V100 joins at 3, one is revoked at
    5): the losses equal the recorded ones bit for bit."""
    from repro.hetero import DynamicBatchAllocator
    model = build_model(CFG)
    ds = ShardedDataset(CFG, global_batch=8, seq_len=16)
    for mode, losses in PINNED_LOSSES.items():
        cluster = SparseCluster(4)
        alloc = None
        if mode == "masked":
            cluster.fill_and_activate(0, 0)
            cluster.fill_and_activate(1, 0)
            events = [RevocationEvent(step=3, slot=2, kind="join"),
                      RevocationEvent(step=5, slot=0, kind="revoke")]
        else:
            cluster.fill_and_activate(0, 0, kind="K80")
            cluster.fill_and_activate(1, 0, kind="V100")
            alloc = DynamicBatchAllocator(cluster, global_batch=5,
                                          cap_per_slot=2, base_workers=2,
                                          base_kind="K80")
            events = [RevocationEvent(step=3, slot=2, kind="join",
                                      server_kind="V100"),
                      RevocationEvent(step=5, slot=1, kind="revoke",
                                      server_kind="V100")]
        rt = ElasticRuntime(model, TCFG, ds, cluster, allocator=alloc)
        rt.add_events(events)
        rt.run(init_state(model, TCFG, jax.random.key(0)), 8)
        assert [m["loss"] for m in rt.metrics_log] == losses, mode
        assert [m["active"] for m in rt.metrics_log] == \
            [2, 2, 2, 3, 3, 2, 2, 2], mode

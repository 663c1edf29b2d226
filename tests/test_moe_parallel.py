"""MoE execution paths: gspmd vs shard_map EP vs a2a EP equivalence for
the softmax-routed capacity layer, and the layout/sharding rules added by
the §Perf hillclimb."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.config import get_config
from repro.data.pipeline import make_batch
from repro.launch.mesh import single_device_mesh
from repro.models import layers as L
from repro.models.builder import build_model
from repro.sharding import param_spec, use_mesh

def softmax_moe(arch):
    """The capacity layer's configs: arctic-480b reduced, and a variant of
    it with a shared expert and a dense first layer in place of arctic's
    parallel dense branch."""
    cfg = get_config("arctic-480b", reduced=True)
    if arch == "shared-dense-first":
        cfg = cfg.replace(name=arch, num_layers=3, num_shared_experts=1,
                          first_dense_layers=1, dense_ff=0, d_ff=128,
                          moe_d_ff=64)
    return cfg


ARCHS = ("shared-dense-first", "arctic-480b")


@pytest.fixture(scope="module")
def mesh():
    return single_device_mesh()


@pytest.mark.parametrize("arch", ARCHS)
def test_ep_matches_gspmd(arch, mesh):
    cfg = softmax_moe(arch).replace(dtype="float32")
    mx = build_model(cfg)
    mp = build_model(cfg.replace(moe_impl="ep"))
    params = L.unbox(mx.init(jax.random.key(0)))
    batch = make_batch(cfg, 2, 32)
    with use_mesh(mesh, "tp"):
        ox, ax = jax.jit(lambda p, b: mx.apply(p, b, remat=False))(params,
                                                                   batch)
        op, ap = jax.jit(lambda p, b: mp.apply(p, b, remat=False))(params,
                                                                   batch)
    assert float(jnp.max(jnp.abs(ox - op))) < 1e-4
    assert abs(float(ax["balance"]) - float(ap["balance"])) < 1e-5


@pytest.mark.parametrize("arch", ARCHS)
def test_a2a_matches_gspmd(arch, mesh):
    # B=1 so the per-rank token pool equals the gspmd per-row pool exactly
    cfg = softmax_moe(arch).replace(dtype="float32")
    mx = build_model(cfg)
    ma = build_model(cfg.replace(moe_impl="a2a"))
    params = L.unbox(mx.init(jax.random.key(1)))
    batch = make_batch(cfg, 1, 32)
    with use_mesh(mesh, "fsdp"):
        ox, _ = jax.jit(lambda p, b: mx.apply(p, b, remat=False))(params,
                                                                  batch)
        oa, _ = jax.jit(lambda p, b: ma.apply(p, b, remat=False))(params,
                                                                  batch)
    assert float(jnp.max(jnp.abs(ox - oa))) < 1e-4


def test_a2a_falls_back_outside_mesh():
    """Without a mesh the a2a config must still run (gspmd fallback)."""
    cfg = softmax_moe("shared-dense-first").replace(moe_impl="a2a")
    model = build_model(cfg)
    params = L.unbox(model.init(jax.random.key(0)))
    batch = make_batch(cfg, 2, 16)
    logits, _ = model.apply(params, batch, remat=False)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


def test_a2a_is_differentiable(mesh):
    cfg = softmax_moe("shared-dense-first").replace(dtype="float32",
                                                    moe_impl="a2a")
    model = build_model(cfg)
    params = L.unbox(model.init(jax.random.key(0)))
    batch = make_batch(cfg, 1, 16)

    def loss(p):
        logits, aux = model.apply(p, batch, remat=False)
        return jnp.mean(logits.astype(jnp.float32) ** 2) + aux["balance"]

    with use_mesh(mesh, "zero1"):
        g = jax.jit(jax.grad(loss))(params)
    norms = [float(jnp.linalg.norm(x.astype(jnp.float32)))
             for x in jax.tree.leaves(g)]
    assert all(jnp.isfinite(jnp.asarray(norms)))
    assert sum(norms) > 0


# --- layout rules -----------------------------------------------------------

def _mesh_like(data, model):
    class M:
        shape = {"data": data, "model": model}
        axis_names = ("data", "model")
        size = data * model
    return M()


def test_fsdp_layout_shards_largest_dim_over_all_axes():
    m = _mesh_like(16, 16)
    cfg = get_config("starcoder2-3b")
    spec = param_spec(("embed", "ff"), cfg, m, (3072, 12288), layout="fsdp")
    assert spec == P(None, ("data", "model"))      # 12288 % 256 == 0


def test_fsdp_layout_skips_layer_stacked_dim():
    m = _mesh_like(16, 16)
    cfg = get_config("starcoder2-3b")
    spec = param_spec(("layers", "embed", "ff"), cfg, m, (512, 3072, 12288),
                      layout="fsdp")
    assert spec[0] is None


def test_zero1_expert_weights_stay_ep_sharded():
    """Experts: 'model' keeps EP; largest other dim FSDPs over 'data'."""
    m = _mesh_like(16, 16)
    cfg = get_config("moonlight-16b-a3b")
    spec = param_spec(("experts", "embed", "ff"), cfg, m, (64, 2048, 1408),
                      layout="zero1")
    assert spec[0] == "model"
    assert spec[1] == "data"                       # 2048 % 16 == 0


def test_tp_layout_unchanged_for_divisible_heads():
    m = _mesh_like(16, 16)
    cfg = get_config("granite-20b")
    spec = param_spec(("embed", "heads", "head_dim"), cfg, m,
                      (6144, 48, 128), layout="tp")
    assert spec == P("data", "model", None)

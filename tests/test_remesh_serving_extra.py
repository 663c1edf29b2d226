"""Serving engine coverage for the MoE and hybrid families."""
import jax
import numpy as np

from repro.config import get_config
from repro.models import layers as L
from repro.models.builder import build_model
from repro.serving import Request, ServeEngine


def test_serving_moe_arch():
    """Continuous batching through a MoE model (router state per token):
    the softmax-routed capacity layer with a shared expert and a dense
    first layer."""
    cfg = get_config("arctic-480b", reduced=True).replace(
        num_layers=3, num_shared_experts=1, first_dense_layers=1, dense_ff=0,
        d_ff=128, moe_d_ff=64)
    model = build_model(cfg)
    params = L.unbox(model.init(jax.random.key(0)))
    eng = ServeEngine(model, params, max_batch=2, max_len=24)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(1, cfg.vocab_size,
                                               size=(4,)).tolist(),
                    max_new_tokens=5) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    assert all(r.done and len(r.generated) == 5 for r in reqs)


def test_serving_hybrid_arch():
    """zamba2: SSM state + shared-attn KV cache both slot-reset correctly."""
    cfg = get_config("zamba2-1.2b", reduced=True).replace(dtype="float32")
    model = build_model(cfg)
    params = L.unbox(model.init(jax.random.key(1)))

    probe = Request(rid=0, prompt=[5, 9, 2], max_new_tokens=4)
    solo = ServeEngine(model, params, max_batch=1, max_len=16)
    solo.submit(probe)
    solo.run_to_completion()

    eng = ServeEngine(model, params, max_batch=1, max_len=16)
    first = Request(rid=1, prompt=[7, 7, 7], max_new_tokens=4)
    second = Request(rid=2, prompt=[5, 9, 2], max_new_tokens=4)
    eng.submit(first)
    eng.submit(second)
    eng.run_to_completion()
    assert second.generated == probe.generated    # no state leak via slot

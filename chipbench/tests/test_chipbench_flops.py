"""The FLOP counts at one small shape, against an independent count of
the reference's matrix products and against the program's parameters."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops, model as M, weights as W

HERE = os.path.dirname(os.path.abspath(__file__))


def tiny():
    with open(os.path.join(HERE, "data", "tiny-train.json")) as f:
        return json.load(f)


def dot_flops(jaxpr) -> float:
    """2 x output size x contracted size of every dot_general, scans
    counted once per iteration."""
    tot = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            a = eqn.invars[0].aval
            k = np.prod([a.shape[i] for i in lc]) if lc else 1
            tot += 2.0 * np.prod(eqn.outvars[0].aval.shape) * k
        for p in eqn.params.values():
            sub = getattr(p, "jaxpr", None)
            if sub is not None and hasattr(sub, "eqns"):
                mult = eqn.params.get("length", 1)
                tot += mult * dot_flops(sub)
            elif hasattr(p, "eqns"):
                tot += eqn.params.get("length", 1) * dot_flops(p)
    return tot


def test_matmul_and_attention_counts_match_the_reference_pass():
    config = tiny()
    s = M.sizes(config)
    ref = M.reference(config)
    model, _ = M.build(config)
    boxed, raw = M.param_tree(model)
    params = W.make(boxed, raw, 0)
    S = 24
    toks = jnp.ones((1, S), jnp.int32)

    def fwd(p, t):
        x = ref.hidden(p, t, s)
        return ref.mm(x, p["embed"]["out"], "bsd,dv->bsv", False)

    counted = dot_flops(jax.make_jaxpr(fwd)(params, toks).jaxpr)
    # the reference scores every (query, key) pair and masks; the count
    # of the algorithm's causal need is the second check below
    want = S * 2 * flops.matmul_params(s, logits=True) \
        + flops.attention_flops(s, 1) * S * S
    assert counted == pytest.approx(want)


def test_causal_counts_by_loop():
    s = M.sizes(tiny())
    per = 2 * flops.matmul_params(s, logits=True)
    att = flops.attention_flops(s, 1)
    S = 16
    # forward of every position p, attending to p + 1 keys; backward twice
    fwd = sum(per + att * (p + 1) for p in range(S)) / S
    assert flops.train_flops_per_token(s, S) == pytest.approx(3 * fwd)


def test_counts_match_the_programs_parameters():
    config = tiny()
    s = M.sizes(config)
    model, _ = M.build(config)
    boxed, raw = M.param_tree(model)
    leaves = jax.tree_util.tree_flatten_with_path(raw)[0]
    big = sum(int(np.prod(x.shape)) for p, x in leaves
              if len(x.shape) >= 3 or jax.tree_util.keystr(p).endswith(
                  "['out']"))
    big -= sum(int(np.prod(x.shape)) for p, x in leaves
               if jax.tree_util.keystr(p).endswith(("['bq']", "['bk']",
                                                    "['bv']")))
    assert big == flops.matmul_params(s, logits=True)

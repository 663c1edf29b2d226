"""The fsdp layer scan gathers each layer's weights one layer ahead in
its forward pass (``transformer._prefetch_scan``); everywhere else the
scan is the plain one. Each case runs in a subprocess with four host
CPU devices (``layer_prefetch_run.py``), all of them at once."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
RUN = os.path.join(HERE, "layer_prefetch_run.py")
# the reduced configs' depth (two layers a trunk: the forward scan, two
# layers a loop body, unrolls whole), and an odd depth that runs the loop
# and a last layer after it
CASES = (("starcoder2-3b", None), ("starcoder2-3b", 5),
         ("moonlight-16b-a3b", None), ("seamless-m4t-large-v2", None))
UNCHANGED = (("fsdp", 1), ("zero1", 4))


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    cases = [["numerics", a] + ([str(n)] if n else []) for a, n in CASES] + [
        ["unchanged", layout, str(n)] for layout, n in UNCHANGED]
    procs = [subprocess.Popen([sys.executable, RUN, *c], env=env,
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                              text=True) for c in cases]
    out = {}
    for p in procs:
        stdout, stderr = p.communicate(timeout=900)
        assert p.returncode == 0, stderr[-3000:]
        for r in map(json.loads, stdout.strip().splitlines()):
            out[r["case"]] = r
    return out


@pytest.mark.parametrize("arch,layers", CASES)
def test_prefetched_step_matches_the_plain_scan(runs, arch, layers):
    """Two fsdp steps on a 4 x 1 mesh in float32: the loss, every leaf of
    the first gradient and every updated parameter equal the plain scan's
    within 1e-6 relative (dense, MoE with held experts, and the decoder's
    cross-attention, whose encoder input is differentiated)."""
    r = runs[f"numerics/{arch}/{layers or ''}"]
    assert r["leaves"] > 0
    for k in ("loss", "grad", "params"):
        assert r[k] <= 1e-6, (k, r)


@pytest.mark.parametrize("arch,layers", CASES)
def test_prefetch_gathers_in_the_forward_pass(runs, arch, layers):
    """The compiled step's all-gathers under the ``fsdp.prefetch`` scope
    sit in the forward pass; the backward pass is the plain scan's, with
    none."""
    r = runs[f"numerics/{arch}/{layers or ''}"]["prefetch_gathers"]
    assert r["fwd"] >= 1 and r["bwd"] == 0, r


@pytest.mark.parametrize("layout,n", UNCHANGED)
def test_plain_scan_where_the_layout_does_not_shard(runs, layout, n):
    """On a 1 x 1 mesh, and under zero1 (which gathers once a step), the
    lowered step is the plain scan's, text for text."""
    assert runs[f"unchanged/{layout}/{n}"]["same"]

"""Record the small traced program that ``test_chipbench_regions.py``
reduces: a jitted ``train_step`` that takes the gradient of a scan (the
``layers`` region) over a checkpointed body of two regions (``attn`` and
``mlp``) and applies it (``update``), run three times inside a
``cb.window`` span on one TPU. Writes ``regions_1chip.xplane.pb``, which
holds the compiled step's HLO as well, into ``out_dir``.

    python -m chipbench.tests.record_regions <out_dir>
"""
import glob
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

LAYERS, WIDTH, ROWS = 3, 256, 256


def make_step():
    import jax
    import jax.numpy as jnp
    from repro.obs.profiling import region

    def body(h, w):
        with region("attn"):
            h = h + jnp.tanh(h @ w["q"])
        with region("mlp"):
            h = h + jax.nn.relu(h @ w["up"]) @ w["down"]
        return h, None

    def loss(params, x):
        with region("layers"):
            h, _ = jax.lax.scan(jax.checkpoint(body), x, params)
        return jnp.mean(h * h)

    def train_step(params, x):
        g = jax.grad(loss)(params, x)
        with region("update"):
            return jax.tree.map(lambda p, d: p - 1e-3 * d, params, g)

    return jax.jit(train_step, donate_argnums=(0,))


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_regions: needs a TPU")
    # source locations by file name alone: the recorded HLO holds no path
    # of the machine that made it
    jax.config.update("jax_hlo_source_file_canonicalization_regex", ".*/")
    k = jax.random.split(jax.random.key(0), 4)
    shape = {"q": (LAYERS, WIDTH, WIDTH), "up": (LAYERS, WIDTH, 4 * WIDTH),
             "down": (LAYERS, 4 * WIDTH, WIDTH)}
    params = {n: 0.02 * jax.random.normal(k[i], s, jnp.float32)
              for i, (n, s) in enumerate(sorted(shape.items()))}
    x = jax.random.normal(k[3], (ROWS, WIDTH), jnp.float32)
    step = make_step()
    params = step(params, x)
    jax.block_until_ready(params)
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with TraceAnnotation("cb.window"):
            for _ in range(3):
                with TraceAnnotation("cb.step"):
                    params = step(params, x)
                    jax.block_until_ready(params)
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        os.makedirs(out_dir, exist_ok=True)
        shutil.copy(path, os.path.join(out_dir, "regions_1chip.xplane.pb"))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])

"""Whether the first steps of the timed train step are the model's steps.

The driver drives the compiled step through its first steps in set-up,
on the window's own call and feed, and keeps three readings: each step's
loss, the norm of every leaf of the first gradient as the optimizer got
it (worked out from the optimizer's state after one step), and the norm
of every leaf's change over the steps. After the window the plain
reference, in float32, runs the same steps from the same weights on the
same batches with the same optimizer arithmetic (copied from the
program's momentum SGD, global-norm clipping and cosine schedule), and
the numbers compared are:

- ``loss_rel_gap``: the largest ``|L - L_ref| / L_ref`` over the steps;
- ``grad_leaf_gap``: over the leaves, the largest gap between the
  program's and the reference's norm of the first gradient, over the
  reference's norm of that leaf or of the median leaf, whichever is
  larger (some gradients are all but zero);
- ``update_leaf_gap``: the same for the change over the steps, leaving
  out leaves whose reference gradient is under a thousandth of the
  median leaf's (such a leaf moves under the optimizer by round-off).

The reference runs on the same chips, its weights and batches split by
rows across them with shardings of its own; it imports nothing of the
program.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

SKIP_BELOW = 1e-3     # of the median leaf's reference gradient norm


def schedule(sch: Dict[str, Any], step: int) -> float:
    """The LR multiplier at ``step`` (the program's warm-up and cosine)."""
    warm = min(1.0, (step + 1.0) / max(1, sch["warmup_steps"]))
    if sch["kind"] == "constant":
        return warm
    if sch["kind"] != "cosine":
        raise ValueError(f"schedule {sch['kind']!r}")
    frac = min(max((step - sch["warmup_steps"])
                   / max(1, sch["total_steps"] - sch["warmup_steps"]), 0.0),
               1.0)
    return warm * (sch["min_ratio"] + (1 - sch["min_ratio"]) * 0.5
                   * (1.0 + math.cos(math.pi * frac)))


def leaf_norms(tree, *others, fn: Optional[Callable] = None
               ) -> Dict[str, float]:
    """``{leaf path: L2 norm of fn(leaf, *other leaves)}`` (``fn`` the
    identity by default), computed where the leaves live, in one program
    that keeps no tree of results."""
    import jax
    import jax.numpy as jnp
    fn = fn or (lambda x, *_: x)
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    rest = [jax.tree.leaves(o) for o in others]

    def norms(*cols):
        return [jnp.sqrt(jnp.sum(jnp.square(fn(*xs).astype(jnp.float32))))
                for xs in zip(*cols)]
    out = jax.jit(norms)([x for _, x in flat], *rest)
    return {jax.tree_util.keystr(p): float(n)
            for (p, _), n in zip(flat, out)}


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             keep: Optional[Sequence[str]] = None) -> float:
    """Largest ``|prog - ref| / max(ref, median(ref))`` over the leaves."""
    keys = list(keep) if keep is not None else list(ref)
    med = float(np.median([ref[k] for k in ref]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
               for k in keys)


def kept_leaves(ref_grad: Dict[str, float]) -> List[str]:
    med = float(np.median(list(ref_grad.values())))
    return [k for k, v in ref_grad.items() if v >= SKIP_BELOW * med]


def compare(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The three numbers, from two sets of readings with the keys
    ``losses``, ``grad`` (leaf norms) and ``update`` (leaf norms); a
    reading without ``update`` gives the first two."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                   ref["losses"]))
    out = {"loss_rel_gap": loss,
           "grad_leaf_gap": leaf_gap(prog["grad"], ref["grad"])}
    if "update" in prog:
        out["update_leaf_gap"] = leaf_gap(prog["update"], ref["update"],
                                          kept_leaves(ref["grad"]))
    return out


def row_shardings(tree, mesh):
    """Each leaf split over every chip along its largest axis that they
    divide (the stacked-layer axis aside), else replicated."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = mesh.size
    axis = mesh.axis_names[0]

    def one(x):
        dims = list(range(1 if x.ndim >= 3 else 0, x.ndim))
        for d in sorted(dims, key=lambda d: -x.shape[d]):
            if x.shape[d] % n == 0:
                spec = [None] * x.ndim
                spec[d] = axis
                return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())
    return jax.tree.map(one, tree)


class Reference:
    """The reference's train steps, jitted over ``mesh``."""

    def __init__(self, ref, sizes: Dict[str, Any], train: Dict[str, Any],
                 mesh, params_like, quantize: bool = False,
                 grad_fault: Optional[Callable] = None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        opt = train["optimizer"]
        self.train = train
        axis = mesh.axis_names[0]
        rows = NamedSharding(mesh, P(axis))
        self.rows = rows
        self.pshard = row_shardings(params_like, mesh)

        def place(x):
            # split by rows; a pass of fewer rows than chips, by position
            if x.shape[0] % mesh.size == 0:
                return jax.lax.with_sharding_constraint(x, rows)
            if x.ndim > 1 and x.shape[1] % mesh.size == 0:
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, P(None, axis)))
            return x

        def grads(params, batch):
            return jax.value_and_grad(ref.loss)(
                params, batch["tokens"], batch["labels"], sizes, quantize,
                place)

        grad_fn = grad_fault(grads) if grad_fault is not None else grads

        def clip(g):
            if opt["grad_clip"] <= 0:
                return g
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                              for x in jax.tree.leaves(g)))
            scale = jnp.minimum(1.0, opt["grad_clip"] / (gn + 1e-9))
            return jax.tree.map(lambda x: x * scale, g)

        def step(params, mu, batch, lr):
            loss, g = grad_fn(params, batch)
            g = clip(g)
            mu = jax.tree.map(
                lambda m, x, p: opt["momentum"] * m + x
                + opt["weight_decay"] * p, mu, g, params)
            params = jax.tree.map(lambda p, m: p - lr * m, params, mu)
            return params, mu, loss, g

        def first(params, batch):
            loss, g = grad_fn(params, batch)
            return loss, clip(g)

        bshard = {"tokens": rows, "labels": rows}
        self._first = jax.jit(first, in_shardings=(self.pshard, bshard),
                              out_shardings=(None, self.pshard))
        self.step_fn = step
        self._step = jax.jit(step, in_shardings=(self.pshard, self.pshard,
                                                 bshard, None),
                             out_shardings=(self.pshard, self.pshard, None,
                                            self.pshard),
                             donate_argnums=(0, 1))

    def readings(self, params0: Callable[[], Any],
                 batches: Callable[[int], Any], steps: int) -> Dict[str, Any]:
        """Run ``steps`` steps from the weights ``params0()`` makes, on
        ``batches(i)``; return the losses and the leaf norms of the first
        clipped gradient and of the change (the start is made again at
        the end rather than kept beside the optimizer's state)."""
        import jax
        import jax.numpy as jnp
        params = jax.device_put(params0(), self.pshard)
        mu = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                     out_shardings=self.pshard)(params)
        losses, grad = [], None
        for i in range(steps):
            lr = self.train["optimizer"]["lr"] * schedule(
                self.train["schedule"], i)
            params, mu, loss, g = self._step(params, mu, batches(i),
                                             jnp.float32(lr))
            losses.append(float(loss))
            if i == 0:
                grad = leaf_norms(g)
            del g
        del mu
        start = (lambda: jax.device_put(params0(), self.pshard))
        return {"losses": losses, "grad": grad,
                "update": change_norms(params, start)}


def first_grad(ref: "Reference", params0: Callable[[], Any], batch
               ) -> Dict[str, Any]:
    """The first step's loss and the leaf norms of its clipped gradient,
    with no optimizer state held: the readings a fault too large in
    memory for three steps can still give."""
    import jax
    loss, g = ref._first(jax.device_put(params0(), ref.pshard), batch)
    out = {"losses": [float(loss)], "grad": leaf_norms(g)}
    del g
    return out


def change_norms(params, params0: Callable[[], Any]) -> Dict[str, float]:
    """Leaf norms of ``params - params0()``, the start made again."""
    start = params0()
    out = leaf_norms(params, start, fn=lambda a, b: a - b.astype(a.dtype))
    del start
    return out


# ---------------------------------------------------------------------------
# Faults planted in the reference put in the program's place
# ---------------------------------------------------------------------------

def half_batch(grads):
    """Half of the batch left out, the mean taken over the rest."""
    def faulty(params, batch):
        half = batch["tokens"].shape[0] // 2
        return grads(params, {k: v[:half] for k, v in batch.items()})
    return faulty


def no_exchange(pshard, n: int):
    """The exchange between chips left out: the shard of each leaf that
    chip ``d`` holds is updated with the gradient of chip ``d``'s own
    rows alone, never summed with the others'. The chips' passes run
    one after another (a scan), so one gradient is held beside the sum."""
    import jax
    import jax.numpy as jnp

    def wrap(grads):
        def faulty(params, batch):
            split = {k: v.reshape((n, v.shape[0] // n) + v.shape[1:])
                     for k, v in batch.items()}

            def one(acc, xs):
                d, rows = xs
                loss, g = grads(params, rows)
                g = jax.tree.map(lambda x, s: _own_shard(x, s, d, n), g,
                                 pshard)
                return jax.tree.map(jnp.add, acc, g), loss

            zero = jax.tree.map(jnp.zeros_like, params)
            acc, losses = jax.lax.scan(one, zero, (jnp.arange(n), split))
            return losses.mean(), acc
        return faulty
    return wrap


def _own_shard(x, sharding, d, n: int):
    """``x`` zeroed outside the slice chip ``d`` holds under ``sharding``."""
    import jax.numpy as jnp
    spec = tuple(sharding.spec) + (None,) * (x.ndim - len(sharding.spec))
    for ax, name in enumerate(spec):
        if name is not None:
            size = x.shape[ax] // n
            idx = jnp.arange(x.shape[ax])
            keep = (idx >= d * size) & (idx < (d + 1) * size)
            shape = [1] * x.ndim
            shape[ax] = x.shape[ax]
            return jnp.where(keep.reshape(shape), x, 0)
    return x / n        # replicated: every chip holds it; each adds its part

#!/usr/bin/env python3
"""Take the readings the correctness limits are set from.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1 2 ... \
        [--faults 1 2 3] [--kinds control half_batch no_exchange]

For a training cell, in one process: for every seed, the program's first
steps (as a run's set-up drives them) against the float32 reference;
for every ``--faults`` seed also the control (the reference with float8
operands in the program's place) and each planted fault (half of the
batch left out; the exchange between chips left out), each against the
reference (``--kinds`` picks some of them). One JSON line per reading. The benchmark's own runs never
run this; ``PERF.md`` records what it printed and the limits set from it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import harness  # noqa: E402

KINDS = ("control", "half_batch", "no_exchange")


def train_readings(cell, device, seeds, fault_seeds, kinds=KINDS):
    from chipbench import check_train, model as M
    from chipbench.drivers import train as D
    steps = int(cell["workload"]["check_steps"])
    ref_mod = M.reference(cell["config"])
    mesh = D._ref_mesh(device)
    for seed in sorted(set(seeds) | set(fault_seeds)):
        t = time.monotonic()
        step, state, feed, ctx = D.build(cell, device, seed)
        state, prog = D.first_steps(step, state, feed, ctx, steps)
        del state, step
        raw = ctx["raw"]
        gen = ctx["gen"]

        def readings(quantize=False, fault=None):
            r = check_train.Reference(ref_mod, ctx["sizes"],
                                      cell["config"]["train"], mesh, raw,
                                      quantize=quantize, grad_fault=fault)
            f = gen.make_fn(cell["mix"], ctx["sizes"]["vocab_size"], r.rows)
            k = gen.key(seed)
            return r, r.readings(ctx["params0"], lambda j: f(k, j), steps)

        r, base = readings()
        out = {"seed": seed, "kind": "program",
               **check_train.compare(prog, base),
               "losses": prog["losses"], "ref_losses": base["losses"],
               "leaves_left_out": sorted(
                   set(base["grad"]) - set(check_train.kept_leaves(
                       base["grad"])))}
        print(json.dumps(out), flush=True)
        if seed in fault_seeds:
            # a batch of one row has no half to leave out; one chip has
            # no exchange to leave out
            can = set(KINDS)
            if cell["mix"]["global_batch"] == 1:
                can.discard("half_batch")
            if mesh.size == 1:
                can.discard("no_exchange")
            for kind in [k for k in kinds if k in can]:
                if kind == "control":
                    _, got = readings(quantize=True)
                elif kind == "half_batch":
                    _, got = readings(fault=check_train.half_batch)
                else:
                    # one step only: three would hold a momentum state
                    # beside two gradients, more than a chip has
                    fr = check_train.Reference(
                        ref_mod, ctx["sizes"], cell["config"]["train"],
                        mesh, raw, grad_fault=check_train.no_exchange(
                            r.pshard, mesh.size))
                    f = gen.make_fn(cell["mix"], ctx["sizes"]["vocab_size"],
                                    fr.rows)
                    got = check_train.first_grad(fr, ctx["params0"],
                                                 f(gen.key(seed), 0))
                print(json.dumps({"seed": seed, "kind": kind,
                                  **check_train.compare(got, base)}),
                      flush=True)
        print(json.dumps({"seed": seed, "seconds": time.monotonic() - t}),
              flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", type=int, nargs="*", default=[])
    ap.add_argument("--kinds", nargs="*", choices=KINDS, default=KINDS)
    args = ap.parse_args()
    cell = harness.load_cell(ROOT, args.workload)
    harness.use_compile_cache(ROOT)
    device = harness.check_devices(cell)
    if cell["workload"]["driver"] != "train":
        raise SystemExit("calibrate: only training cells")
    train_readings(cell, device, args.seeds, args.faults, args.kinds)
    return 0


if __name__ == "__main__":
    sys.exit(main())

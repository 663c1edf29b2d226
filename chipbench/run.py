#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its files
are found by name: ``chipbench/workloads/<cell>.json`` (driver, limits),
``chipbench/configs/<config>.json`` (sizes, with the plain reference
named beside them) and ``chipbench/traffic/<traffic>.json`` (the mix,
read by the generator its ``kind`` names). A run makes its weights and
inputs from ``--seed``, warms up (counted in ``setup_s``), measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON object as the last line of stdout. With
``--trace 1`` it reports the cell's per-layer metrics from a profiler
trace instead of its end-to-end ones.

It exits non-zero and prints no result when JAX finds no TPU, fewer
chips than the cell asks for, or a ``device_kind`` missing from
``chipbench/peaks.json``.
"""
from __future__ import annotations

import time

T_START = time.monotonic()      # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
# ask the TPU runtime not to write log files (its TPU driver was still
# seen to write some)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = harness.load_cell(ROOT, args.workload)
    except harness.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache(ROOT)
    try:
        device = harness.check_devices(cell)
    except harness.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    result = harness.run_cell(cell, device, seed=args.seed,
                              seconds=args.seconds, trace=bool(args.trace),
                              t_start=T_START)
    for name, value, limit in result["checks"]:
        print(f"check {name}: {value!r} limit {limit!r}", file=sys.stderr)
    print(json.dumps(harness.result_line(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The chip benchmark: cells of ``BENCHMARK.json`` run on a TPU.

Entry point: ``python3 chipbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``. Everything here is the yardstick: traffic
generation, weights, the plain references, the FLOP and byte counts, the
trace reduction, the metric readers and the correctness checks. From the
program (``src/repro``) it takes only the system under test.
"""

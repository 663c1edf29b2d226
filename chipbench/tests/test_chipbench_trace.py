"""The trace reduction, on small traces recorded on a TPU v5e: a jitted
program run three times between harness spans (one chip), and the same
with a sharded product whose result is gathered (four chips)."""
import os

import pytest

from chipbench import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")


def load(name):
    return T.load(os.path.join(DATA, name))


def test_interval_arithmetic():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                        (7, 10)]
    assert T.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]
    ev = [T.Event("loop", 0, 10), T.Event("a", 1, 2), T.Event("b", 4, 3),
          T.Event("c", 20, 1)]
    assert [e.name for e in T.leaves(ev)] == ["a", "b", "c"]


def test_one_chip_trace():
    tr = load("trace_1chip.xplane.pb")
    assert len(tr.devices) == 1
    w0, w1 = tr.window()
    assert w1 > w0
    runs = T.module_runs(tr.devices[0], (0, 10**12), "lambda")
    assert len(runs) == 3
    s = T.summarize(tr, 1)
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["window_s"] == pytest.approx((w1 - w0) / 1e9)
    # between the program runs the host sleeps in its cb.wait spans
    gaps = s["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "cb.wait" and gaps[0][1] > 0.005
    assert len(s["breakdown"]["device_ops"]) <= 10
    assert all(not n.startswith("%") or " " not in n
               for n, _ in s["breakdown"]["device_ops"])
    # busy is the union of the operations' intervals in the window
    busy = sum(e.dur for e in T.leaves(tr.devices[0].ops)
               if w0 <= e.start and e.end <= w1)
    assert s["busy_s"] == pytest.approx(busy / 1e9, rel=0.05)
    assert T.exposed_collective_ns(tr.devices[0], tr.window()) == 0


def test_exposed_collectives_and_program_runs():
    dev = T.DeviceTrace(0, ops=[
        T.Event("%fusion.1", 0, 10), T.Event("%all-gather.2", 5, 10),
        T.Event("%reduce-scatter.3", 30, 4), T.Event("%fusion.4", 28, 3)],
        modules=[T.Event("jit_train_step(1)", 0, 16),
                 T.Event("jit_gen(2)", 20, 2),
                 T.Event("jit_train_step(1)", 29, 6)])
    window = (0, 40)
    # nothing else runs beside the all-gather in 10-15, nor beside the
    # reduce-scatter in 31-34
    assert T.exposed_collective_ns(dev, window) == 5 + 3
    runs = T.module_runs(dev, window, "train_step")
    assert [e.start for e in runs] == [0, 29]
    assert T.busy_ns(dev, window) == 15 + 6

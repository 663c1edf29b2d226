"""Collective time on device 0 with no other operation beside it, over
the device time of the train step's runs in the traced slice."""
from chipbench import trace as T

TRAIN_PROGRAM = r"train_step"


def read(run, name):
    tr = run.get("trace")
    if tr is None or not run.get("train"):
        return None
    win = tr.window()
    dev = tr.devices[0]
    step = sum(e.dur for e in T.module_runs(dev, win, TRAIN_PROGRAM))
    if step <= 0:
        return None
    return 100.0 * T.exposed_collective_ns(dev, win) / step

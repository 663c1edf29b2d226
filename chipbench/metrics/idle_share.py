"""1 - busy / window from the profiler trace: the share of the traced
slice in which no operation ran, averaged over the chips used. One
reader for ``idle_share.<cell kind>``."""


def read(run, name):
    if run.get("busy_s") is None or not run.get("window_s"):
        return None
    return 100.0 * (1.0 - run["busy_s"] / run["window_s"])

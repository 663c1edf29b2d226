"""Set-up: from the process's first line to the window's opening (weights,
compilation or cache reads, warm-up and the traffic's ramp)."""


def read(run, name):
    return run.get("setup_s")

"""Share of the train step's summed device-op time on device 0 spent in
one pass (``pass_share.<pass>``): ``fwd``, ``recompute`` (the forward
that remat runs again inside the backward) or ``bwd``; ops of the
``update`` region are in none (``chipbench/regions.py``)."""
from chipbench import regions


def read(run, name):
    return regions.read(run, "pass", name)

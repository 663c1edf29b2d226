"""Share of the train step's summed device-op time on device 0 spent in
one region of the program (``region_share.<region>``): ``embed``,
``attn``, ``mlp``, ``layer_loop``, ``head``, ``update``, ``collective``
or ``unattributed``; the eight sum to 100 (``chipbench/regions.py``)."""
from chipbench import regions


def read(run, name):
    return regions.read(run, "region", name)

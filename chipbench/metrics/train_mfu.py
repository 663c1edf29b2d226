"""Training MFU: (6 N + attention) FLOPs a token needs
(``flops.train_flops_per_token``, recomputation not counted) times the
tokens per second of the run's window, over chips x peak. The window is
timed before the profiler starts, so its start and stop do not count."""
from chipbench import flops


def read(run, name):
    tr = run.get("train")
    if not tr or not tr.get("window_tokens"):
        return None
    lo, hi = run["window"]
    rate = tr["window_tokens"] / (hi - lo)
    per_tok = flops.train_flops_per_token(run["sizes"], tr["seq_len"])
    return 100.0 * rate * per_tok / (
        len(run["device"].devices) * run["peaks"]["bf16_flops_per_s"])

"""Tokens of every train step that finished inside the window, over the
window's length (host clock; each step's end is its loss read back)."""


def read(run, name):
    tr = run.get("train")
    if not tr:
        return None
    lo, hi = run["window"]
    return tr["window_tokens"] / (hi - lo)

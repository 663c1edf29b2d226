"""Training batches: deterministic in the seed, every row different."""
import numpy as np

from chipbench.traffic import train_batches


def test_train_batches_deterministic_and_rows_differ():
    m = {"global_batch": 4, "seq_len": 32}
    fn = train_batches.make_fn(m, 512)
    k = train_batches.key(2**40 + 3)
    a, b = fn(k, 0), fn(k, 0)
    assert np.array_equal(a["tokens"], b["tokens"])
    c = fn(k, 1)
    assert not np.array_equal(a["tokens"], c["tokens"])
    rows = np.asarray(a["tokens"])
    assert len({tuple(r) for r in rows}) == 4
    assert np.array_equal(np.asarray(a["tokens"])[:, 1:],
                          np.asarray(a["labels"])[:, :-1])

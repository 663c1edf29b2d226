"""``--profile DIR``: the device trace starts and stops with the run, and a
profiler that cannot start stops the run instead of leaving it untraced."""
import argparse
import os

import jax
import pytest

from repro.launch.obs_args import finalize_recorder, recorder_from_args


def _args(profile):
    return argparse.Namespace(events=None, profile=str(profile))


def test_profile_writes_trace_and_timeline(tmp_path):
    args = _args(tmp_path / "prof")
    rec = recorder_from_args(args, meta={"driver": "test"})
    with rec.span("step", cat="train"):
        jax.block_until_ready(jax.numpy.ones(4) + 1)
    out = finalize_recorder(args, rec)
    assert out["profile_dir"] == args.profile
    assert os.path.exists(out["events"]) and os.path.exists(out["timeline"])
    assert any(files for _, _, files in os.walk(os.path.join(args.profile,
                                                             "plugins")))


def test_profiler_that_cannot_start_raises(tmp_path):
    jax.profiler.start_trace(str(tmp_path / "first"))
    try:
        with pytest.raises(RuntimeError):
            recorder_from_args(_args(tmp_path / "second"))
    finally:
        jax.profiler.stop_trace()

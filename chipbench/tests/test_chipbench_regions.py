"""The split of the train step's device time by region and pass: on
hand-made ops and HLO, and on a small program recorded on a TPU v5e (a
gradient of a scan over a checkpointed body of two regions)."""
import os
import shutil

import pytest

from chipbench import regions as R
from chipbench import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
REGIONS = ("embed", "layers", "attn", "mlp", "head", "update")

HLO = """\
HloModule jit_train_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  ROOT %m.1 = f32[8]{0} multiply(%p.1, %p.1)
}

%body.2 (arg.2: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg.2 = (s32[], f32[8]{0}) parameter(0)
  %copy.3 = f32[8]{0} copy(%g.2)
  %fusion.4 = f32[8]{0} fusion(%copy.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(layers)/while/body/closed_call/attn/mul"}
  %convert_fusion.5 = f32[8]{0} fusion(%fusion.4), kind=kLoop, calls=%fused_computation.1, metadata={op_name="convert.9"}
  ROOT %t.2 = (s32[], f32[8]{0}) tuple(%i.2, %convert_fusion.5)
}

%cond.3 (arg.3: (s32[], f32[8])) -> pred[] {
  ROOT %lt.3 = pred[] compare(%a.3, %b.3), direction=LT
}

ENTRY %main.9 (x.9: f32[8]) -> f32[8] {
  %x.9 = f32[8]{0} parameter(0)
  %convert.16 = f32[8]{0} convert(%x.9)
  %tuple.9 = (s32[], f32[8]{0}) tuple(%i.9, %convert.16)
  %while.10 = (s32[], f32[8]{0}) while(%tuple.9), condition=%cond.3, body=%body.2, metadata={op_name="jit(train_step)/transpose(jvp(layers))/while" stack_frame_id=3}
  %all-gather.11 = f32[8]{0} all-gather(%x.9), dimensions={0}, metadata={op_name="jit(train_step)/jvp(layers)/while/body/closed_call/mlp/all-gather"}
  %fusion.12 = f32[8]{0} fusion(%x.9), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/transpose(jvp(layers))/while/body/checkpoint/rematted_computation/mlp/dot_general"}
  %fusion.13 = f32[8]{0} fusion(%x.9), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/update/add"}
  %copy-start.17 = (f32[8]{0}, f32[8]{0}) copy-start(%fusion.13)
  %copy-done.17 = f32[8]{0} copy-done(%copy-start.17)
  %copy.14 = f32[8]{0} copy(%x.9)
  %fusion.15 = f32[8]{0} fusion(%x.9), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(head)/exp"}
  ROOT %tuple.18 = (f32[8]{0}, f32[8]{0}) tuple(%copy-done.17, %fusion.15)
}
"""


def test_unnamed_instructions_take_a_neighbours_path():
    names = R.op_names(HLO)
    assert names["fusion.4"].endswith("closed_call/attn/mul")
    # the loop's own copy, and a fusion XLA named after another
    # instruction, take the path of the while that runs their body
    assert names["copy.3"] == names["while.10"] == \
        "jit(train_step)/transpose(jvp(layers))/while"
    assert names["convert_fusion.5"] == names["while.10"]
    # inside a fusion, the path of the fusion (the first caller found)
    assert names["m.1"] == names["fusion.4"]
    # in the entry: a cast hoisted out of the loop takes the path of the
    # loop that uses it; a copy out of a result, that of what made it
    assert names["convert.16"] == names["while.10"]
    assert names["copy-done.17"] == names["copy-start.17"] == \
        "jit(train_step)/update/add"
    assert names["copy.14"] == ""
    assert R.scopes("jit(train_step)/transpose(jvp(layers))/while") == [
        "train_step", "layers", "while"]


@pytest.mark.parametrize("op, path, want", [
    ("fusion.4", "jit(train_step)/jvp(layers)/while/body/closed_call/attn/"
     "mul", ("attn", "fwd")),
    ("copy.3", "jit(train_step)/transpose(jvp(layers))/while",
     ("layer_loop", "bwd")),
    ("fusion.12", "jit(train_step)/transpose(jvp(layers))/while/body/"
     "checkpoint/rematted_computation/mlp/dot_general", ("mlp", "recompute")),
    ("all-gather.11", "jit(train_step)/jvp(layers)/while/body/closed_call/"
     "mlp/all-gather", ("collective", "fwd")),
    ("all-reduce.7", "jit(train_step)/update/reduce_sum", ("collective",
                                                           None)),
    ("fusion.13", "jit(train_step)/update/add", ("update", None)),
    ("copy.14", "", ("unattributed", "fwd")),
    ("fusion.15", "jit(train_step)/jvp(head)/exp", ("head", "fwd")),
])
def test_classify(op, path, want):
    assert R.classify(op, path, REGIONS) == want


def trace_of(ops, modules, window=(0, 100)):
    return T.Trace([T.DeviceTrace(0, ops, modules)],
                   [T.Event("cb.window", window[0], window[1] - window[0])])


def test_split_of_the_step_runs_in_the_window():
    names = R.op_names(HLO)
    ops = [T.Event("%while.10", 10, 20),            # a container, not a leaf
           T.Event("%copy.3", 10, 4), T.Event("%fusion.4", 14, 6),
           T.Event("%all-gather.11", 20, 10), T.Event("%fusion.12", 30, 5),
           T.Event("%fusion.13", 35, 3), T.Event("%copy.14", 38, 1),
           T.Event("%fusion.15", 39, 1),
           T.Event("%fusion.4", 52, 8),             # in another program
           T.Event("%fusion.13", 95, 4),            # run starts in the window
           T.Event("%fusion.15", 120, 9)]           # run starts after it
    modules = [T.Event("jit_train_step(1)", 10, 31),
               T.Event("jit_gen(2)", 50, 20),
               T.Event("jit_train_step(1)", 95, 5),
               T.Event("jit_train_step(1)", 110, 30)]
    got = R.split(trace_of(ops, modules), names, REGIONS)
    total = 4 + 6 + 10 + 5 + 3 + 1 + 1 + 4
    share = {k: v * total / 100 for k, v in got["region"].items()}
    assert share == pytest.approx({
        "embed": 0, "layer_loop": 4, "attn": 6, "mlp": 5, "head": 1,
        "update": 7, "collective": 10, "unattributed": 1})
    assert sum(got["region"].values()) == pytest.approx(100)
    passes = {k: v * total / 100 for k, v in got["pass"].items()}
    assert passes == pytest.approx({"fwd": 6 + 10 + 1 + 1, "recompute": 5,
                                    "bwd": 4})
    assert sum(got["pass"].values()) == pytest.approx(
        100 - got["region"]["update"])


def with_trace(root, name="regions_1chip.xplane.pb"):
    """``root`` holding a recorded trace where the driver writes its own."""
    where = os.path.join(root, ".chipbench_cache", "trace", "cell",
                         "plugins", "profile", "t")
    os.makedirs(where)
    shutil.copy(os.path.join(DATA, name), os.path.join(where, "h.xplane.pb"))
    return str(root)


def test_a_program_without_regions_reads_nothing(tmp_path, monkeypatch):
    ops = [T.Event("%copy.14", 10, 4), T.Event("%all-gather.11", 20, 10)]
    tr = trace_of(ops, [T.Event("jit_train_step(1)", 10, 30)])
    assert R.split(tr, R.op_names(HLO), REGIONS) is None
    # no trace written, or one that holds no train step's HLO
    assert R.read({"trace": tr}, "region", "region_share.attn",
                  root=str(tmp_path)) is None
    root = with_trace(tmp_path, "trace_1chip.xplane.pb")
    assert R.trace_op_names(R.latest_trace(root)) == {}
    assert R.read({"trace": tr}, "region", "region_share.attn",
                  root=root) is None
    monkeypatch.setattr(R, "program_regions", lambda: None)
    monkeypatch.setattr(R, "trace_op_names", lambda path: R.op_names(HLO))
    tr = trace_of([T.Event("%fusion.4", 12, 4)],
                  [T.Event("jit_train_step(1)", 10, 30)])
    assert R.read({"trace": tr}, "pass", "pass_share.fwd",
                  root=root) is None
    assert R.read({"trace": None}, "pass", "pass_share.fwd",
                  root=root) is None


def test_recorded_step_is_attributed(tmp_path):
    root = with_trace(tmp_path)
    path = R.latest_trace(root)
    tr = T.load(path)
    # the trace holds the HLO of the step it ran
    modules = R.trace_modules(path)
    assert [m for m in modules if "train_step" in m] == list(modules)
    names = R.trace_op_names(path)
    ops = R.step_ops(tr)
    assert ops
    # every op the chip ran is an instruction of the step's module
    assert {e.name.lstrip("%") for e in ops} <= set(names)
    got = R.split(tr, names, REGIONS)
    assert R.read({"trace": tr}, "region", "region_share.mlp",
                  root=root) == got["region"]["mlp"]
    region, passes = got["region"], got["pass"]
    assert sum(region.values()) == pytest.approx(100)
    # all but a stated share of the step's time is in the program's regions
    assert region["unattributed"] < 1.0
    assert min(region[r] for r in ("attn", "mlp", "layer_loop",
                                   "update")) > 0
    assert region["embed"] == region["head"] == region["collective"] == 0
    assert min(passes.values()) > 0
    assert passes["bwd"] > passes["recompute"]
    assert sum(passes.values()) == pytest.approx(100 - region["update"])

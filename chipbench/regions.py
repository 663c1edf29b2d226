"""Split the train step's device time by the program's regions and passes.

The program names its regions with ``jax.named_scope`` (the closed set
``repro.obs.profiling.REGIONS``). The names reach the compiled step as
each instruction's ``metadata={op_name="..."}``, a path such as

    jit(train_step)/jvp(layers)/while/body/closed_call/attn/dot_general
    jit(train_step)/transpose(jvp(layers))/while/body/checkpoint/
        rematted_computation/mlp/dot_general

The profiler's ``XLA Ops`` events carry the instruction's name
(``%fusion.779``) but not that path. The trace's ``/host:metadata``
plane holds the optimized HLO of each program it ran, so the paths come
from the train step's module there (``trace_op_names``), in the newest
trace under ``.chipbench_cache/trace/``: the one the driver wrote in
this run. An instruction with no ``op_name`` takes the path of the
instruction that calls its computation (a ``while``, a fusion, a
conditional): the loop's own copies and slices land in ``layers``. One
in the entry computation takes the path of the first instruction that
uses its result (the casts and layout copies of the stacked weights
that XLA hoists out of the loop land in ``layers`` too), else that of
its first operand (the copy of a result out to memory lands with what
made the result). An ``op_name`` that is no path (XLA names some
instructions it makes after another instruction, ``convert.31``)
counts as none.

Region of an op: a collective (``trace.COLLECTIVE`` on its name) is
``collective`` whatever its path; else the innermost region its path
names, with ``layers`` reported as ``layer_loop`` (the scan's slices of
the stacked weights, its carry and residual copies); a path that names
no region is ``unattributed``. Pass of an op: ``recompute`` where the
path holds ``rematted_computation``, ``bwd`` where it holds
``transpose(``, else ``fwd``; ops of the ``update`` region have no pass.

The ops reduced are the leaf ops of device 0 whose start falls inside a
``train_step`` program run that starts inside the ``cb.window`` span;
each share is of their summed time.
"""
from __future__ import annotations

import functools
import glob
import os
import re
from typing import Dict, Iterator, List, Optional, Tuple

from chipbench import trace as T

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACES = os.path.join(".chipbench_cache", "trace", "*", "plugins",
                      "profile", "*", "*.xplane.pb")
TRAIN_PROGRAM = r"train_step"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
LAYER_LOOP = "layer_loop"
COLLECTIVE = "collective"
UNATTRIBUTED = "unattributed"
PASSES = ("fwd", "recompute", "bwd")

_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?(%?[\w.\-]+)\s*=")
_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation|"
                    r"false_computation)=(%?[\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_CALL_LISTS = re.compile(r"\b(?:calls|branch_computations|"
                         r"called_computations)=\{([^}]*)\}")
_SCOPE = re.compile(r"^(?:[\w.]+\()*([^()]*)\)*$")


def program_regions() -> Optional[Tuple[str, ...]]:
    """The program's region names, or None where the program names none."""
    try:
        from repro.obs.profiling import REGIONS
    except ImportError:
        return None
    return tuple(REGIONS)


def region_names(regions: Tuple[str, ...]) -> Tuple[str, ...]:
    """The buckets ``region_share`` reports, in a fixed order."""
    inner = tuple(LAYER_LOOP if r == "layers" else r for r in regions)
    return inner + (COLLECTIVE, UNATTRIBUTED)


def _name(instr: str) -> str:
    return instr.lstrip("%")


def op_names(hlo_text: str) -> Dict[str, str]:
    """``{instruction: op_name}`` for every instruction of an HLO module's
    text; an instruction without one takes its caller's, or in the entry
    computation its first user's, else its first operand's."""
    own: Dict[str, str] = {}
    comp_of: Dict[str, str] = {}
    caller: Dict[str, str] = {}
    refs: Dict[str, List[str]] = {}
    comp = None
    for line in hlo_text.splitlines():
        if not line or line[0].isspace():
            m = _INSTR.match(line) if comp else None
            if m is None:
                continue
            name = _name(m.group(1))
            comp_of[name] = comp
            refs[name] = _REF.findall(line[m.end():])
            op = _OP_NAME.search(line)
            if op and "/" in op.group(1):
                own[name] = op.group(1)
            callees = _CALLS.findall(line)
            for group in _CALL_LISTS.findall(line):
                callees += [c.strip() for c in group.split(",") if c.strip()]
            for c in callees:
                caller.setdefault(_name(c), name)
        elif line.rstrip().endswith("{"):
            words = line.split()
            comp = _name(words[1] if words[0] == "ENTRY" else words[0])
        elif line.startswith("}"):
            comp = None

    # the text lists operands before their users (HLO's post or schedule
    # order), so one pass each way finds the first path met downstream
    # (through users) and upstream (through operands)
    order = list(refs)
    operands = {n: [o for o in refs[n] if o != n and comp_of.get(o) ==
                    comp_of[n]] for n in order}
    users: Dict[str, List[str]] = {}
    for name in order:
        for op in operands[name]:
            users.setdefault(op, []).append(name)
    upstream: Dict[str, str] = {}
    for name in order:
        upstream[name] = own.get(name) or next(
            filter(None, (upstream.get(o) for o in operands[name])), "")
    downstream: Dict[str, str] = {}
    for name in reversed(order):
        downstream[name] = own.get(name) or next(
            filter(None, (downstream.get(u) for u in users.get(name, ()))),
            "")

    out: Dict[str, str] = {}

    def resolve(name: str) -> str:
        if name not in out:
            up = caller.get(comp_of[name])
            out[name] = own.get(name) or (
                resolve(up) if up is not None
                else downstream[name] or upstream[name])
        return out[name]

    for name in comp_of:
        resolve(name)
    return out


def scopes(path: str) -> List[str]:
    """The scope names of an op_name path, each unwrapped from the
    transformations around it (``transpose(jvp(layers))`` -> ``layers``)."""
    return [_SCOPE.match(part).group(1) for part in path.split("/")]


def classify(op: str, path: str, regions: Tuple[str, ...]
             ) -> Tuple[str, Optional[str]]:
    """``(region bucket, pass)`` of one op; the pass is None for an op of
    the ``update`` region."""
    named = [s for s in scopes(path) if s in regions]
    inner = named[-1] if named else None
    if T.COLLECTIVE.search(op):
        bucket = COLLECTIVE
    elif inner is None:
        bucket = UNATTRIBUTED
    else:
        bucket = LAYER_LOOP if inner == "layers" else inner
    if inner == "update":
        return bucket, None
    if "rematted_computation" in path:
        return bucket, "recompute"
    if "transpose(" in path:
        return bucket, "bwd"
    return bucket, "fwd"


def step_ops(trace: T.Trace) -> List[T.Event]:
    """Leaf ops of device 0 that start inside a train-step run that
    starts inside the window."""
    dev = trace.devices[0]
    runs = T.module_runs(dev, trace.window(), TRAIN_PROGRAM)
    return [e for e in T.leaves(dev.ops)
            if any(r.start <= e.start < r.end for r in runs)]


def split(trace: T.Trace, names: Dict[str, str],
          regions: Tuple[str, ...]) -> Optional[Dict[str, Dict[str, float]]]:
    """``{"region": {bucket: %}, "pass": {pass: %}}`` of the train step's
    summed leaf-op time; None where there is no step time, or where no op
    names a region (a program without them)."""
    ops = step_ops(trace)
    total = sum(e.dur for e in ops)
    if total <= 0:
        return None
    by_region = dict.fromkeys(region_names(regions), 0)
    by_pass = dict.fromkeys(PASSES, 0)
    kinds: Dict[str, Tuple[str, Optional[str]]] = {}
    for e in ops:
        op = _name(e.name)
        if op not in kinds:
            kinds[op] = classify(op, names.get(op, ""), regions)
        bucket, pass_ = kinds[op]
        by_region[bucket] += e.dur
        if pass_ is not None:
            by_pass[pass_] += e.dur
    if by_region[UNATTRIBUTED] + by_region[COLLECTIVE] == total:
        return None
    return {"region": {k: 100.0 * v / total for k, v in by_region.items()},
            "pass": {k: 100.0 * v / total for k, v in by_pass.items()}}


def _fields(buf: memoryview) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of each field of a serialized protobuf
    message: an int for a varint, a memoryview for the other wire types."""
    i, n = 0, len(buf)

    def varint() -> int:
        nonlocal i
        x = shift = 0
        while True:
            c = buf[i]
            i += 1
            x |= (c & 0x7F) << shift
            shift += 7
            if c < 0x80:
                return x

    while i < n:
        key = varint()
        wire = key & 7
        if wire == 0:
            yield key >> 3, varint()
            continue
        if wire == 2:
            size = varint()
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire} not read")
        yield key >> 3, buf[i:i + size]
        i += size


def trace_modules(path: str) -> Dict[str, bytes]:
    """``{program name: serialized HloModuleProto}`` of the programs whose
    HLO the profiler stored in a trace's metadata plane.

    Fields read (``xplane.proto``, ``hlo.proto``): XSpace.planes 1;
    XPlane.name 2, event_metadata 4 and stat_metadata 5, maps whose
    entries hold the value in field 2; XEventMetadata.name 2 and stats 5;
    XStatMetadata.id 1 and name 2; XStat.metadata_id 1 and bytes_value 6;
    HloProto.hlo_module 1."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, bytes] = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        fields = list(_fields(plane))
        if not any(k == 2 and bytes(v) == METADATA_PLANE.encode()
                   for k, v in fields):
            continue
        stat_names = {}
        for k, v in fields:
            if k == 5:
                meta = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        for k, v in fields:
            if k != 4:
                continue
            event = list(_fields(dict(_fields(v)).get(2, b"")))
            name = next((bytes(x).decode() for j, x in event if j == 2), "")
            for j, x in event:
                stat = dict(_fields(x)) if j == 5 else {}
                if stat_names.get(stat.get(1)) == HLO_PROTO_STAT \
                        and 6 in stat:
                    module = dict(_fields(stat[6])).get(1)
                    if module is not None:
                        out[name] = bytes(module)
    return out


@functools.lru_cache(maxsize=4)
def _trace_op_names(path: str, stamp: Tuple[int, int]) -> Dict[str, str]:
    from jax._src.lib import xla_client
    hlo = xla_client._xla
    options = hlo.HloPrintOptions()
    options.print_metadata = True
    names: Dict[str, str] = {}
    for program, module in trace_modules(path).items():
        if re.search(TRAIN_PROGRAM, program):
            names.update(op_names(
                hlo.HloModule.from_serialized_hlo_module_proto(module)
                .to_string(options)))
    return names


def trace_op_names(path: str) -> Dict[str, str]:
    """``op_names`` of the train step's module that a trace holds; empty
    where it holds none."""
    st = os.stat(path)
    return _trace_op_names(path, (st.st_mtime_ns, st.st_size))


def latest_trace(root: str = ROOT) -> Optional[str]:
    """The newest ``.xplane.pb`` the driver wrote under ``root``."""
    return max(glob.glob(os.path.join(root, TRACES)),
               key=os.path.getmtime, default=None)


def read(run, kind: str, name: str, root: str = ROOT) -> Optional[float]:
    """One share (``kind`` "region" or "pass") named ``<prefix>.<part>``
    from a driver's record and the trace it wrote under ``root``; None
    where they hold nothing to read."""
    tr, regions = run.get("trace"), program_regions()
    if tr is None or regions is None:
        return None
    path = latest_trace(root)
    names = trace_op_names(path) if path else None
    if not names:
        return None
    got = split(tr, names, regions)
    if got is None:
        return None
    return got[kind].get(name.split(".", 1)[1])

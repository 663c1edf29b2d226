"""Reduce a profiler trace to device metrics.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it. A TPU shows as planes
``/device:TPU:<n>``, whose ``XLA Ops`` line holds one event per operation
run and whose ``XLA Modules`` line holds one per program run; the host
shows as ``/host:CPU``, whose thread lines hold the spans the harness
writes with ``jax.profiler.TraceAnnotation`` (named ``cb.*``). All
events share one clock, in nanoseconds; the device's events may sit
about a millisecond off the host's (a program run was seen to start a
millisecond before the host span that dispatched it), which is below
what the reductions here resolve.

What is reduced, over the harness's ``cb.window`` span:

- busy: the union of the intervals in which an operation ran, per
  device; idle is the rest of the window;
- op time: the summed duration of each operation name (leaf operations
  only: an event that encloses others, such as a loop, is a container);
- module runs: each program run, with its name, start and duration;
- exposed collectives: the part of the union of collective operations
  during which no other operation ran on that device;
- idle gaps: each interval with no operation, named by the innermost
  harness span open at its midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all", re.IGNORECASE)
HOST_PREFIX = "cb."
WINDOW_SPAN = "cb.window"


@dataclasses.dataclass
class Event:
    name: str
    start: int
    dur: int

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class DeviceTrace:
    index: int
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Trace:
    devices: List[DeviceTrace]
    host: List[Event]            # harness spans, every thread

    def window(self) -> Interval:
        spans = [e for e in self.host if e.name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
        return spans[0].start, spans[0].end


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read the device and harness events of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    # "%fusion.12 = bf16[...] fusion(...)" -> "%fusion.12"
                    ops = [Event(e.name.split(" = ", 1)[0], int(e.start_ns),
                                 int(e.duration_ns)) for e in line.events]
                elif line.name == "XLA Modules":
                    modules = [Event(e.name, int(e.start_ns),
                                     int(e.duration_ns))
                               for e in line.events]
            devices.append(DeviceTrace(int(m.group(1)), ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    devices.sort(key=lambda d: d.index)
    return Trace(devices, sorted(host, key=lambda e: e.start))


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """``a`` minus ``b``; both sorted and disjoint."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def leaves(events: Sequence[Event]) -> List[Event]:
    """Events that enclose no other event (containers such as loops left
    out), so that no time is counted twice."""
    evs = sorted(events, key=lambda e: (e.start, -e.dur))
    out = []
    for i, e in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt.start < e.end and nxt.end <= e.end \
                and nxt is not e:
            continue
        out.append(e)
    return out


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------

def busy_ns(dev: DeviceTrace, window: Interval) -> int:
    return length(clip(union((e.start, e.end) for e in dev.ops), *window))


def op_seconds(dev: DeviceTrace, window: Interval) -> Dict[str, float]:
    tot: Dict[str, float] = {}
    for e in leaves(dev.ops):
        d = length(clip([(e.start, e.end)], *window))
        if d:
            tot[e.name] = tot.get(e.name, 0.0) + d / 1e9
    return tot


def module_runs(dev: DeviceTrace, window: Interval,
                pattern: Optional[str] = None) -> List[Event]:
    """Program runs that start inside the window, by name pattern."""
    rx = re.compile(pattern) if pattern else None
    return [e for e in dev.modules
            if window[0] <= e.start < window[1]
            and (rx is None or rx.search(e.name))]


def exposed_collective_ns(dev: DeviceTrace, window: Interval) -> int:
    ops = leaves(dev.ops)
    coll = union((e.start, e.end) for e in ops if COLLECTIVE.search(e.name))
    comp = union((e.start, e.end) for e in ops
                 if not COLLECTIVE.search(e.name))
    return length(clip(subtract(coll, comp), *window))


def idle_gaps(dev: DeviceTrace, host: Sequence[Event], window: Interval
              ) -> List[Tuple[str, float]]:
    """Every idle interval of the device in the window, longest first,
    named by the innermost harness span open at its midpoint."""
    busy = clip(union((e.start, e.end) for e in dev.ops), *window)
    gaps = subtract([window], busy)
    spans = [e for e in host if e.name != WINDOW_SPAN]
    out = []
    for s, e in gaps:
        mid = (s + e) // 2
        open_ = [h for h in spans if h.start <= mid < h.end]
        name = min(open_, key=lambda h: h.dur).name if open_ else "none"
        out.append((name, (e - s) / 1e9))
    return sorted(out, key=lambda g: -g[1])


def summarize(trace: Trace, n_devices: int, top: int = 10) -> Dict:
    """``busy_s`` (mean over the devices used), ``window_s``, and the
    breakdown of device 0: its ``top`` operations by time and its
    ``top`` longest idle gaps."""
    window = trace.window()
    devs = trace.devices[:n_devices]
    if not devs:
        raise ValueError("trace has no TPU device plane")
    busy = sum(busy_ns(d, window) for d in devs) / len(devs)
    ops = sorted(op_seconds(devs[0], window).items(), key=lambda kv: -kv[1])
    return {"busy_s": busy / 1e9, "window_s": (window[1] - window[0]) / 1e9,
            "breakdown": {
                "device_ops": [[n, s] for n, s in ops[:top]],
                "idle_gaps": [[n, s] for n, s in
                              idle_gaps(devs[0], trace.host, window)[:top]]}}

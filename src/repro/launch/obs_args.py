"""Shared ``--events`` / ``--profile`` observability flags for launchers.

Both drivers (``launch.train``, ``launch.serve``) expose the same pair:

``--events PATH``   record a structured event log and flush it as JSONL
                    (``repro.obs`` Recorder format; feed it to
                    ``python -m repro.obs.export`` for a Perfetto trace).
``--profile DIR``   additionally start a ``jax.profiler`` device trace
                    into DIR (a profiler that cannot start is an error,
                    not a silent untraced run) and drop ``events.jsonl``
                    + a validated ``timeline.trace.json`` next to it, so the device
                    trace and the sim/step timeline can be opened
                    side-by-side in Perfetto.

Either flag alone enables the Recorder; with neither, every instrumented
call site sees the NULL recorder and the run is observability-free.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Optional

import jax

from repro import obs


def add_obs_args(ap) -> None:
    ap.add_argument("--events", default=None, metavar="PATH",
                    help="write a structured event log (JSONL) here")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="jax.profiler trace dir; also writes events.jsonl "
                         "+ timeline.trace.json")


def recorder_from_args(args, *, meta: Optional[Dict[str, Any]] = None
                       ) -> Optional[obs.Recorder]:
    """The recorder the flags ask for, with the device trace started under
    ``--profile``; None when observability is off."""
    if not (args.events or args.profile):
        return None
    rec = obs.Recorder(jsonl=args.events, meta=meta)
    if args.profile:
        os.makedirs(args.profile, exist_ok=True)
        jax.profiler.start_trace(args.profile)
    return rec


def finalize_recorder(args, rec: Optional[obs.Recorder], *,
                      clock: str = "sim") -> Dict[str, str]:
    """Stop the device trace, flush the log, export the timeline.

    Returns the paths written (for the driver's stdout summary). ``clock``
    picks the exported timeline's axis: "sim" for trace/step-driven runs,
    "wall" for serving (whose events carry host timestamps only).
    """
    from repro.obs import export

    out: Dict[str, str] = {}
    if args.profile:
        jax.profiler.stop_trace()
        out["profile_dir"] = args.profile
    if rec is None:
        return out
    if args.events:
        out["events"] = rec.flush(args.events)
    if args.profile:
        jsonl = os.path.join(args.profile, "events.jsonl")
        out.setdefault("events", rec.flush(jsonl))
        if rec.events:
            out["timeline"] = export.write_chrome_trace(
                rec.events, os.path.join(args.profile, "timeline.trace.json"),
                clock=clock, meta=rec.meta)
    return out

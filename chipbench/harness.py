"""Find a cell's files by name, check the devices, run its driver, and
turn what the driver recorded into the result line.

Every piece is found by the name ``BENCHMARK.json`` gives it:

- ``chipbench/workloads/<cell>.json``: the driver and the limits of the
  correctness check;
- ``chipbench/configs/<config>.json``: the configuration as it is run;
- ``chipbench/traffic/<traffic>.json``: the mix, whose ``kind`` names
  the generator module ``chipbench/traffic/<kind>.py``;
- ``chipbench/drivers/<driver>.py``: builds the system under test,
  warms it up, measures, and checks it;
- ``chipbench/metrics/<metric>.py`` (or, for ``a.b``, ``<a>.py``): a
  reader that takes one metric from the driver's record, or returns
  ``None`` when it finds nothing to read.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os
from typing import Any, Dict, List, Optional, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))
# JAX's persistent compilation cache: a fixed path inside the checkout,
# so a later run of the same checkout reads what an earlier one compiled
CACHE_SUBDIR = os.path.join(".chipbench_cache", "jax")


class BenchError(RuntimeError):
    """A cell, a file or a device that the benchmark cannot run with."""


def _read_json(path: str) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchError(f"missing file {os.path.relpath(path)}") from None


def load_peaks(kind: str, path: Optional[str] = None) -> Dict[str, float]:
    """The row of ``peaks.json`` for ``device_kind``; an unknown kind is
    an error, never a default."""
    table = _read_json(path or os.path.join(HERE, "peaks.json"))
    rows = table["devices"]
    if kind not in rows:
        raise BenchError(f"device_kind {kind!r} is not in peaks.json "
                         f"(known: {sorted(rows)})")
    return rows[kind]


def load_cell(root: str, name: str) -> Dict[str, Any]:
    """The cell's entry of ``BENCHMARK.json`` and every file it names."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r} (known: "
                         f"{sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise BenchError(f"{name}: unknown config {w['config']!r}")
    entry = configs[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", ())
                 or ("workloads" not in m and m["moves"] in reported)]
    mix = _read_json(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    return {"name": name, "chips": int(w["chips"]), "root": root,
            "end_to_end": e2e, "per_layer": per_layer,
            "workload": _read_json(os.path.join(HERE, "workloads",
                                                name + ".json")),
            "config": _read_json(os.path.join(root, entry["file"])),
            "mix": mix}


def use_compile_cache(root: str) -> str:
    """Keep JAX's compilation cache inside the checkout, and keep every
    program in it however fast it compiled, so that a warm run compiles
    nothing (JAX's default keeps only programs that took over a second)."""
    import jax
    path = os.path.join(root, CACHE_SUBDIR)
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclasses.dataclass
class Device:
    platform: str
    kind: str
    count: int
    peaks: Dict[str, float]
    devices: Sequence[Any]


def check_devices(cell: Dict[str, Any], devices: Optional[Sequence] = None,
                  peaks_path: Optional[str] = None) -> Device:
    """The TPUs this run uses; raises when there is no TPU, fewer chips
    than the cell asks for, or a kind the peaks table lacks."""
    if devices is None:
        import jax
        devices = jax.devices()
    if not devices or devices[0].platform != "tpu":
        found = devices[0].platform if devices else "nothing"
        raise BenchError(f"needs a TPU, JAX found {found!r}")
    if len(devices) < cell["chips"]:
        raise BenchError(f"{cell['name']} needs {cell['chips']} chips, "
                         f"JAX found {len(devices)}")
    kind = devices[0].device_kind
    return Device(platform=devices[0].platform, kind=kind,
                  count=len(devices), peaks=load_peaks(kind, peaks_path),
                  devices=list(devices)[:cell["chips"]])


def peak_bytes(devices: Sequence) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the
    backend keeps no statistics, as the CPU)."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def reader(name: str):
    """The ``read`` function of a metric's module: ``metrics/<name>.py``,
    else ``metrics/<prefix>.py`` for a name ``<prefix>.<part>``."""
    for mod in (name, name.split(".", 1)[0]):
        if os.path.exists(os.path.join(HERE, "metrics", mod + ".py")):
            return importlib.import_module(f"chipbench.metrics.{mod}").read
    raise BenchError(f"no reader for metric {name!r} in chipbench/metrics/")


def read_metrics(entries: List[Dict[str, Any]], run: Dict[str, Any]
                 ) -> Dict[str, Dict[str, Any]]:
    """``{name: {"value", "unit"}}`` for every metric whose reader found
    something; a reader that finds nothing leaves its metric out."""
    out = {}
    for m in entries:
        value = reader(m["name"])(run, m["name"])
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell: Dict[str, Any], device: Device, *, seed: int,
             seconds: float, trace: bool, t_start: float) -> Dict[str, Any]:
    """Run the cell's driver and read its metrics."""
    driver = importlib.import_module(
        f"chipbench.drivers.{cell['workload']['driver']}")
    run = driver.run(cell, device, seed=seed, seconds=seconds, trace=trace,
                     t_start=t_start)
    run["metrics"] = read_metrics(
        cell["per_layer"] if trace else cell["end_to_end"], run)
    return run


def result_line(run: Dict[str, Any]) -> Dict[str, Any]:
    """The line a run prints last: correctness, counts, metrics and
    device, then the numbers compared beside their limits."""
    dev = run["device"]
    device = {"platform": dev.platform, "kind": dev.kind, "count": dev.count,
              "memory_peak_bytes": run["memory_peak_bytes"]}
    for k in ("busy_s", "window_s"):
        if run.get(k) is not None:
            device[k] = run[k]
    line = {"correct": bool(run["correct"]),
            "attempted": int(run["attempted"]),
            "failed": int(run["failed"]), "metrics": run["metrics"],
            "device": device}
    if run.get("breakdown"):
        line["breakdown"] = run["breakdown"]
    line["checks"] = {name: {"value": value, "limit": limit}
                      for name, value, limit in run["checks"]}
    return line

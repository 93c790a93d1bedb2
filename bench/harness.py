"""What every cell shares: finding its files by name, seeds, the record of a
run, the profiler window, and turning a record into the result line.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Its configuration
is ``bench/configs/<config>.json``, its traffic ``bench/traffic/<traffic>.json``,
and the traffic names the driver that runs it, ``bench/drivers/<driver>.py``.
Every metric is read by ``bench/metrics/<name>.py``.  So a cell, a
configuration or a metric is added with files and entries, never by an edit.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: compile cache, generated-module store and traces: fixed paths in the checkout
CACHE = ROOT / ".bench_cache"
#: host annotation around the measured window, which the traced readers use
WINDOW = "bench.window"


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One workload: its entry, configuration and traffic, and which metrics it reports."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    #: also read the control (the reference in the next lower precision) on
    #: every sampled answer; ``bench/control.py`` sets it, the benchmark never
    control: bool = False

    @property
    def driver(self) -> str:
        return self.traffic["driver"]


def _reports(metric: Dict[str, Any], cell: str, e2e_names: Optional[set] = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def load_cell(name: str, benchmark: Optional[Dict[str, Any]] = None) -> Cell:
    bm = benchmark if benchmark is not None else load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bm["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    config = next(c for c in bm["configs"] if c["name"] == entry["config"])
    e2e = [m for m in bm["end_to_end"] if _reports(m, name)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"] if _reports(m, name, names)]
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=load_json(ROOT / config["file"]),
        traffic=load_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
        end_to_end=e2e,
        per_layer=per_layer,
    )


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def seed_ints(seed: int, n: int) -> List[int]:
    """``n`` 31-bit integers from a seed of any size, one stream per use."""
    return [int(x) & 0x7FFFFFFF for x in np.random.SeedSequence(int(seed)).generate_state(n)]


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(int(seed), spawn_key=(int(stream),)))


def peaks(device_kind: str) -> Dict[str, float]:
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"bench: no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table["devices"][device_kind]


def process_age_s() -> float:
    """Seconds since this process started, from the kernel's own record."""
    with open("/proc/self/stat") as f:
        after_name = f.read().rsplit(")", 1)[1].split()
    start_ticks = int(after_name[19])  # field 22, starttime, in clock ticks since boot
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


@dataclass
class Check:
    """One number compared with its limit; it passes when ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


@dataclass
class Record:
    """What a driver measured in one run, before any metric is read from it."""

    cell: Cell
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    #: quantities the window counted (points, steps, requests, samples)
    counters: Dict[str, Any] = field(default_factory=dict)
    checks: List[Check] = field(default_factory=list)
    #: device kind, for the peaks table
    device_kind: str = ""
    memory_peak_bytes: int = 0
    #: the reduced profiler trace of a ``--trace 1`` run, else None
    trace: Any = None
    chips: int = 1


def lower_precision(dtype) -> Any:
    """The precision below a configuration's: bfloat16 for float32, float32
    for float64."""
    import ml_dtypes

    return {"float32": ml_dtypes.bfloat16, "float64": np.float32}[np.dtype(dtype).name]


def memory_peak_bytes(devices) -> int:
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks_)) if peaks_ else 0


@contextlib.contextmanager
def profiled(enabled: bool, workload: str, out: Dict[str, Any]):
    """Run the body under the JAX profiler when ``enabled``; afterwards
    ``out["trace"]`` holds the reduced trace.  The raw trace is deleted once
    read: what the benchmark writes to disk stays small."""
    if not enabled:
        yield
        return
    import jax

    from . import trace as btrace

    path = CACHE / "trace" / workload
    shutil.rmtree(path, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
    files = sorted(path.glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise RuntimeError(f"bench: the profiler wrote no trace under {path}")
    out["trace"] = btrace.load(files[-1])
    shutil.rmtree(path, ignore_errors=True)


def result(rec: Record, trace: bool, device) -> Dict[str, Any]:
    """The result line: the end-to-end metrics, or with ``trace`` the
    per-layer ones, each read by its own reader; ``checks`` comes last."""
    metrics: Dict[str, Any] = {}
    unread = []
    for m in rec.cell.per_layer if trace else rec.cell.end_to_end:
        value = load_module("metrics", m["name"]).read(rec)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        elif not trace or value is not None:
            # an end-to-end metric that reads nothing, or a per-layer one
            # that reads a number that is not finite, leaves the run not correct
            unread.append(m["name"])
            print(f"bench: {m['name']} read {value}", file=sys.stderr)
    dev = {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": rec.chips,
        "memory_peak_bytes": rec.memory_peak_bytes,
    }
    out: Dict[str, Any] = {
        "correct": bool(rec.checks) and all(c.ok for c in rec.checks) and rec.failed == 0 and not unread,
        "attempted": rec.attempted,
        "failed": rec.failed,
        "metrics": metrics,
        "device": dev,
    }
    if trace:
        from . import trace as btrace

        t0, t1 = rec.trace.window(WINDOW)
        busy = [rec.trace.busy_ns(d, t0, t1) for d in rec.trace.devices[: rec.chips]]
        dev["busy_s"] = float(np.mean(busy)) / 1e9
        dev["window_s"] = (t1 - t0) / 1e9
        out["breakdown"] = btrace.breakdown(rec.trace, t0, t1)
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in rec.checks}
    return out


def print_checks(rec: Record) -> None:
    for c in rec.checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} {'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    if rec.failed:
        print(f"check failed_operations: {rec.failed} of {rec.attempted}", file=sys.stderr)


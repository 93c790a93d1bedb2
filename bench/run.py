#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine and print its result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``.  The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``, each number compared with its limit.  The same checks are the
last lines of standard error.

There is no CPU path: the command exits with code 2, and prints no result,
when the first device is not a TPU or there are fewer than the cell's chips.
JAX's compile cache and the generated-module store sit at fixed paths in the
checkout (``.bench_cache/``), so only a checkout's first run compiles.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(harness.CACHE / "jax")
    os.environ["REPRO_GT_CACHE"] = str(harness.CACHE / "gt")
    cell = harness.load_cell(args.workload)

    import jax

    jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: the first JAX device is {devices[0].platform!r}, not a TPU; there is no CPU path",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found {len(devices)}", file=sys.stderr)
        return 2
    harness.peaks(devices[0].device_kind)  # an unknown device is an error before any work

    import repro  # noqa: F401  (float64 on, as the served program expects)

    driver = importlib.import_module(f"bench.drivers.{cell.driver}")
    rec = driver.run(cell, args.seed, args.seconds, bool(args.trace))
    line = harness.result(rec, bool(args.trace), devices[0])
    harness.print_checks(rec)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

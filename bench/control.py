#!/usr/bin/env python3
"""Read a cell's control beside the program on several seeds, in one process.

    python bench/control.py --workload <cell> --seconds 5 --seeds 11 12 13

The control is the reference computed in the precision below the one the
configuration states (bfloat16 for float32, float32 for float64), read in
the program's place on the same sampled answers.  A sound limit lies above
the largest reading of the program and below the smallest of the control.
Prints one JSON line per seed with every number compared.  The benchmark's
own runs never read the control.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(harness.CACHE / "jax")
    os.environ["REPRO_GT_CACHE"] = str(harness.CACHE / "gt")
    import jax

    import repro  # noqa: F401

    cell = harness.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 2
    cell.control = True
    driver = importlib.import_module(f"bench.drivers.{cell.driver}")
    for seed in args.seeds:
        rec = driver.run(cell, seed, args.seconds, False)
        print(json.dumps({"seed": seed, "attempted": rec.attempted, "failed": rec.failed,
                          **{c.name: c.value for c in rec.checks}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

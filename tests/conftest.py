import os
import sys
from pathlib import Path

# Tests see the single real CPU device; multi-device tests set
# --xla_force_host_platform_device_count in a child process.
os.environ.setdefault("REPRO_GT_CACHE", str(Path(__file__).resolve().parent.parent / ".gt_cache"))

SRC = str(Path(__file__).resolve().parent.parent / "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

import repro  # noqa: E402,F401  (enables jax x64 once, before any test)

"""The package's import arrows point one way: core, then program, then
ensemble, then serving.  Each case imports one entry point in a fresh
interpreter and lists the ``repro`` subpackages it loaded."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parent.parent / "src")
SERVING_STACK = {"core", "obs", "program", "ensemble", "parallel", "runtime", "serving", "stencils"}


def _loaded(*modules):
    """The ``repro`` subpackages a fresh interpreter holds after importing
    ``modules``."""
    script = (
        f"import sys, json; sys.path.insert(0, {SRC!r})\n"
        + "".join(f"import {m}\n" for m in modules)
        + "print(json.dumps(sorted({n.split('.')[1] for n in sys.modules"
        " if n.startswith('repro.')})))"
    )
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, timeout=120, check=True
    )
    return set(json.loads(out.stdout.strip().splitlines()[-1]))


@pytest.mark.parametrize(
    "modules, allowed",
    [
        (("repro.core",), {"core", "obs"}),
        (("repro.program",), {"core", "obs", "program"}),
        (("repro.ensemble",), {"core", "obs", "program", "ensemble"}),
        (("repro.serving", "repro.stencils.distributed"), SERVING_STACK),
    ],
    ids=["core", "program", "ensemble", "serving"],
)
def test_imports_load_only_lower_layers(modules, allowed):
    loaded = _loaded(*modules)
    assert loaded <= allowed, f"{modules} loaded {sorted(loaded - allowed)}"

"""Structural fingerprint cache for generated stencil modules.

Per the paper (§2.3): stencils are hashed so that *reformatting* the Python
source does not trigger re-codegen — the fingerprint is computed from the
(normalized) Definition IR, not from source text.  Generated modules are
written to a cache directory as real ``.py`` files (inspectable, steppable)
and re-imported on subsequent runs if the fingerprint matches.
"""

from __future__ import annotations

import hashlib
import importlib.util
import os
import sys
import threading
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, Optional

from . import ir

_CACHE_VERSION = "repro-gt-5"
_lock = threading.Lock()
_memory_cache: Dict[str, ModuleType] = {}


#: the checkout this package runs from (``src/repro/core`` is three levels in)
CHECKOUT = Path(__file__).resolve().parents[3]


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it itself, and
    nothing is set here).  Otherwise the cache is ``.jax_cache`` in the
    checkout, a fixed path: the path is part of what a later run must find
    again.  Entry points that drive the chip call this
    (``chip_smoke.py``, ``repro.launch.serve``); importing ``repro`` never
    does, so tests run without a compile cache."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_dir() -> Path:
    root = os.environ.get("REPRO_GT_CACHE")
    if root:
        p = Path(root)
    else:
        p = Path.home() / ".cache" / "repro_gt"
    p.mkdir(parents=True, exist_ok=True)
    return p


def fingerprint(
    definition: ir.StencilDefinition,
    backend: str,
    options: Optional[Dict[str, Any]] = None,
    pass_config: Optional[Dict[str, Any]] = None,
) -> str:
    """Cache key for a generated module.

    Keyed on the normalized Definition IR (so reformatting the python source
    does not re-codegen), the backend, its codegen options, AND the
    optimization-pass configuration: the same definition at a different
    ``opt_level`` / pass set is a different generated module.  The names of
    the registered passes participate too, so adding a pass to the pipeline
    invalidates stale artifacts.
    """
    from . import passes  # local import: passes depends on analysis/ir only

    payload = "|".join(
        [
            _CACHE_VERSION,
            backend,
            repr(definition),
            repr(sorted((options or {}).items())),
            repr(sorted((pass_config or {}).items())),
            repr(passes.PASS_NAMES),
        ]
    )
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def program_fingerprint(
    name: str,
    graph_repr: str,
    part_fingerprints,
    backend: str,
    options: Optional[Dict[str, Any]] = None,
) -> str:
    """Cache key for a compiled *program* (``repro.program``).

    Keyed on the structural dataflow-graph hash plus the fingerprints of the
    constituent (merged) stencils — so a program re-generates exactly when
    one of its stencils, the graph wiring, or the orchestration options
    change, and never when the step function is merely reformatted (the
    graph repr is built from IR-level facts, not source text)."""
    payload = "|".join(
        [
            _CACHE_VERSION,
            "program",
            name,
            backend,
            hashlib.sha256(graph_repr.encode()).hexdigest(),
            repr(tuple(part_fingerprints)),
            repr(sorted((options or {}).items())),
        ]
    )
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def tuning_path(name: str, fp: str) -> Path:
    """Where the Pallas tile autotuner persists its result for a module.

    Lives alongside the generated ``<name>_<fp>.py`` and shares its
    fingerprint, so a tuning record can never outlive the exact IR + options
    it was measured for (``core/autotune.py``)."""
    return cache_dir() / f"{name}_{fp}.tune.json"


def load_generated_module(name: str, fp: str, source: str, rebuild: bool = False) -> ModuleType:
    """Write ``source`` to the cache (if needed) and import it as a module."""
    key = f"{name}_{fp}"
    with _lock:
        if not rebuild and key in _memory_cache:
            return _memory_cache[key]
        module_name = f"_repro_gt_{key}"
        try:
            path = cache_dir() / f"{key}.py"
            if rebuild or not path.exists() or path.read_text() != source:
                path.write_text(source)
            spec = importlib.util.spec_from_file_location(module_name, path)
            assert spec and spec.loader
            module = importlib.util.module_from_spec(spec)
            sys.modules[module_name] = module
            spec.loader.exec_module(module)
        except OSError:
            # read-only filesystem: exec in-memory
            module = ModuleType(module_name)
            module.__dict__["__file__"] = f"<generated {key}>"
            exec(compile(source, f"<generated {key}>", "exec"), module.__dict__)
        _memory_cache[key] = module
        return module

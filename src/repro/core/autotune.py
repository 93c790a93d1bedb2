"""Horizontal tile-size autotuning for the Pallas backend.

The Pallas code generator bakes one ``_BLOCK_DEFAULT`` into the module, but
the best ``(BI, BJ)`` tile depends on the domain, the stencil's VMEM
footprint, and the DMA/compute balance — exactly the schedule knob the paper
argues the toolchain (not the user) should turn.  This module times a small
set of candidate tiles against the stencil's own generated ``run`` (on
synthetic inputs shaped from the module's field metadata: warmup, then
best-of-N) and picks the fastest.

Results are **keyed on the pass-aware cache fingerprint** from
``core/caching.py`` and persisted as ``<name>_<fp>.tune.json`` next to the
generated module, so a second build of the identical IR + options is a pure
cache hit — the search never reruns.  A different ``opt_level`` / pass set /
codegen option is a different fingerprint and tunes (and persists)
independently.  The chosen tile and per-candidate timings surface through
``exec_info["autotune"]`` on the stencil call.

Candidates are filtered against the module's per-tile VMEM estimate
(``_vmem_bytes``) so the search never times a tile that cannot fit.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.obs import trace as otrace

from . import caching
from .codegen_pallas import VMEM_LIMIT_BYTES

# (BI, BJ) candidates: sublane multiples × the 128-lane TPU vector width.
# Clamped to the domain (and deduplicated) before timing.
DEFAULT_CANDIDATES: Tuple[Tuple[int, int], ...] = (
    (8, 128),
    (16, 128),
    (32, 128),
    (8, 256),
    (16, 256),
)

# (TI, TJ) candidates for the tiled numpy backend (codegen_array stage
# tiling): row-major arrays want long contiguous j-runs; the i side sets the
# L2 working-set of a tile's live stage chain.
DEFAULT_NUMPY_CANDIDATES: Tuple[Tuple[int, int], ...] = (
    (32, 64),
    (32, 128),
    (64, 128),
    (64, 256),
    (128, 128),
)


def _is_numpy_module(module) -> bool:
    return getattr(module, "_BACKEND", None) == "numpy"

# Mosaic's default scoped-VMEM limit per kernel.  The generated
# ``_vmem_bytes`` estimates from above what the compiler allocates (checked
# against it in tests/test_tpu_compile.py), so a tile under this budget
# compiles.
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES

_lock = threading.Lock()
_memory: Dict[Tuple[str, str, str], Dict[str, Any]] = {}


def candidate_blocks(
    module,
    domain: Tuple[int, int, int],
    candidates: Optional[Sequence[Tuple[int, int]]] = None,
) -> List[Tuple[int, int]]:
    """Domain-clamped, VMEM-filtered, deduplicated candidate tiles."""
    ni, nj, nk = domain
    defaults = DEFAULT_NUMPY_CANDIDATES if _is_numpy_module(module) else DEFAULT_CANDIDATES
    cands = [tuple(c) for c in (candidates or defaults)]
    default = tuple(getattr(module, "_BLOCK_DEFAULT", (8, 128)))
    if default not in cands:
        cands.insert(0, default)
    vmem_bytes = getattr(module, "_vmem_bytes", None)
    seen: set = set()
    out: List[Tuple[int, int]] = []
    for bi, bj in cands:
        eff = (min(int(bi), ni), min(int(bj), nj))
        if eff in seen:
            continue
        seen.add(eff)
        if vmem_bytes is not None and vmem_bytes(eff[0], eff[1], nk) > VMEM_BUDGET_BYTES:
            continue
        out.append(eff)
    if not out:  # every candidate over budget: fall back to the clamped default
        out.append((min(default[0], ni), min(default[1], nj)))
    return out


def _synthetic_call_args(module, domain: Tuple[int, int, int], batch: Optional[int] = None):
    """Fields/scalars/origins for timing, built from the module's metadata.

    Values are uniform in [0.5, 1.5]: away from zero so division-heavy
    stencils (Thomas solvers) stay finite, with enough variation that no
    arithmetic folds away.  ``batch`` prepends a member axis to every field
    so batched runs are timed as they will execute (under ``jax.vmap``).
    Numpy modules (``_BACKEND == 'numpy'``) get mutable host arrays — their
    generated ``run`` writes fields in place.
    """
    if _is_numpy_module(module):
        jnp = np
    else:
        import jax.numpy as jnp

    ni, nj, nk = domain
    H = int(getattr(module, "_H", 0))
    rng = np.random.default_rng(0)
    fields: Dict[str, Any] = {}
    origins: Dict[str, Tuple[int, int, int]] = {}
    for name, axes in module._AXES.items():
        dtype = module._DTYPES[name]
        if axes == ("I", "J", "K"):
            shape: Tuple[int, ...] = (ni + 2 * H, nj + 2 * H, nk)
            origins[name] = (H, H, 0)
        elif axes == ("I", "J"):
            shape = (ni + 2 * H, nj + 2 * H)
            origins[name] = (H, H, 0)
        else:
            shape = (nk,)
            origins[name] = (0, 0, 0)
        if batch is not None:
            shape = (batch,) + shape
        fields[name] = jnp.asarray(0.5 + rng.random(shape), dtype=dtype)
    scalars = {s: 0.5 for s in module._SCALARS}
    return fields, scalars, origins


def batch_count(module, operand_shapes) -> Optional[int]:
    """The leading member-batch extent implied by the operand shapes, or
    ``None`` for an unbatched call (ranks match the module's field axes)."""
    if not operand_shapes:
        return None
    axes = module._AXES
    for name, shape in operand_shapes:
        if name in axes and len(shape) == len(axes[name]) + 1:
            return int(shape[0])
    return None


def _time_block(
    module,
    fields,
    scalars,
    domain: Tuple[int, int, int],
    origins,
    block: Tuple[int, int],
    warmup: int,
    iters: int,
    batch: Optional[int] = None,
) -> float:
    """Best-of-``iters`` wall time of one tiled call, in microseconds."""
    if _is_numpy_module(module):
        # synchronous host execution: nothing to block on, no batching.
        # The generated run() writes fields in place, so each candidate gets
        # a fresh copy of the synthetic data — otherwise recurrence stencils
        # would drift values across candidates and bias the timings.
        fields = {n: np.array(v, copy=True) for n, v in fields.items()}

        def call():
            module.run(fields, scalars, domain, origins, block=block)

        for _ in range(max(1, warmup)):
            call()
        best = float("inf")
        for _ in range(max(1, iters)):
            t0 = time.perf_counter()
            call()
            best = min(best, time.perf_counter() - t0)
        return best * 1e6

    import jax

    if batch is None:
        run = lambda: module.run(fields, scalars, domain, origins, block=block)  # noqa: E731
    else:
        vmapped = jax.vmap(
            lambda f, s: module.run(f, s, domain, origins, block=block), in_axes=(0, None)
        )
        run = lambda: vmapped(fields, scalars)  # noqa: E731

    def call():
        jax.block_until_ready(run())

    for _ in range(max(1, warmup)):
        call()  # compile + cache warm
    best = float("inf")
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        call()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _domain_key(domain: Tuple[int, int, int], candidates, operand_shapes=None) -> str:
    """Store key for one tuning record.

    The FULL operand shapes participate alongside the compute domain: a
    member-batched (vmapped) run has the same ``(ni, nj, nk)`` domain as the
    unbatched one but a different DMA/compute balance per tile, so it must
    never reuse a ``(BI, BJ)`` tuned for unbatched shapes (and vice versa).
    """
    key = "x".join(str(d) for d in domain)
    if operand_shapes:
        key += "|" + ";".join(
            f"{name}:{'x'.join(str(s) for s in shape)}" for name, shape in sorted(operand_shapes)
        )
    if candidates:
        key += "|" + ";".join(f"{bi}x{bj}" for bi, bj in candidates)
    return key


def _load_store(path) -> Dict[str, Any]:
    try:
        data = json.loads(path.read_text())
        if isinstance(data, dict) and "domains" in data:
            return data
    except (OSError, ValueError):
        pass
    return {"version": 1, "domains": {}}


def select_block(
    module,
    name: str,
    fingerprint: str,
    domain: Tuple[int, int, int],
    *,
    candidates: Optional[Sequence[Tuple[int, int]]] = None,
    warmup: int = 1,
    iters: int = 3,
    operand_shapes=None,
) -> Tuple[Tuple[int, int], Dict[str, Any]]:
    """The tuned ``(BI, BJ)`` for ``domain``, searching at most once.

    ``operand_shapes`` — ``((field_name, shape), ...)`` of the actual call —
    folds the full operand geometry (member/batch axes included) into the
    store key, and batched shapes are timed under ``jax.vmap`` exactly as
    they will run.  Returns ``(block, record)`` where ``record`` carries the
    per-candidate timings (``cache_hit`` marks a persisted result being
    reused).
    """
    domain = tuple(int(d) for d in domain)
    cands = [tuple(c) for c in candidates] if candidates else None
    operand_shapes = (
        tuple(sorted((str(n), tuple(int(x) for x in s)) for n, s in operand_shapes))
        if operand_shapes
        else None
    )
    dkey = _domain_key(domain, cands, operand_shapes)
    path = caching.tuning_path(name, fingerprint)

    with _lock:
        mem = _memory.get((name, fingerprint, dkey))
        if mem is not None:
            rec = dict(mem, cache_hit=True)
            return tuple(rec["block"]), rec
        store = _load_store(path)
        entry = store["domains"].get(dkey)
        if entry is not None:
            rec = dict(entry, cache_hit=True)
            _memory[(name, fingerprint, dkey)] = dict(entry)
            return tuple(rec["block"]), rec

    with otrace.span(
        "stencil.autotune", category="compile", stencil=name, domain=list(domain)
    ) as tsp:
        blocks = candidate_blocks(module, domain, cands)
        batch = batch_count(module, operand_shapes)
        fields, scalars, origins = _synthetic_call_args(module, domain, batch)
        timings: List[Dict[str, Any]] = []
        for block in blocks:
            us = _time_block(
                module, fields, scalars, domain, origins, block, warmup, iters, batch
            )
            timings.append({"block": list(block), "us": us})
        best = min(timings, key=lambda t: t["us"])
        tsp.set("candidates", len(blocks))
        tsp.set("block", list(best["block"]))
        tsp.set("cache_hit", False)
    record: Dict[str, Any] = {
        "block": list(best["block"]),
        "timings": timings,
        "domain": list(domain),
        "batch": batch,
        "cache_hit": False,
    }

    with _lock:
        persisted = {k: v for k, v in record.items() if k != "cache_hit"}
        _memory[(name, fingerprint, dkey)] = persisted
        store = _load_store(path)
        store["domains"][dkey] = persisted
        try:
            tmp = path.with_suffix(".json.tmp")
            tmp.write_text(json.dumps(store, indent=2) + "\n")
            tmp.replace(path)
        except OSError:
            pass  # read-only cache: in-memory result still serves this process
    return tuple(record["block"]), record


def record_batch_observation(
    name: str,
    fingerprint: str,
    batch: int,
    us_per_step: float,
    *,
    source: str = "serving",
) -> None:
    """Merge one *observed* ``(batch size → wall)`` record into the tune store.

    The serving engine calls this with the per-step dispatch wall of batches
    it actually ran, closing the loop the other way around: ``select_block``
    writes measurements the tuner made, this writes measurements the traffic
    made.  Records land under their own ``serving|batch=N`` domain key —
    they carry ``"batch"``, so :func:`repro.serving.engine.tuned_member_counts`
    picks the extents up as preferred padding targets, while the key shape
    keeps them from ever colliding with a tuner-written ``(BI, BJ)`` record.

    Concurrency: the store is read-merged-rewritten under the module lock
    with an atomic (pid-suffixed tmp + ``replace``) publish, so concurrent
    engines — or an engine racing the tuner — never clobber each other's
    *other* keys; the worst cross-process race loses one observation, never
    the store.  The best (minimum) wall wins; observation counts accumulate.
    An unwritable store is ignored — feedback is an optimization, never a
    liveness dependency."""
    path = caching.tuning_path(name, fingerprint)
    dkey = f"serving|batch={int(batch)}"
    with _lock:
        store = _load_store(path)
        prev = store["domains"].get(dkey)
        count = 1
        best = float(us_per_step)
        if isinstance(prev, dict):
            count += int(prev.get("count", 0))
            prev_us = prev.get("us_per_step")
            if isinstance(prev_us, (int, float)):
                best = min(best, float(prev_us))
        store["domains"][dkey] = {
            "batch": int(batch),
            "us_per_step": best,
            "count": count,
            "source": source,
        }
        try:
            tmp = path.with_name(f"{path.name}.{os.getpid()}.tmp")
            tmp.write_text(json.dumps(store, indent=2) + "\n")
            tmp.replace(path)
        except OSError:
            pass  # read-only store: the next engine re-observes, nothing breaks

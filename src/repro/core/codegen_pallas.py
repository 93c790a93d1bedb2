"""Pallas TPU backend: the analogue of the paper's ``gtcuda`` code generator.

TPU adaptation of the GridTools GPU schedule (see DESIGN.md §2):

* The horizontal (i, j) plane is tiled over a 2-D Pallas grid; each grid cell
  DMAs its *tile + halo* from HBM (inputs live in ``ANY`` memory space) into
  VMEM scratch with ``pltpu.make_async_copy`` — TPU blocks cannot overlap, so
  the CUDA shared-memory halo load becomes an explicit strided DMA.
* **Software-prefetched halo DMAs**: every input tile's copy is issued up
  front on its own semaphore, and the ``wait`` is deferred to the first
  multi-stage that touches the field — inputs consumed by later multi-stages
  stream in *while earlier multi-stages compute* instead of serializing
  behind a start-all/wait-all barrier.
* All multi-stages of the stencil execute **fused** inside one kernel while
  the tile is VMEM-resident: intermediate stages (temporaries) never touch
  HBM.  This is the GridTools fusion argument restated for the TPU memory
  hierarchy — the memory-roofline win of the backend.
* The in-kernel layout is chosen from the IR.  A PARALLEL-only kernel keeps
  every array ``(tile_i, tile_j, k)``, K on lanes, and vectorizes each
  multi-stage over the whole block.  A kernel with a FORWARD/BACKWARD
  multi-stage goes **K-major**: each (I, J, K) array is a VMEM ref laid out
  ``(k, tile_i, tile_j)`` (J on lanes, I on sublanes), filled from its halo
  window by one transpose per I row, so a sweep level is the plane
  ``ref[k]`` and a vertical offset a leading-axis slice.  Its sweeps run
  **k-blocked** ``lax.fori_loop``s (``analysis.sequential_carry_plan``):
  API outputs and cross-multi-stage temporaries stay full 3-D refs,
  sweep-local recurrence temporaries collapse to a rolling window of 2-D
  planes — which is what frees VMEM headroom for larger tiles.
* Outputs are written back through regular non-overlapping BlockSpecs.
* The generated module exports ``SCHEDULE`` (layout, DMA waits, carried
  planes, window depths) and ``_vmem_bytes`` (per-tile VMEM estimate) so the
  autotuner (``core/autotune.py``) can filter and time ``(BI, BJ)``
  candidates; ``run`` accepts ``block=`` to override ``_BLOCK_DEFAULT``.
* The ``pallas_call`` is named ``KERNEL_PREFIX`` + the stencil's name, so
  the compiled custom-call, and its events in a device profile, carry the
  stencil's name.

Limitations (documented): written API fields may not be read at nonzero
horizontal offsets (allocate a temporary instead); Mosaic has no 64-bit
vector types, so a float64 stencil runs only in the Pallas interpreter (off
the TPU) and fails to build where the first device is a TPU.  Whether a
kernel runs interpreted is ``StencilObject.interpreted``.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from . import analysis, ir
from .codegen_common import (
    ArrayExprPrinter,
    Emitter,
    _c,
    emit_helpers,
    emit_parallel_block,
    emit_sweep,
    multistage_plan,
)
from .gtscript import GTScriptSemanticError

#: the name of every generated kernel starts with this, then the stencil's name
KERNEL_PREFIX = "gt_"


def _reads_of(impl: ir.StencilImplementation) -> Dict[str, List[Tuple[int, int, int]]]:
    reads: Dict[str, List[Tuple[int, int, int]]] = {}
    for ms in impl.multi_stages:
        for itv in ms.intervals:
            for st in itv.stages:
                for stmt in st.stmts:
                    for n, off in ir.stmt_reads(stmt):
                        reads.setdefault(n, []).append(off)
    return reads


def _writes_of(impl: ir.StencilImplementation) -> List[str]:
    out: List[str] = []
    for ms in impl.multi_stages:
        for itv in ms.intervals:
            for st in itv.stages:
                for w in st.writes:
                    if w not in out:
                        out.append(w)
    return out


def _ms_touched(ms: ir.MultiStage) -> set:
    touched: set = set()
    for itv in ms.intervals:
        for st in itv.stages:
            touched.update(st.reads)
            touched.update(st.writes)
    return touched


def _masked_writes(impl: ir.StencilImplementation) -> set:
    """Fields only ever written under an ``If`` keep their old value on the
    false lanes — the kernel must start from the caller's data, not zeros."""
    masked: set = set()
    for ms in impl.multi_stages:
        for itv in ms.intervals:
            for st in itv.stages:
                for stmt in st.stmts:
                    if isinstance(stmt, ir.If):
                        masked.update(ir.stmt_writes(stmt))
    return masked


def _written_k_coverage_full(impl: ir.StencilImplementation, name: str) -> bool:
    """True when the union of vertical intervals writing ``name`` provably
    covers the whole [START, END) axis (at representation level, so the
    answer is domain-size independent; gaps that only close for specific nk
    count as partial — conservative)."""
    intervals = [
        itv.interval
        for ms in impl.multi_stages
        for itv in ms.intervals
        if any(name in st.writes for st in itv.stages)
    ]
    if not intervals:
        return True
    ivs = sorted(intervals, key=lambda iv: iv.start.key())
    if ivs[0].start != ir.AxisBound(ir.LevelMarker.START, 0):
        return False
    end = ivs[0].end
    for iv in ivs[1:]:
        if iv.start.key() > end.key():
            return False  # gap under large-domain ordering
        if iv.end.key() > end.key():
            end = iv.end
    return end == ir.AxisBound(ir.LevelMarker.END, 0)


def generate_pallas_source(
    impl: ir.StencilImplementation,
    block: Tuple[int, int] = (8, 128),
) -> str:
    api_names = {f.name: f for f in impl.api_fields}
    reads = _reads_of(impl)
    writes = _writes_of(impl)
    written_api = [w for w in writes if w in api_names]
    read_api = [f.name for f in impl.api_fields if f.name in reads]
    # API fields that are both read and written need their tile DMA'd in as
    # the initial value of the functional in-kernel array.  So do outputs
    # whose writes don't provably cover the whole vertical axis, or that are
    # only written under a mask: every other backend preserves the caller's
    # values on unwritten planes / false lanes, and a zeros-initialized
    # kernel array would clobber them (a divergence the backend-differential
    # fuzzer caught on boundary-only outputs).
    masked = _masked_writes(impl)
    inout_api = [
        n
        for n in written_api
        if n in reads or n in masked or not _written_k_coverage_full(impl, n)
    ]
    input_api = [n for n in read_api if n not in written_api] + inout_api

    for n in written_api:
        for off in reads.get(n, []):
            if (off[0], off[1]) != (0, 0):
                raise GTScriptSemanticError(
                    f"pallas backend: written API field {n!r} is read at horizontal offset "
                    f"{off}; stage the value through a temporary instead"
                )

    # vertical reads stay in-domain (analysis._check_vertical_bounds) and the
    # DMA always carries the full column, so only the horizontal halo matters.
    H = max(impl.max_halo[0], impl.max_halo[1])

    axes_of = {f.name: f.axes for f in impl.all_fields}
    dtype_of = {f.name: f.dtype for f in impl.all_fields}
    for n in api_names:
        if axes_of[n] not in (("I", "J", "K"), ("I", "J"), ("K",)):
            raise GTScriptSemanticError(f"pallas backend: unsupported axes {axes_of[n]} for {n!r}")

    # the fields that arrive via an explicit halo DMA (K fields ride whole in VMEM)
    dma_inputs = [n for n in input_api if axes_of[n] != ("K",)]
    k_inputs = [n for n in input_api if axes_of[n] == ("K",)]

    # first multi-stage that touches each DMA'd input — the wait point
    first_use: Dict[str, int] = {}
    for mi, ms in enumerate(impl.multi_stages):
        touched = _ms_touched(ms)
        for n in dma_inputs:
            if n in touched:
                first_use.setdefault(n, mi)
    for n in dma_inputs:
        first_use.setdefault(n, 0)

    # k-blocked sweep plan: which sequential state is carried full vs windowed
    carry_plans = analysis.sequential_carry_plan(impl)
    windowed: Dict[str, int] = {}
    for plan in carry_plans.values():
        windowed.update(dict(plan.window))

    # K-major kernels hold each (I, J, K) array as a (k, i, j) VMEM ref: the
    # DMA'd inputs' transposed windows, pure outputs and temporaries, in the
    # order they follow the DMA semaphores among the kernel's arguments
    kmajor = any(ms.order != ir.IterationOrder.PARALLEL for ms in impl.multi_stages)
    layout = "k_major" if kmajor else "k_minor"
    arrays: List[Tuple[str, str]] = []  # (name, shape expression in bi, bj, nk)
    if kmajor:
        arrays += [(n, f"(nk,) + _window({n!r}, bi, bj, nk)[:2]") for n in dma_inputs if axes_of[n] == ("I", "J", "K")]
        arrays += [(n, "(nk, bi, bj)") for n in written_api if n not in inout_api and axes_of[n] == ("I", "J", "K")]
        for t in impl.temporaries:
            if t.name in windowed:
                continue
            (ilo, ihi), (jlo, jhi), (klo, khi) = impl.extent_of(t.name).as_tuple()
            dims = {"K": f"nk{_c(khi - klo)}", "I": f"bi{_c(ihi - ilo)}", "J": f"bj{_c(jhi - jlo)}"}
            shape = [dims[a] for a in ("K", "I", "J") if a in t.axes] + (["1", "1"] if t.axes == ("K",) else [])
            arrays.append((t.name, f"({', '.join(shape)})"))

    printer = ArrayExprPrinter(impl, "jnp", axes_of, dtype_of, layout=layout)

    # ---------------- kernel body ----------------
    kb = Emitter()
    kb.push()  # inside def _make_kernel
    kb.push()  # inside def _kernel
    kb.line("ni, nj = _BI, _BJ")
    kb.line("nk = _NK")
    kb.line("gi = pl.program_id(0)")
    kb.line("gj = pl.program_id(1)")
    # issue every halo DMA up front, each on its own semaphore; waits are
    # deferred to each field's first-use multi-stage (software prefetch)
    # into a scratch shaped by _window(): the tile, its halo, and tiling padding
    for i, n in enumerate(dma_inputs):
        win = f"pl.ds(gi * _BI, _s_{n}.shape[0]), pl.ds(gj * _BJ, _s_{n}.shape[1])"
        if axes_of[n] == ("I", "J"):
            src = f"{n}_hbm.at[{win}]"
        else:
            src = f"{n}_hbm.at[{win}, :]"
        kb.line(f"_cp_{n} = pltpu.make_async_copy({src}, _s_{n}, _dma_sems.at[{i}])")
        kb.line(f"_cp_{n}.start()")
    for s in impl.scalars:
        kb.line(f"{s.name} = {s.name}_smem[0]")
    # K fields arrive whole in VMEM — no DMA to wait on
    for n in k_inputs:
        if kmajor:
            printer.refs[n] = f"{n}_vmem"
        else:
            kb.line(f"{n} = {n}_vmem[...]")
        kb.line(f"_oi_{n}, _oj_{n}, _ok_{n} = (0, 0, 0)")
    # pure outputs start as zeros (functional in-kernel arrays)
    for n in written_api:
        if n in inout_api:
            continue  # bound from the DMA'd scratch at first use
        axes = axes_of[n]
        if kmajor:
            # a pure output is written on every plane of the tile: no zeros
            if axes == ("I", "J"):
                printer.refs[n] = f"{n}_out_ref"
            kb.line(f"_oi_{n}, _oj_{n}, _ok_{n} = (0, 0, 0)")
            continue
        if axes == ("I", "J", "K"):
            shape = "(ni, nj, nk)"
        elif axes == ("I", "J"):
            shape = "(ni, nj)"
        else:
            shape = "(nk,)"
        kb.line(f"{n} = jnp.zeros({shape}, dtype='{dtype_of[n]}')")
        kb.line(f"_oi_{n}, _oj_{n}, _ok_{n} = (0, 0, 0)")
    # temporaries (in-tile, VMEM-resident — the fusion payoff); sweep-window
    # temporaries materialize as rolling planes inside their sweep instead
    for t in impl.temporaries:
        if t.name in windowed:
            continue
        ext = impl.extent_of(t.name)
        (ilo, ihi), (jlo, jhi), (klo, khi) = ext.as_tuple()
        axes = axes_of[t.name]
        if axes == ("I", "J", "K"):
            shape = f"(ni{_c(ihi - ilo)}, nj{_c(jhi - jlo)}, nk{_c(khi - klo)})"
            origin = (-ilo, -jlo, -klo)
        elif axes == ("I", "J"):
            shape = f"(ni{_c(ihi - ilo)}, nj{_c(jhi - jlo)})"
            origin = (-ilo, -jlo, 0)
        else:
            shape = f"(nk{_c(khi - klo)},)"
            origin = (0, 0, -klo)
        if kmajor:
            kb.line(f"{t.name}[...] = jnp.zeros({t.name}.shape, dtype={t.name}.dtype)")
        else:
            kb.line(f"{t.name} = jnp.zeros({shape}, dtype='{t.dtype}')")
        kb.line(f"_oi_{t.name}, _oj_{t.name}, _ok_{t.name} = {origin}")

    # ----- fused multi-stages, with DMA waits at each input's first use
    for mi, ms in enumerate(impl.multi_stages):
        kb.line(f"# === multi-stage {mi}: {multistage_plan(ms)}")
        for n in dma_inputs:
            if first_use[n] != mi:
                continue
            kb.line(f"_cp_{n}.wait()")
            if kmajor:
                if axes_of[n] == ("I", "J", "K"):
                    # one (J, K) -> (K, J) transpose per I row of the window
                    kb.line(f"for _i in range(_s_{n}.shape[0]):")
                    kb.push()
                    kb.line(f"{n}[:, _i, :] = _s_{n}[_i].T[:nk]")
                    kb.pop()
                else:
                    # an (I, J) window is already laid out as a plane
                    printer.refs[n] = f"_s_{n}"
                kb.line(f"_oi_{n}, _oj_{n}, _ok_{n} = (_H, _H, 0)")
            elif n in inout_api:
                if axes_of[n] == ("I", "J"):
                    kb.line(f"{n} = _s_{n}[_H:_H + ni, _H:_H + nj]")
                else:
                    kb.line(f"{n} = _s_{n}[_H:_H + ni, _H:_H + nj, :nk]")
                kb.line(f"_oi_{n}, _oj_{n}, _ok_{n} = (0, 0, 0)")
            else:
                # read-only: every read loads its window from the scratch
                printer.refs[n] = f"_s_{n}"
                kb.line(f"_oi_{n}, _oj_{n}, _ok_{n} = (_H, _H, 0)")
        if ms.order == ir.IterationOrder.PARALLEL:
            emit_parallel_block(impl, printer, kb, ms, mi, functional=True)
        else:
            emit_sweep(impl, printer, kb, ms, mi, carry_plans[mi], "jnp", carry_full=False)

    for n in written_api:
        if not kmajor:
            kb.line(f"{n}_out_ref[...] = {n}" if axes_of[n] == ("I", "J") else f"{n}_out_ref[:, :, :nk] = {n}")
        elif axes_of[n] == ("I", "J", "K"):
            # back to the (I, J, K) output block, one transpose per I row
            kb.line("for _i in range(ni):")
            kb.push()
            kb.line(f"{n}_out_ref[_i, :, :nk] = {n}[:, _oi_{n} + _i, _oj_{n}:_oj_{n} + nj].T")
            kb.pop()
        elif n in inout_api:
            kb.line(f"{n}_out_ref[...] = _s_{n}[_H:_H + ni, _H:_H + nj]")

    # ---------------- static schedule / VMEM metadata ----------------
    schedule = {
        "layout": layout,
        "halo": H,
        "block_default": tuple(block),
        "dma_inputs": list(dma_inputs),
        "dma_first_use_ms": dict(sorted(first_use.items())),
        "sweeps": {
            mi: {"full": list(plan.full), "window": dict(plan.window)}
            for mi, plan in sorted(carry_plans.items())
        },
        "full_carry_fields": sum(len(p.full) for p in carry_plans.values()),
        "window_fields": len(windowed),
        "window_planes": sum(windowed.values()),
    }

    # per-tile VMEM estimate terms (kind, extra_i, extra_j, copies, itemsize):
    # "ijk" is a (bi+di, bj+dj, nk) block, "ij" a (bi+di, bj+dj) slab, "k" an
    # (nk,) vector.  copies counts the VMEM buffers and in-kernel values; the
    # sum bounds Mosaic's scoped allocation from above (docs/pallas.md).  A
    # K-major kernel adds its ``_arrays`` and holds no whole-array values: an
    # (I, J, K) window and output block count once per buffer.
    vmem_terms: List[Tuple[str, int, int, int, int]] = []

    def kind(name: str) -> str:
        return {("I", "J", "K"): "ijk", ("I", "J"): "ij", ("K",): "k"}[axes_of[name]]

    for n in dma_inputs:  # halo window + the slices loaded from it
        copies = 1 if kmajor and kind(n) == "ijk" else 2
        vmem_terms.append((kind(n), 2 * H, 2 * H, copies, np.dtype(dtype_of[n]).itemsize))
    for n in k_inputs:
        vmem_terms.append(("k", 0, 0, 1, np.dtype(dtype_of[n]).itemsize))
    for n in written_api:  # double-buffered output block + the in-kernel value
        vmem_terms.append((kind(n), 0, 0, 2 if kmajor else 3, np.dtype(dtype_of[n]).itemsize))
    for t in impl.temporaries:
        isz = np.dtype(t.dtype).itemsize
        (ilo, ihi), (jlo, jhi), _ = impl.extent_of(t.name).as_tuple()
        if t.name in windowed:
            vmem_terms.append(("ij", ihi - ilo, jhi - jlo, windowed[t.name] + 1, isz))
        elif not kmajor:
            vmem_terms.append((kind(t.name), ihi - ilo, jhi - jlo, 1, isz))

    # ---------------- module assembly ----------------
    em = Emitter()
    em.line(f'"""Auto-generated by repro.core — stencil {impl.name!r}, backend \'pallas\'."""')
    em.line("import functools")
    em.line("import numpy as np")
    em.line("import jax")
    em.line("import jax.numpy as jnp")
    em.line("from jax import lax")
    em.line("from jax.experimental import pallas as pl")
    em.line("from jax.experimental.pallas import tpu as pltpu")
    emit_helpers(em, printer.used_helpers, "jnp")
    em.line()
    em.line(f"_H = {H}")
    em.line(f"_BLOCK_DEFAULT = {tuple(block)!r}")
    em.line(f"_SCALARS = {[s.name for s in impl.scalars]!r}")
    em.line(f"_INPUT_API = {input_api!r}")
    em.line(f"_WRITTEN_API = {written_api!r}")
    em.line(f"_K_FIELDS = {k_inputs!r}")
    em.line(f"_AXES = {dict(sorted((n, axes_of[n]) for n in api_names))!r}")
    em.line(f"_DTYPES = {dict(sorted((n, dtype_of[n]) for n in api_names))!r}")
    em.line(f"SCHEDULE = {schedule!r}")
    em.line(f"_VMEM_TERMS = {vmem_terms!r}")
    # A 32-bit kernel is traced with x64 off, so every index, loop level and
    # Python constant in it is 32-bit as Mosaic requires.  A 64-bit kernel
    # keeps x64 and runs only interpreted: Mosaic has no 64-bit vector types,
    # so building one where the first device is a TPU fails here, at import.
    wide = sorted({f.dtype for f in impl.all_fields if np.dtype(f.dtype).itemsize == 8})
    em.line(f"_X64 = {bool(wide)!r}")
    em.line("# off the TPU the kernel runs in the Pallas interpreter (StencilObject.interpreted)")
    em.line("INTERPRET = jax.devices()[0].platform != 'tpu'")
    if wide:
        em.line("if not INTERPRET:")
        em.push()
        msg = (
            f"pallas backend: stencil {impl.name!r} has {', '.join(wide)} fields, and kernels on a TPU "
            "take 32-bit and narrower dtypes only (retype it to float32, or use the jax backend)"
        )
        em.line(f"raise TypeError({msg!r})")
        em.pop()
    em.line()
    em.line("def _tiled(n, m):")
    em.push()
    em.line('"""n rounded up to a multiple of m."""')
    em.line("return -(-n // m) * m")
    em.pop()
    em.line()
    em.line("def _sublanes(dtype):")
    em.push()
    em.line('"""Rows of one (sublane, 128-lane) vector tile of dtype."""')
    em.line("return 8 * max(1, 4 // np.dtype(dtype).itemsize)")
    em.pop()
    em.line()
    em.line("def _window(n, bi, bj, nk):")
    em.push()
    em.line('"""Shape of input n\'s VMEM halo window for a (bi, bj) tile: the tile and its')
    em.line('halo, the two minor axes rounded up to the vector tiling Mosaic slices by."""')
    em.line("sub = _sublanes(_DTYPES[n])")
    em.line("if _AXES[n] == ('I', 'J'):")
    em.push()
    em.line("return (_tiled(bi + 2 * _H, sub), _tiled(bj + 2 * _H, 128))")
    em.pop()
    em.line("return (bi + 2 * _H, _tiled(bj + 2 * _H, sub), _tiled(nk, 128))")
    em.pop()
    em.line()
    if kmajor:
        em.line("def _arrays(bi, bj, nk):")
        em.push()
        em.line('"""(shape, dtype) of each K-major VMEM array, (k, i, j), for a (bi, bj) tile')
        em.line('at nk levels, in the order the kernel takes them."""')
        em.line("return [")
        em.push()
        for n, shape in arrays:
            em.line(f"({shape}, {dtype_of[n]!r}),  # {n}")
        em.pop()
        em.line("]")
        em.pop()
        em.line()
    em.line("def _vmem_bytes(bi, bj, nk):")
    em.push()
    em.line('"""Per-tile VMEM footprint estimate for (bi, bj) at nk levels, counted in')
    em.line('whole (sublane, lane) vector tiles as Mosaic lays arrays out."""')
    em.line("total = 0")
    em.line("for kind, di, dj, copies, isz in _VMEM_TERMS:")
    em.push()
    em.line("sub = 8 * max(1, 4 // isz)")
    em.line("if kind == 'ijk':")
    em.push()
    em.line("size = (bi + di) * -(-(bj + dj) // sub) * sub * _tiled(nk, 128)")
    em.pop()
    em.line("elif kind == 'ij':")
    em.push()
    em.line("size = -(-(bi + di) // sub) * sub * _tiled(bj + dj, 128)")
    em.pop()
    em.line("else:")
    em.push()
    # a K-major kernel holds a K field as an (nk, 1, 1) column
    em.line("size = nk * sub * 128" if kmajor else "size = sub * _tiled(nk, 128)")
    em.pop()
    em.line("total += copies * size * isz")
    em.pop()
    if kmajor:
        em.line("for shape, dtype in _arrays(bi, bj, nk):")
        em.push()
        em.line("rows = int(np.prod(shape[:-2])) * _tiled(shape[-2], _sublanes(dtype))")
        em.line("total += rows * _tiled(shape[-1], 128) * np.dtype(dtype).itemsize")
        em.pop()
    em.line("return total")
    em.pop()
    em.line()
    em.line("def _make_kernel(_BI, _BJ, _NK):")
    em.push()
    em.line("def _kernel(" + ", ".join(
        [f"{s.name}_smem" for s in impl.scalars]
        + [f"{n}_vmem" if axes_of[n] == ("K",) else f"{n}_hbm" for n in input_api]
        + [f"{n}_out_ref" for n in written_api]
        + [f"_s_{n}" for n in dma_inputs]
        + (["_dma_sems"] if dma_inputs else [])
        + [n for n, _ in arrays]
    ) + "):")
    em.pop()
    source = em.source() + kb.source()

    tail = Emitter()
    tail.push()
    tail.line("return _kernel")
    tail.pop()
    tail.line()
    tail.line("@functools.lru_cache(maxsize=None)")
    tail.line("def _build(domain, block):")
    tail.push()
    tail.line("ni, nj, nk = domain")
    tail.line("bi = min(block[0], ni)")
    tail.line("bj = min(block[1], nj)")
    tail.line("nti = -(-ni // bi)")
    tail.line("ntj = -(-nj // bj)")
    tail.line("nkp = _tiled(nk, 128)")
    tail.line("kernel = _make_kernel(bi, bj, nk)")
    tail.line("in_specs = []")
    tail.line("for s in _SCALARS:")
    tail.push()
    tail.line("in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))")
    tail.pop()
    tail.line("for n in _INPUT_API:")
    tail.push()
    tail.line("if n in _K_FIELDS:")
    tail.push()
    tail.line("in_specs.append(pl.BlockSpec(memory_space=pltpu.VMEM))")
    tail.pop()
    tail.line("else:")
    tail.push()
    tail.line("in_specs.append(pl.BlockSpec(memory_space=pl.ANY))")
    tail.pop()
    tail.pop()
    tail.line("out_specs = []")
    tail.line("out_shapes = []")
    tail.line("for n in _WRITTEN_API:")
    tail.push()
    tail.line("if _AXES[n] == ('I', 'J', 'K'):")
    tail.push()
    tail.line("out_specs.append(pl.BlockSpec((bi, bj, nkp), lambda i, j: (i, j, 0)))")
    tail.line("out_shapes.append(jax.ShapeDtypeStruct((nti * bi, ntj * bj, nkp), _DTYPES[n]))")
    tail.pop()
    tail.line("elif _AXES[n] == ('I', 'J'):")
    tail.push()
    tail.line("out_specs.append(pl.BlockSpec((bi, bj), lambda i, j: (i, j)))")
    tail.line("out_shapes.append(jax.ShapeDtypeStruct((nti * bi, ntj * bj), _DTYPES[n]))")
    tail.pop()
    tail.line("else:")
    tail.push()
    tail.line("raise NotImplementedError('K-field outputs in pallas backend')")
    tail.pop()
    tail.pop()
    tail.line("scratch = [pltpu.VMEM(_window(n, bi, bj, nk), _DTYPES[n]) for n in _INPUT_API if n not in _K_FIELDS]")
    tail.line("if scratch:")
    tail.push()
    tail.line("# one DMA semaphore per prefetched input tile")
    tail.line("scratch.append(pltpu.SemaphoreType.DMA((len(scratch),)))")
    tail.pop()
    if kmajor:
        tail.line("scratch += [pltpu.VMEM(shape, dtype) for shape, dtype in _arrays(bi, bj, nk)]")
    tail.line("call = pl.pallas_call(kernel, grid=(nti, ntj), in_specs=in_specs, out_specs=out_specs,")
    tail.line("                      out_shape=out_shapes, scratch_shapes=scratch, interpret=INTERPRET,")
    tail.line(f"                      name={KERNEL_PREFIX + impl.name!r})")
    tail.line("return jax.jit(call), (bi, bj, nti, ntj)")
    tail.pop()
    tail.line()
    tail.line("def run(fields, scalars, domain, origins, block=None):")
    tail.push()
    tail.line("ni, nj, nk = domain")
    tail.line("call, (bi, bj, nti, ntj) = _build(tuple(domain), tuple(block or _BLOCK_DEFAULT))")
    tail.line("args = []")
    tail.line("for s in _SCALARS:")
    tail.push()
    tail.line("args.append(jnp.asarray([scalars[s]], dtype=_DTYPES[_WRITTEN_API[0]]))")
    tail.pop()
    tail.line("for n in _INPUT_API:")
    tail.push()
    tail.line("arr = fields[n]")
    tail.line("oi, oj, ok = origins[n]")
    tail.line("if n in _K_FIELDS:")
    tail.push()
    # a K-major kernel takes a K field as an (nk, 1, 1) column
    tail.line("args.append(jax.lax.dynamic_slice(arr, (ok,), (nk,))" + (".reshape(nk, 1, 1))" if kmajor else ")"))
    tail.line("continue")
    tail.pop()
    tail.line("# edge-pad the region so the last tile's window stays in bounds")
    tail.line("win = _window(n, bi, bj, nk)")
    tail.line("pads = [(0, (nti - 1) * bi + win[0] - ni - 2 * _H), (0, (ntj - 1) * bj + win[1] - nj - 2 * _H)]")
    tail.line("if _AXES[n] == ('I', 'J'):")
    tail.push()
    tail.line("region = arr[oi - _H:oi + ni + _H, oj - _H:oj + nj + _H]")
    tail.pop()
    tail.line("else:")
    tail.push()
    tail.line("region = arr[oi - _H:oi + ni + _H, oj - _H:oj + nj + _H, ok:ok + nk]")
    tail.line("pads.append((0, win[2] - nk))")
    tail.pop()
    tail.line("args.append(jnp.pad(region, pads, mode='edge'))")
    tail.pop()
    tail.line("with jax.enable_x64(_X64):")
    tail.push()
    tail.line("outs = call(*args)")
    tail.pop()
    tail.line("updates = {}")
    tail.line("for n, new in zip(_WRITTEN_API, outs):")
    tail.push()
    tail.line("arr = fields[n]")
    tail.line("oi, oj, ok = origins[n]")
    tail.line("if _AXES[n] == ('I', 'J'):")
    tail.push()
    tail.line("updates[n] = arr.at[oi:oi + ni, oj:oj + nj].set(new[:ni, :nj])")
    tail.pop()
    tail.line("else:")
    tail.push()
    tail.line("updates[n] = arr.at[oi:oi + ni, oj:oj + nj, ok:ok + nk].set(new[:ni, :nj, :nk])")
    tail.pop()
    tail.pop()
    tail.line("return updates")
    tail.pop()

    return source + tail.source()

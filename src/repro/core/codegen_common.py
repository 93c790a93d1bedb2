"""Shared source-generation machinery for the array backends (numpy / jax).

Backends generate *actual Python source* (inspectable via
``StencilObject.generated_source``, cached on disk by ``caching.py``), in the
spirit of the paper's code-generating toolchain.

Conventions of generated ``run()`` functions
--------------------------------------------
* ``fields``  : dict name → array, full storage *including halo*
* ``scalars`` : dict name → python/np scalar
* ``domain``  : (ni, nj, nk) compute-domain size (python ints → static)
* ``origins`` : dict name → (oi, oj, ok) offset of the compute-domain origin
  inside each field's storage

Field reads at relative offset (di, dj, dk) from a stage with compute extent
((ilo, ihi), (jlo, jhi)) over vertical interval [k0, k1) become slices::

    arr[o_i + ilo + di : o_i + ni + ihi + di,
        o_j + jlo + dj : o_j + nj + jhi + dj,
        o_k + k0 + dk  : o_k + k1 + dk]          # PARALLEL (3D block)

or, in sequential (FORWARD/BACKWARD) multi-stages, 2D planes at a loop-
carried level ``k``.  Temporaries are allocated inside ``run`` extended by
their required extents, with origins shifted accordingly.
"""

from __future__ import annotations

import io
from typing import Dict, Optional, Tuple

from . import ir


def _c(off: int) -> str:
    """Format '+ n' / '- n' / '' for a constant offset inside a slice."""
    if off == 0:
        return ""
    return f" + {off}" if off > 0 else f" - {-off}"


def bound_expr(b: ir.AxisBound) -> str:
    if b.level == ir.LevelMarker.START:
        return str(b.offset)
    return f"nk{_c(b.offset)}" if b.offset else "nk"


class Emitter:
    def __init__(self) -> None:
        self._buf = io.StringIO()
        self._indent = 0

    def line(self, s: str = "") -> None:
        self._buf.write(("    " * self._indent) + s + "\n" if s else "\n")

    def push(self) -> None:
        self._indent += 1

    def pop(self) -> None:
        self._indent -= 1

    def source(self) -> str:
        return self._buf.getvalue()


class ArrayExprPrinter:
    """Prints ir.Expr as vectorized numpy/jnp source.

    ``mode`` is "block" (PARALLEL: 3D region over [k0, k1)) or "plane"
    (sequential: 2D region at level variable ``k``).
    """

    def __init__(
        self,
        impl: ir.StencilImplementation,
        lib: str,  # 'np' | 'jnp'
        axes_of: Dict[str, Tuple[str, ...]],
        dtype_of: Dict[str, str],
        layout: Optional[str] = None,
    ):
        self.impl = impl
        self.lib = lib
        # Pallas TPU kernels (codegen_pallas) set ``layout``; Mosaic lowers no
        # dynamic_slice or dynamic_update_slice.  "k_minor" (PARALLEL-only
        # kernels): arrays are values with K last, written through ``_put``
        # (plain assignment or an iota-mask select).  "k_major" (kernels with
        # a FORWARD/BACKWARD multi-stage): (I, J, K) arrays are VMEM refs laid
        # out (K, I, J), so level k is the plane ``ref[k, ...]``, read and
        # stored by indexing the leading axis.
        self.layout = layout
        self.axes_of = axes_of
        self.dtype_of = dtype_of
        self.mode = "block"
        self.extent: ir.Extent = ir.Extent.zero()
        self.k0 = "_k0"
        self.k1 = "_k1"
        # horizontal sub-ranges of the compute domain: ("0", "ni") covers the
        # whole domain (the default); the numpy stage-tiling emitter rebinds
        # these to the current tile's bounds ("_t0", "_t1") so every slice is
        # evaluated tile-by-tile.
        self.irange: Tuple[str, str] = ("0", "ni")
        self.jrange: Tuple[str, str] = ("0", "nj")
        self.used_helpers: set = set()
        # demoted temporaries (ir.StencilImplementation.local_decls): bound as
        # plain block/plane variables — reads are the bare name (the demotion
        # pass guarantees zero offsets and shape-identical stage extents).
        self.locals_: set = {f.name for f in impl.local_decls}
        # k-blocked sweep temporaries (analysis.SweepCarryPlan.window): in
        # plane mode, dk=0 reads hit the current plane ``_wp_<name>`` and
        # trailing reads hit the rolling history ``_wh_<name>_<q>`` instead of
        # a full 3-D array.  Bound by emit_sweep for the active multi-stage.
        self.window: Dict[str, int] = {}
        # Pallas fields read straight from a ref (name -> ref expression):
        # an input's VMEM halo scratch rather than an in-kernel value copy,
        # and in a K-major kernel the K columns and (I, J) outputs
        self.refs: Dict[str, str] = {}

    # -- region slices ---------------------------------------------------------

    @staticmethod
    def _hbound(origin: str, bound: str, off: int) -> str:
        if bound == "0":
            return f"{origin}{_c(off)}"
        return f"{origin} + {bound}{_c(off)}"

    def _hslices(self, name: str, di: int, dj: int) -> Tuple[str, str]:
        (ilo, ihi), (jlo, jhi), _ = self.extent.as_tuple()
        i0, i1 = self.irange
        j0, j1 = self.jrange
        si = (
            f"{self._hbound(f'_oi_{name}', i0, ilo + di)}"
            f":{self._hbound(f'_oi_{name}', i1, ihi + di)}"
        )
        sj = (
            f"{self._hbound(f'_oj_{name}', j0, jlo + dj)}"
            f":{self._hbound(f'_oj_{name}', j1, jhi + dj)}"
        )
        return si, sj

    def _kslice(self, name: str, dk: int) -> str:
        if self.mode == "block":
            return f"_ok_{name} + {self.k0}{_c(dk)}:_ok_{name} + {self.k1}{_c(dk)}"
        return f"_ok_{name} + k{_c(dk)}"

    def _kmajor_index(self, name: str, di: int, dj: int, dk: int) -> str:
        """``name``'s region in a K-major kernel: the K index leads, then I, J.
        A K field is an (nk, 1, 1) column, so it broadcasts over the plane."""
        axes = self.axes_of[name]
        parts = [self._kslice(name, dk)] if "K" in axes else []
        if "I" in axes:
            parts.extend(self._hslices(name, di, dj))
        return f"{self.refs.get(name, name)}[{', '.join(parts)}]"

    def kmajor_store(self, name: str) -> Tuple[str, str]:
        """(target, shape) of a stage's write to ``name`` in a K-major kernel."""
        (ilo, ihi), (jlo, jhi), _ = self.extent.as_tuple()
        axes = self.axes_of[name]
        shape = []
        if "K" in axes and self.mode == "block":
            shape.append(f"{self.k1} - {self.k0}")
        if "I" in axes:
            shape.extend([f"ni{_c(ihi - ilo)}", f"nj{_c(jhi - jlo)}"])
        else:
            shape.extend(["1", "1"])
        return self._kmajor_index(name, 0, 0, 0), f"({', '.join(shape)})"

    def read(self, fa: ir.FieldAccess) -> str:
        name = fa.name
        if name in self.locals_:
            return name
        di, dj, dk = fa.offset
        if self.mode == "plane" and name in self.window:
            si, sj = self._hslices(name, di, dj)
            if dk == 0:
                return f"_wp_{name}[{si}, {sj}]"
            return f"_wh_{name}_{abs(dk)}[{si}, {sj}]"
        axes = self.axes_of[name]
        if self.layout == "k_major":
            region = self._kmajor_index(name, di, dj, dk)
            return f"{region}[None]" if axes == ("I", "J") and self.mode == "block" else region
        # a ref (Pallas VMEM scratch) is loaded window by window: no None
        # axes in its index
        ref = self.refs.get(name)
        arr = ref or name
        if axes == ("I", "J", "K"):
            si, sj = self._hslices(name, di, dj)
            return f"{arr}[{si}, {sj}, {self._kslice(name, dk)}]"
        if axes == ("I", "J"):
            si, sj = self._hslices(name, di, dj)
            if self.mode == "block":
                return f"{arr}[{si}, {sj}][:, :, None]" if ref else f"{arr}[{si}, {sj}, None]"
            return f"{arr}[{si}, {sj}]"
        if axes == ("K",):
            if self.mode == "block":
                return f"{arr}[None, None, {self._kslice(name, dk)}]"
            return f"{arr}[{self._kslice(name, dk)}]"
        raise NotImplementedError(f"axes {axes}")

    def write_target(self, name: str) -> str:
        axes = self.axes_of[name]
        if axes == ("I", "J", "K"):
            si, sj = self._hslices(name, 0, 0)
            return f"{name}[{si}, {sj}, {self._kslice(name, 0)}]"
        if axes == ("I", "J"):
            si, sj = self._hslices(name, 0, 0)
            return f"{name}[{si}, {sj}]"
        if axes == ("K",):
            return f"{name}[{self._kslice(name, 0)}]"
        raise NotImplementedError(f"axes {axes}")

    def write_starts_shape(self, name: str) -> Tuple[str, str]:
        """(start-indices tuple expr, region shape tuple expr) for functional
        writes through ``_dus`` / ``_put`` (Pallas kernels may not capture
        the scatter constants `.at[].set()` would create)."""
        axes = self.axes_of[name]
        (ilo, ihi), (jlo, jhi), _ = self.extent.as_tuple()
        si = f"_oi_{name}{_c(ilo)}"
        sj = f"_oj_{name}{_c(jlo)}"
        di = f"ni{_c(ihi - ilo)}"
        dj = f"nj{_c(jhi - jlo)}"
        if self.mode == "block":
            sk = f"_ok_{name} + {self.k0}"
            dk = f"{self.k1} - {self.k0}"
        else:
            sk = f"_ok_{name} + k"
            dk = "1"
        if axes == ("I", "J", "K"):
            return f"({si}, {sj}, {sk})", f"({di}, {dj}, {dk})"
        if axes == ("I", "J"):
            return f"({si}, {sj})", f"({di}, {dj})"
        if axes == ("K",):
            return f"({sk},)", f"({dk},)"
        raise NotImplementedError(f"axes {axes}")

    def plane_write_starts_shape(self, name: str) -> Tuple[str, str]:
        """2-D (starts, shape) for writing a windowed temporary's current
        plane ``_wp_<name>`` in a sequential sweep."""
        (ilo, ihi), (jlo, jhi), _ = self.extent.as_tuple()
        si = f"_oi_{name}{_c(ilo)}"
        sj = f"_oj_{name}{_c(jlo)}"
        di = f"ni{_c(ihi - ilo)}"
        dj = f"nj{_c(jhi - jlo)}"
        return f"({si}, {sj})", f"({di}, {dj})"

    # -- expressions -----------------------------------------------------------

    def expr(self, e: ir.Expr) -> str:
        lib = self.lib
        if isinstance(e, ir.Literal):
            if e.dtype == "bool":
                return "True" if e.value else "False"
            return repr(e.value)
        if isinstance(e, ir.ScalarRef):
            return e.name
        if isinstance(e, ir.FieldAccess):
            return self.read(e)
        if isinstance(e, ir.UnaryOp):
            if e.op == "not":
                return f"{lib}.logical_not({self.expr(e.operand)})"
            return f"({e.op}{self.expr(e.operand)})"
        if isinstance(e, ir.BinOp):
            if e.op == "and":
                return f"{lib}.logical_and({self.expr(e.left)}, {self.expr(e.right)})"
            if e.op == "or":
                return f"{lib}.logical_or({self.expr(e.left)}, {self.expr(e.right)})"
            return f"({self.expr(e.left)} {e.op} {self.expr(e.right)})"
        if isinstance(e, ir.TernaryOp):
            return f"{lib}.where({self.expr(e.cond)}, {self.expr(e.true_expr)}, {self.expr(e.false_expr)})"
        if isinstance(e, ir.NativeCall):
            return self._native(e)
        if isinstance(e, ir.Cast):
            self.used_helpers.add("cast")
            return f"_cast({self.expr(e.expr)}, '{e.dtype}')"
        raise NotImplementedError(f"expr {type(e)}")

    def _native(self, e: ir.NativeCall) -> str:
        lib = self.lib
        args = ", ".join(self.expr(a) for a in e.args)
        fn = e.func
        if fn == "min":
            return f"{lib}.minimum({args})"
        if fn == "max":
            return f"{lib}.maximum({args})"
        if fn == "abs":
            return f"{lib}.abs({args})"
        if fn == "mod":
            return f"{lib}.mod({args})"
        if fn == "pow":
            return f"{lib}.power({args})"
        if fn == "sigmoid":
            self.used_helpers.add("sigmoid")
            return f"_sigmoid({args})"
        if fn in ("erf", "erfc"):
            self.used_helpers.add(fn)
            return f"_{fn}({args})"
        if fn == "gamma":
            self.used_helpers.add("gamma")
            return f"_gamma({args})"
        return f"{lib}.{fn}({args})"


class ArrayStmtEmitter:
    """Emits statements for one (multi-stage, interval) context."""

    def __init__(self, printer: ArrayExprPrinter, em: Emitter, functional: bool):
        self.p = printer
        self.em = em
        # functional=True (jax): writes rebind names via .at[].set();
        # functional=False (numpy): writes mutate slices in place.
        self.functional = functional
        self._mask_counter = 0

    def assign(self, stmt: ir.Assign, mask: Optional[str]) -> None:
        p = self.p
        name = stmt.target.name
        value = p.expr(stmt.value)
        if mask is not None:
            old = p.read(ir.FieldAccess(name, (0, 0, 0)))
            value = f"{p.lib}.where({mask}, {value}, {old})"
        write = "put" if p.layout else "dus"
        if name in p.locals_:
            # demoted temporary: direct variable binding, no field write
            self.em.line(f"{name} = {value}")
        elif p.mode == "plane" and name in p.window:
            # k-blocked sweep temporary: write the current 2-D plane
            starts, shape = p.plane_write_starts_shape(name)
            if p.layout == "k_major" and p.extent.as_tuple()[:2] == p.impl.extent_of(name).as_tuple()[:2]:
                # the stage covers the whole plane: a plain rebinding
                p.used_helpers.add("fit")
                self.em.line(f"_wp_{name} = _fit({value}, {shape}, '{p.dtype_of[name]}')")
            else:
                p.used_helpers.add(write)
                self.em.line(f"_wp_{name} = _{write}(_wp_{name}, {value}, {starts}, {shape})")
        elif p.layout == "k_major":
            p.used_helpers.add("fit")
            target, shape = p.kmajor_store(name)
            self.em.line(f"{target} = _fit({value}, {shape}, '{p.dtype_of[name]}')")
        elif self.functional:
            p.used_helpers.add(write)
            starts, shape = p.write_starts_shape(name)
            self.em.line(f"{name} = _{write}({name}, {value}, {starts}, {shape})")
        else:
            tgt = p.write_target(name)
            self.em.line(f"{tgt} = {value}")

    def if_stmt(self, stmt: ir.If, mask: Optional[str]) -> None:
        p = self.p
        self._mask_counter += 1
        mv = f"_mask_{self._mask_counter}"
        cond = p.expr(stmt.cond)
        self.em.line(f"{mv} = {cond}")
        then_mask = mv if mask is None else f"{p.lib}.logical_and({mask}, {mv})"
        if mask is not None:
            then_v = f"_mask_{self._mask_counter}_t"
            self.em.line(f"{then_v} = {then_mask}")
            then_mask = then_v
        for s in stmt.body:
            self.stmt(s, then_mask)
        if stmt.orelse:
            else_mask = f"{p.lib}.logical_not({mv})"
            if mask is not None:
                else_mask = f"{p.lib}.logical_and({mask}, {else_mask})"
            else_v = f"_mask_{self._mask_counter}_e"
            self.em.line(f"{else_v} = {else_mask}")
            for s in stmt.orelse:
                self.stmt(s, else_v)

    def stmt(self, stmt: ir.Stmt, mask: Optional[str] = None) -> None:
        if isinstance(stmt, ir.Assign):
            self.assign(stmt, mask)
        elif isinstance(stmt, ir.If):
            self.if_stmt(stmt, mask)
        else:
            raise NotImplementedError(type(stmt))


# ---------------------------------------------------------------------------
# Shared preamble / allocation helpers
# ---------------------------------------------------------------------------


def temp_alloc_shape(impl: ir.StencilImplementation, name: str) -> Tuple[str, Tuple[int, int, int]]:
    """Returns (shape_expr, origin) for a temporary field."""
    ext = impl.extent_of(name)
    (ilo, ihi), (jlo, jhi), (klo, khi) = ext.as_tuple()
    axes = impl.field(name).axes
    oi, oj, ok = -ilo, -jlo, -klo
    if axes == ("I", "J", "K"):
        shape = f"(ni{_c(ihi - ilo)}, nj{_c(jhi - jlo)}, nk{_c(khi - klo)})"
        return shape, (oi, oj, ok)
    if axes == ("I", "J"):
        shape = f"(ni{_c(ihi - ilo)}, nj{_c(jhi - jlo)})"
        return shape, (oi, oj, 0)
    if axes == ("K",):
        shape = f"(nk{_c(khi - klo)},)"
        return shape, (0, 0, ok)
    raise NotImplementedError(axes)


def emit_helpers(em: Emitter, used: set, lib: str) -> None:
    if "dus" in used:
        em.line("def _dus(arr, val, starts, shape):")
        em.push()
        em.line("val = jnp.asarray(val, dtype=arr.dtype)")
        em.line("if val.ndim == len(shape) - 1:")
        em.push()
        em.line("val = val[..., None]")
        em.pop()
        em.line("val = jnp.broadcast_to(val, shape)")
        em.line("return lax.dynamic_update_slice(arr, val, starts)")
        em.pop()
    if "put" in used:
        # Every start is a Python int (a K-major kernel stores its sweep
        # levels through refs): the whole-extent test and the pads are
        # decided while the kernel is traced.
        em.line("def _put(arr, val, starts, shape):")
        em.push()
        em.line('"""arr with the box starts/shape set to val: plain assignment when the box')
        em.line('is all of arr, otherwise an iota-mask select over arr."""')
        em.line("val = jnp.asarray(val, dtype=arr.dtype)")
        em.line("if val.ndim == len(shape) - 1:")
        em.push()
        em.line("val = val[..., None]")
        em.pop()
        em.line("if tuple(shape) == arr.shape:")
        em.push()
        em.line("return jnp.broadcast_to(val, shape)")
        em.pop()
        em.line("mask = None")
        em.line("pads = []")
        em.line("for ax, (s, d, n) in enumerate(zip(starts, shape, arr.shape)):")
        em.push()
        em.line("if d == n:")
        em.push()
        em.line("pads.append((0, 0))")
        em.line("continue")
        em.pop()
        # a 1-D iota along ax: the mask broadcasts, no block-sized index array
        em.line("io = lax.broadcasted_iota(jnp.int32, tuple(n if a == ax else 1 for a in range(arr.ndim)), ax)")
        em.line("if isinstance(s, int) and d > 1:")
        em.push()
        em.line("pads.append((s, n - s - d))")
        em.line("m = (io >= s) & (io < s + d)")
        em.pop()
        em.line("else:  # one row: val broadcasts along ax")
        em.push()
        em.line("pads.append(None)")
        em.line("m = io == s")
        em.pop()
        em.line("mask = m if mask is None else mask & m")
        em.pop()
        em.line("val = jnp.broadcast_to(val, tuple(1 if p is None else d for p, d in zip(pads, shape)))")
        em.line("if any(p not in (None, (0, 0)) for p in pads):")
        em.push()
        em.line("val = jnp.pad(val, [p or (0, 0) for p in pads])")
        em.pop()
        em.line("return jnp.where(mask, val, arr)")
        em.pop()
    if "fit" in used:
        em.line("def _fit(val, shape, dtype):")
        em.push()
        em.line('"""val as dtype, broadcast to shape: the value a ref store takes."""')
        em.line("return jnp.broadcast_to(jnp.asarray(val, dtype=dtype), shape)")
        em.pop()
    if "cast" in used:
        em.line("def _cast(x, dt):")
        em.push()
        em.line(f"return {lib}.asarray(x).astype(dt)")
        em.pop()
    if "sigmoid" in used:
        em.line("def _sigmoid(x):")
        em.push()
        em.line(f"return 1.0 / (1.0 + {lib}.exp(-x))")
        em.pop()
    if "erf" in used or "erfc" in used:
        if lib == "np":
            em.line("import math as _math")
            em.line("_erf = _np_vectorize_erf = __import__('numpy').vectorize(_math.erf)")
            em.line("def _erfc(x):")
            em.push()
            em.line("return 1.0 - _erf(x)")
            em.pop()
        else:
            em.line("from jax.scipy.special import erf as _erf")
            em.line("def _erfc(x):")
            em.push()
            em.line("return 1.0 - _erf(x)")
            em.pop()


def emit_parallel_block(
    impl: ir.StencilImplementation,
    printer: ArrayExprPrinter,
    em: Emitter,
    ms: ir.MultiStage,
    mi: int,
    functional: bool,
) -> None:
    """Emit a PARALLEL multi-stage: every statement fully vectorized over its
    3-D region, interval by interval (shared by numpy / jax / pallas)."""
    for ii, itv in enumerate(ms.intervals):
        k0, k1 = f"_k0_{mi}_{ii}", f"_k1_{mi}_{ii}"
        em.line(f"{k0} = {bound_expr(itv.interval.start)}")
        em.line(f"{k1} = {bound_expr(itv.interval.end)}")
        printer.mode = "block"
        printer.k0, printer.k1 = k0, k1
        emitter = ArrayStmtEmitter(printer, em, functional)
        for st in itv.stages:
            printer.extent = st.compute_extent
            for stmt in st.stmts:
                emitter.stmt(stmt)


def emit_sweep(
    impl: ir.StencilImplementation,
    printer: ArrayExprPrinter,
    em: Emitter,
    ms: ir.MultiStage,
    mi: int,
    plan,  # analysis.SweepCarryPlan
    lib: str,
    carry_full: bool = True,
) -> None:
    """Emit a FORWARD/BACKWARD multi-stage as ``lax.fori_loop``s carrying only
    the liveness-proven state (shared by the jax and pallas backends).

    Full fields are carried as whole arrays, unless ``carry_full`` is off:
    a K-major Pallas kernel holds them in VMEM refs the loop body indexes
    and stores into, so only the window planes ride the carry.  Window
    fields carry ``depth`` rolling 2-D history planes (``_wh_<name>_<q>`` is
    the plane ``q`` iterations behind the sweep) plus a per-iteration current
    plane ``_wp_<name>`` — the k-blocking that keeps a sweep's VMEM live set
    bounded by its true vertical dependency depth instead of nk.

    The history planes thread through *every* interval of the multi-stage so
    state chains across interval boundaries; planes the sweep never wrote
    read as zeros, matching the zero-initialized 3-D temporary they replace.
    """
    backward = ms.order == ir.IterationOrder.BACKWARD

    def plane_shape(name: str) -> str:
        (ilo, ihi), (jlo, jhi), _ = impl.extent_of(name).as_tuple()
        return f"(ni{_c(ihi - ilo)}, nj{_c(jhi - jlo)})"

    for name, depth in plan.window:
        (ilo, ihi), (jlo, jhi), _ = impl.extent_of(name).as_tuple()
        em.line(f"_oi_{name}, _oj_{name}, _ok_{name} = ({-ilo}, {-jlo}, 0)")
        dt = impl.field(name).dtype
        for q in range(1, depth + 1):
            em.line(f"_wh_{name}_{q} = {lib}.zeros({plane_shape(name)}, dtype='{dt}')")
    printer.window = dict(plan.window)
    carried = (list(plan.full) if carry_full else []) + [
        f"_wh_{n}_{q}" for n, d in plan.window for q in range(1, d + 1)
    ]
    carry = ", ".join(carried)
    trailing = "," if len(carried) == 1 else ""

    for ii, itv in enumerate(ms.intervals):
        k0, k1 = f"_k0_{mi}_{ii}", f"_k1_{mi}_{ii}"
        em.line(f"{k0} = {bound_expr(itv.interval.start)}")
        em.line(f"{k1} = {bound_expr(itv.interval.end)}")
        printer.mode = "plane"
        em.line(f"def _body_{mi}_{ii}(_it, _carry):")
        em.push()
        if carried:
            em.line(f"({carry}{trailing}) = _carry")
        em.line(f"k = {k1} - 1 - _it" if backward else f"k = {k0} + _it")
        for name, _depth in plan.window:
            dt = impl.field(name).dtype
            em.line(f"_wp_{name} = {lib}.zeros({plane_shape(name)}, dtype='{dt}')")
        emitter = ArrayStmtEmitter(printer, em, functional=True)
        for st in itv.stages:
            printer.extent = st.compute_extent
            for stmt in st.stmts:
                emitter.stmt(stmt)
        for name, depth in plan.window:
            for q in range(depth, 1, -1):
                em.line(f"_wh_{name}_{q} = _wh_{name}_{q - 1}")
            if depth >= 1:
                em.line(f"_wh_{name}_1 = _wp_{name}")
        em.line(f"return ({carry}{trailing})" if carried else "return ()")
        em.pop()
        loop = f"lax.fori_loop(0, {k1} - {k0}, _body_{mi}_{ii}, ({carry}{trailing}))"
        em.line(f"({carry}{trailing}) = {loop}" if carried else loop)
    printer.window = {}


def multistage_plan(ms: ir.MultiStage) -> str:
    """Human-readable schedule line for the generated source header."""
    parts = []
    for itv in ms.intervals:
        parts.append(
            f"[{bound_expr(itv.interval.start)}, {bound_expr(itv.interval.end)}) × {len(itv.stages)} stages"
        )
    return f"{ms.order.name}: " + "; ".join(parts)

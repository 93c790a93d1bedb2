"""Program compiler: dataflow graph → one fused, jit-cached step.

The compilation pipeline (every stage reuses the single-stencil toolchain —
the merged groups go through ``analysis.analyze`` + the ``passes.py``
pipeline + the normal backends, so cross-stencil fusion, CSE and temporary
demotion all fire on the *merged* IR for free):

1. dead-store elimination + grouping (``program.passes``);
2. each group's stencil definitions are **spliced** into one merged
   ``StencilDefinition``: field params rename to program buffer names,
   per-stencil temporaries get a ``_p<node>_`` prefix, scalars rename to
   program scalar names (or ``_c<node>_<param>`` runtime-bound constants),
   and program-internal buffers demote to stencil temporaries
   (``is_api=False``) — the *eliminated temporaries*;
3. an orchestration module is generated (real, inspectable Python source,
   cached by ``core.caching`` under the program fingerprint) that threads
   the buffer dict through the group ``run`` functions and applies the
   output binding — double-buffer rotation is a dict re-wiring, not a copy;
4. for the jax family the orchestrator is wrapped in a single ``jax.jit``.

Fusing never changes values: spliced statements keep their order, crossing
buffers that any later node reads off-center stay API fields of the merged
stencil (so their stale-halo semantics — reads of points no stencil wrote —
are byte-for-byte those of the eager call sequence).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.core import caching, ir
from repro.core import stencil as stencil_mod
from repro.core.storage import Storage
from repro.obs import trace as otrace

from . import halo as halo_planning
from .graph import ProgramGraph
from .passes import (
    Group,
    check_not_empty,
    eliminate_dead_stores,
    plan_groups,
    rotation_plan,
    validate_iterable,
)
from .trace import ProgramError, Trace, tracing


class ProgramCompileError(ProgramError):
    """The traced graph cannot be compiled as requested."""


# ---------------------------------------------------------------------------
# Definition splicing
# ---------------------------------------------------------------------------


def _map_stmt_scalars(stmt: ir.Stmt, smap: Dict[str, str]) -> ir.Stmt:
    def fn(e: ir.Expr) -> ir.Expr:
        if isinstance(e, ir.ScalarRef) and e.name in smap:
            return ir.ScalarRef(smap[e.name])
        return e

    return ir.map_stmt_exprs(stmt, fn)


def splice_group_definition(
    name: str,
    graph: ProgramGraph,
    group: Group,
    node_index: Dict[int, int],
    internals: set,
) -> Tuple[ir.StencilDefinition, Dict[str, Any]]:
    """Merge the group's stencil definitions into one; returns the merged
    definition and the runtime values of its ``_c*`` constant scalars."""
    field_decls: Dict[str, ir.FieldDecl] = {}
    temp_decls: List[ir.FieldDecl] = []
    scalar_decls: Dict[str, ir.ScalarDecl] = {}
    const_values: Dict[str, Any] = {}
    computations: List[ir.ComputationBlock] = []
    externals: List[Tuple[str, Any]] = []

    for node in group.nodes:
        idx = node_index[id(node)]
        defn = node.stencil.definition_ir
        fmap: Dict[str, str] = {}
        for decl in defn.api_fields:
            if decl.is_api:
                buf = node.field_bind[decl.name]
                fmap[decl.name] = buf
                if buf not in field_decls:
                    field_decls[buf] = ir.FieldDecl(buf, decl.dtype, decl.axes, is_api=buf not in internals)
            else:
                new = f"_p{idx}_{decl.name}"
                fmap[decl.name] = new
                temp_decls.append(ir.FieldDecl(new, decl.dtype, decl.axes, is_api=False))
        smap: Dict[str, str] = {}
        for sdecl in defn.scalars:
            kind, ref = node.scalar_bind[sdecl.name]
            if kind == "scalar":
                smap[sdecl.name] = ref
                prev = scalar_decls.get(ref)
                if prev is not None and prev.dtype != sdecl.dtype:
                    raise ProgramCompileError(
                        f"program scalar {ref!r} bound with conflicting dtypes "
                        f"{prev.dtype} / {sdecl.dtype}"
                    )
                scalar_decls[ref] = ir.ScalarDecl(ref, sdecl.dtype)
            else:
                cname = f"_c{idx}_{sdecl.name}"
                smap[sdecl.name] = cname
                scalar_decls[cname] = ir.ScalarDecl(cname, sdecl.dtype)
                const_values[cname] = ref
        for block in defn.computations:
            intervals = tuple(
                ir.IntervalBlock(
                    ib.interval,
                    tuple(_map_stmt_scalars(ir.rename_fields(s, fmap), smap) for s in ib.body),
                )
                for ib in block.intervals
            )
            computations.append(ir.ComputationBlock(block.order, intervals))
        externals.extend((f"_n{idx}_{k}", v) for k, v in defn.externals)

    merged = ir.StencilDefinition(
        name=name,
        api_fields=tuple(field_decls.values()) + tuple(temp_decls),
        scalars=tuple(scalar_decls.values()),
        computations=tuple(computations),
        externals=tuple(externals),
        docstring=f"spliced from {[n.stencil.name for n in group.nodes]}",
    )
    return merged, const_values


# ---------------------------------------------------------------------------
# Orchestrator source generation
# ---------------------------------------------------------------------------


def _generate_orchestrator(
    name: str,
    backend: str,
    group_domains: List[Tuple[int, int, int]],
    group_fields: List[List[str]],
    group_origins: List[Dict[str, Tuple[int, int, int]]],
    alloc_internal: Dict[str, Tuple[Tuple[int, ...], str]],  # name -> (shape, dtype)
    outputs: Dict[str, str],  # output name -> buffer to return
    written_buffers: List[str],  # written program buffers (not temporaries)
) -> str:
    functional = backend in ("jax", "pallas")
    lines: List[str] = [
        f'"""Auto-generated by repro.program — program {name!r}, backend {backend!r}."""',
    ]
    if functional:
        lines.append("import jax.numpy as jnp")
        _zeros = "jnp.zeros"
    else:
        lines.append("import numpy as np")
        _zeros = "np.zeros"
    lines.append("")
    lines.append("def run(fields, scalars, group_runs):")
    lines.append("    vals = dict(fields)")
    for b, (shape, dtype) in sorted(alloc_internal.items()):
        lines.append(
            f"    vals[{b!r}] = {_zeros}({tuple(shape)!r}, dtype={dtype!r})"
            "  # cross-group program temporary"
        )
    for gi, fields in enumerate(group_fields):
        origins = {b: tuple(group_origins[gi][b]) for b in fields}
        dom = tuple(group_domains[gi])
        if functional:
            lines.append(f"    vals.update(group_runs[{gi}](vals, scalars, {dom!r}, {origins!r}))")
        else:
            lines.append(f"    group_runs[{gi}](vals, scalars, {dom!r}, {origins!r})")
    ret = ", ".join(f"{o!r}: vals[{b!r}]" for o, b in outputs.items())
    # written (non-temporary) buffers come back alongside the output binding
    # so every backend persists them into the caller's storages — matching
    # the eager per-stencil path, where each call writes its fields back
    wrt = ", ".join(f"{b!r}: vals[{b!r}]" for b in written_buffers)
    lines.append(f"    return {{{ret}}}, {{{wrt}}}")
    lines.append("")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Shared planning (single-device AND distributed compilers)
# ---------------------------------------------------------------------------


class ProgramPlan:
    """The shared front half of program compilation: dead-store elimination,
    grouping, buffer internalization, and the spliced+built group stencils.
    Both compilers consume this one object so their planning can never
    drift; they differ only in what they *execute* (a generated orchestrator
    vs. a shard_map body with halo exchanges)."""

    def __init__(
        self,
        name: str,
        graph: ProgramGraph,
        backend: str,
        backend_opts,
        validate_args: bool,
        *,
        distributed: bool,
    ):
        nodes, dropped = eliminate_dead_stores(graph)
        check_not_empty(nodes)
        graph.nodes = nodes  # classification and grouping see live nodes only
        self.nodes = nodes
        self.dropped = dropped
        self.stencil_nodes = graph.stencil_nodes()
        self.node_index = {id(n): i for i, n in enumerate(self.stencil_nodes)}
        self.groups, self.markers = plan_groups(
            graph,
            nodes,
            distributed=distributed,
            split_halo_crossing=distributed or backend == "pallas",
        )
        _inputs, _out_buffers, internals = graph.classify()
        if not distributed:
            # internalizing a buffer is only value-preserving when every
            # access agrees on geometry (same compute domain, same buffer
            # origin): the eager path addresses one shared allocation, and
            # positional agreement is what lets a bare domain-sized temporary
            # replace it.  On a mesh geometry is planner-controlled (uniform
            # local domain, per-field padding), so the filter does not apply.
            geo: Dict[str, set] = {}
            for n in self.stencil_nodes:
                for b in set(n.field_bind.values()):
                    geo.setdefault(b, set()).add((n.domain, n.origins[b]))
            internals = [b for b in internals if len(geo.get(b, set())) <= 1]
        # a buffer only becomes a stencil temporary when one group owns every
        # access; internals crossing groups are materialized by the runtime
        # instead (they still never escape the program)
        touching: Dict[str, set] = {}
        for gi, g in enumerate(self.groups):
            for b in g.buffers():
                touching.setdefault(b, set()).add(gi)
        self.temp_internals = sorted(b for b in internals if len(touching.get(b, ())) <= 1)
        self.alloc_internals = sorted(b for b in internals if len(touching.get(b, ())) > 1)
        self.outputs = {o: b for o, (b, _v) in graph.outputs.items()}
        self.const_scalars: Dict[str, Any] = {}
        self.group_objects: List[stencil_mod.StencilObject] = []
        temp_set = set(self.temp_internals)
        for gi, g in enumerate(self.groups):
            merged, consts = splice_group_definition(f"{name}_g{gi}", graph, g, self.node_index, temp_set)
            self.const_scalars.update(consts)
            obj = stencil_mod.build_from_definition(
                merged, backend, validate_args=validate_args, backend_opts=dict(backend_opts or {})
            )
            self.group_objects.append(obj)

    def base_report(self) -> Dict[str, Any]:
        return {
            "nodes": len(self.stencil_nodes),
            "groups": len(self.groups),
            "fused_stencils": len(self.stencil_nodes) - len(self.groups),
            "group_stencils": [[n.stencil.name for n in g.nodes] for g in self.groups],
            "dead_stores_eliminated": self.dropped,
            "eliminated_temporaries": self.temp_internals + self.alloc_internals,
        }


# ---------------------------------------------------------------------------
# Compiled program (single device)
# ---------------------------------------------------------------------------


class CompiledProgram:
    """One traced+compiled specialization of a program (per shapes/origins)."""

    def __init__(self, name: str, graph: ProgramGraph, backend: str, backend_opts, validate_args: bool):
        self.name = name
        self.graph = graph
        self.backend = backend
        t0 = time.perf_counter()
        plan = ProgramPlan(name, graph, backend, backend_opts, validate_args, distributed=False)
        self.nodes = plan.nodes
        self._node_index = plan.node_index
        groups = plan.groups
        self.temp_internals = plan.temp_internals
        self.alloc_internals = plan.alloc_internals
        self.rotation = rotation_plan(graph, plan.nodes)
        self.iterable_reason = validate_iterable(graph)

        self.domain = groups[0].domain
        self.groups = groups
        self.const_scalars = plan.const_scalars
        self.group_objects = plan.group_objects
        self.outputs = plan.outputs
        temp_set = set(self.temp_internals)
        group_fields = [
            [b for b in g.buffers() if b not in temp_set] for g in groups
        ]
        alloc_set = set(self.alloc_internals)
        group_origins = []
        for gi, g in enumerate(groups):
            org = {b: o for b, o in g.origins().items() if b not in temp_set}
            for b in group_fields[gi]:
                org.setdefault(b, (0, 0, 0))
            # orchestrator-allocated temporaries are bare domain-sized arrays
            for b in alloc_set:
                if b in org:
                    org[b] = (0, 0, 0)
            group_origins.append(org)
        alloc = {}
        for b in self.alloc_internals:
            bi = graph.buffers[b]
            dom = next(g.domain for g in groups if b in g.buffers())
            alloc[b] = (_domain_shape(dom, bi.axes), bi.dtype)
        self.written_buffers = [
            b
            for g in groups
            for n in g.nodes
            for b in graph.node_writes(n)
            if b not in temp_set and b not in alloc_set
        ]
        self.written_buffers = list(dict.fromkeys(self.written_buffers))
        source = _generate_orchestrator(
            name,
            backend,
            [g.domain for g in groups],
            group_fields,
            group_origins,
            alloc,
            self.outputs,
            self.written_buffers,
        )
        self.fingerprint = caching.program_fingerprint(
            name,
            graph.structural_repr(),
            [o.fingerprint for o in self.group_objects],
            backend,
            dict(backend_opts or {}),
        )
        self.generated_source = source
        self._module = caching.load_generated_module(f"{name}_prog", self.fingerprint, source)
        self._group_runs = [
            self._bind_group_run(o, g.domain) for o, g in zip(self.group_objects, groups)
        ]
        self._jitted: Optional[Callable] = None
        self._iter_cache: Dict[int, Callable] = {}
        self.report = {
            **plan.base_report(),
            "backend": backend,
            "fingerprint": self.fingerprint,
            "group_multi_stages": [
                len(o.implementation_ir.multi_stages) for o in self.group_objects
            ],
            "rotation": dict(self.rotation),
            "elided_exchanges": len(plan.markers),
            "compile_seconds": 0.0,
        }
        self.report["compile_seconds"] = time.perf_counter() - t0
        otrace.current_tracer().add_span(
            "program.compile",
            t0,
            time.perf_counter(),
            category="compile",
            program=name,
            backend=backend,
            groups=len(groups),
            fused_stencils=self.report["fused_stencils"],
            fingerprint=self.fingerprint,
        )

    # -- execution ---------------------------------------------------------

    def _bind_group_run(self, obj: stencil_mod.StencilObject, domain) -> Callable:
        run = obj._run
        if obj.backend != "pallas":
            return run
        block, _rec = obj._resolve_block(tuple(domain))
        if block is None:
            return run

        def _with_block(fields, scalars, domain, origins):
            return run(fields, scalars, domain, origins, block=tuple(block))

        return _with_block

    def _jit(self) -> Callable:
        if self._jitted is None:
            import jax

            module_run, group_runs = self._module.run, self._group_runs

            def _pure(fields, scalars):
                return module_run(fields, scalars, group_runs)

            self._jitted = jax.jit(_pure)
        return self._jitted

    def runtime_scalars(self, scalar_values: Dict[str, Any]) -> Dict[str, Any]:
        merged = dict(self.const_scalars)
        merged.update(scalar_values)
        return merged

    def execute(
        self,
        raw_fields: Dict[str, Any],
        scalar_values: Dict[str, Any],
        exec_info: Optional[dict] = None,
    ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        """Returns (output binding, written program buffers) — the latter so
        the caller can persist every written field's storage, matching the
        eager per-stencil path on all backends."""
        scalars = self.runtime_scalars(scalar_values)
        if exec_info is not None:
            exec_info["program_report"] = dict(self.report)
            exec_info["run_start_time"] = time.perf_counter()
            out = self._execute_profiled(raw_fields, scalars, exec_info)
            exec_info["run_end_time"] = time.perf_counter()
            return out
        if self.backend in ("jax", "pallas"):
            return self._jit()(raw_fields, scalars)
        return self._module.run(raw_fields, scalars, self._group_runs)

    def _execute_profiled(self, raw_fields, scalars, exec_info) -> Dict[str, Any]:
        """Same generated orchestrator, with each group run timed (eager for
        the jax family so per-group walls are real device times)."""
        functional = self.backend in ("jax", "pallas")
        timings: List[Dict[str, Any]] = []

        def timed(gi: int, fn: Callable) -> Callable:
            def _run(fields, scalars, domain, origins):
                t0 = time.perf_counter()
                out = fn(fields, scalars, domain, origins)
                if functional:
                    for v in out.values():
                        v.block_until_ready()
                timings.append(
                    {
                        "group": gi,
                        "stencils": self.report["group_stencils"][gi],
                        "seconds": time.perf_counter() - t0,
                    }
                )
                return out

            return _run

        runs = [timed(gi, fn) for gi, fn in enumerate(self._group_runs)]
        out = self._module.run(raw_fields, scalars, runs)
        exec_info["program_report"]["node_timings"] = timings
        return out


def _domain_shape(domain: Tuple[int, int, int], axes: Tuple[str, ...]) -> Tuple[int, ...]:
    m = dict(zip(("I", "J", "K"), domain))
    return tuple(m[a] for a in axes)


# ---------------------------------------------------------------------------
# The user-facing @program object
# ---------------------------------------------------------------------------


class ProgramObject:
    """A traced, compiled multi-stencil step function.

    Calling mirrors the stencil convention: fields positional-or-keyword,
    scalars keyword-only.  The first call per argument geometry traces the
    step function and compiles the fused program; later calls dispatch the
    cached jitted step directly.  Outputs follow the step function's return
    binding; ``Storage`` arguments named by an output are rebound in place,
    so a driver loop is just ``for _ in range(nt): prog(phi, ...)``.
    """

    def __init__(
        self,
        definition: Callable,
        backend: str = "numpy",
        *,
        name: Optional[str] = None,
        validate_args: bool = True,
        **backend_opts: Any,
    ):
        import inspect

        self.definition = definition
        self.backend = backend
        self.name = name or definition.__name__
        self.validate_args = validate_args
        self.backend_opts = dict(backend_opts)
        self._cache: Dict[Any, CompiledProgram] = {}
        self.field_params: List[str] = []
        self.scalar_params: List[str] = []
        for p in inspect.signature(definition).parameters.values():
            if p.kind == p.POSITIONAL_OR_KEYWORD:
                self.field_params.append(p.name)
            elif p.kind == p.KEYWORD_ONLY:
                self.scalar_params.append(p.name)
            else:
                raise ProgramError(
                    f"program {self.name!r}: unsupported parameter kind for {p.name!r} "
                    "(fields are positional-or-keyword, scalars keyword-only)"
                )

    # -- binding -----------------------------------------------------------

    def _bind(self, args, kwargs) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        fields: Dict[str, Any] = {}
        if len(args) > len(self.field_params):
            raise TypeError(f"{self.name}() takes {len(self.field_params)} field arguments, got {len(args)}")
        for pname, val in zip(self.field_params, args):
            fields[pname] = val
        scalars: Dict[str, Any] = {}
        for key, val in kwargs.items():
            if key in self.field_params:
                if key in fields:
                    raise TypeError(f"{self.name}() got duplicate field argument {key!r}")
                fields[key] = val
            elif key in self.scalar_params:
                scalars[key] = val
            else:
                raise TypeError(f"{self.name}() got unexpected argument {key!r}")
        missing = [p for p in self.field_params if p not in fields]
        if missing:
            raise TypeError(f"{self.name}() missing field arguments: {missing}")
        missing_s = [p for p in self.scalar_params if p not in scalars]
        if missing_s:
            raise TypeError(f"{self.name}() missing scalar arguments: {missing_s}")
        return fields, scalars

    @staticmethod
    def _raw(value):
        return value.data if isinstance(value, Storage) else value

    def _key(self, fields: Dict[str, Any]):
        parts = []
        for name in self.field_params:  # canonical order: kwargs order must not re-key
            v = fields[name]
            origin = tuple(v.default_origin) if isinstance(v, Storage) else None
            parts.append((name, tuple(v.shape), str(v.dtype), origin))
        return tuple(parts)

    # -- tracing / compiling ------------------------------------------------

    def trace(self, fields: Dict[str, Any], scalars: Dict[str, Any]) -> Trace:
        with otrace.span("program.trace", category="compile", program=self.name) as tsp:
            t = Trace(self.name)
            handles = [t.add_field(n, fields[n]) for n in self.field_params]
            scalar_handles = {n: t.add_scalar(n, scalars[n]) for n in self.scalar_params}
            with tracing(t):
                result = self.definition(*handles, **scalar_handles)
            t.finish(result)
            tsp.set("nodes", len(t.nodes))
        return t

    def compiled(self, fields: Dict[str, Any], scalars: Dict[str, Any]) -> CompiledProgram:
        key = self._key(fields)
        cp = self._cache.get(key)
        if cp is None:
            graph = ProgramGraph(self.trace(fields, scalars))
            cp = CompiledProgram(self.name, graph, self.backend, self.backend_opts, self.validate_args)
            self._validate_fields(cp, fields)
            self._cache[key] = cp
        return cp

    def _validate_fields(self, cp: CompiledProgram, fields: Dict[str, Any]) -> None:
        if not self.validate_args:
            return
        for obj, group in zip(cp.group_objects, cp.groups):
            sub = {n: fields[n] for n in obj.field_info if n in fields}
            origins = obj._resolve_origins(sub, None)
            obj._validate(sub, {}, group.domain, origins)

    # -- execution ----------------------------------------------------------

    def __call__(self, *args, exec_info: Optional[dict] = None, **kwargs):
        fields, scalars = self._bind(args, kwargs)
        cp = self.compiled(fields, scalars)
        raw = {n: self._raw(v) for n, v in fields.items()}
        with otrace.span(
            "program.run", category="program", program=self.name, backend=self.backend
        ):
            outs, writes = cp.execute(raw, dict(scalars), exec_info)
        # every written program buffer persists into its storage (eager
        # parity on all backends), then the output binding rebinds — so a
        # rotation like {"phi": phi_new} wins over phi_new's own write
        self._writeback(fields, writes)
        self._writeback(fields, outs)
        return outs

    @staticmethod
    def _writeback(fields, updates) -> None:
        for name, arr in updates.items():
            store = fields.get(name)
            if isinstance(store, Storage) and store.data is not arr:
                store.data = arr

    def iterate(self, n: int, *args, exec_info: Optional[dict] = None, **kwargs):
        """Run ``n`` fused steps as one ``lax.fori_loop`` dispatch.

        Requires the jax-family backends and a *rotation-closed* output
        binding: every output name rebinds a program field of identical
        geometry, so the step composes with itself.
        """
        if self.backend not in ("jax", "pallas"):
            raise ProgramError(f"iterate() requires the jax/pallas backends, not {self.backend!r}")
        fields, scalars = self._bind(args, kwargs)
        cp = self.compiled(fields, scalars)
        if cp.iterable_reason is not None:
            raise ProgramError(f"program {self.name!r} cannot iterate: {cp.iterable_reason}")
        raw = {n: self._raw(v) for n, v in fields.items()}
        values = cp.runtime_scalars(dict(scalars))
        steps = cp._iter_cache.get(int(n))
        if steps is None:
            import jax
            from jax import lax

            module_run, group_runs = cp._module.run, cp._group_runs

            def _steps(vals, scalars):
                def body(_i, vals):
                    outs, writes = module_run(vals, scalars, group_runs)
                    # per-step state: written buffers update, then the
                    # output binding rebinds (rotation wins over the write)
                    return {**vals, **writes, **outs}

                return lax.fori_loop(0, n, body, vals)

            steps = jax.jit(_steps)
            cp._iter_cache[int(n)] = steps
        with otrace.span(
            "program.iterate", category="program", program=self.name,
            backend=self.backend, steps=int(n),
        ):
            final = steps(raw, values)
        if exec_info is not None:
            exec_info["program_report"] = dict(cp.report)
            exec_info["program_report"]["iterated_steps"] = n
        self._writeback(fields, {b: final[b] for b in fields if b in final})
        return {o: final[o] for o in cp.outputs}

    def distribute(self, mesh, **kwargs) -> "DistributedProgram":
        return DistributedProgram(self, mesh, **kwargs)

    def ensemble(self, members: int, **kwargs):
        """An :class:`repro.ensemble.Ensemble` of this program: ``members``
        perturbed copies advanced in one ``jax.vmap``-batched jit dispatch."""
        from repro.ensemble import Ensemble

        return Ensemble(self, members, **kwargs)

    def __repr__(self) -> str:
        return f"ProgramObject({self.name!r}, backend={self.backend!r})"


def program(
    backend: str = "numpy",
    definition: Optional[Callable] = None,
    *,
    name: Optional[str] = None,
    validate_args: bool = True,
    **backend_opts: Any,
):
    """Decorator: trace a multi-stencil step function into a fused program.

    Mirrors ``gtscript.stencil``'s surface::

        @program(backend="jax")
        def step(phi, u, v, adv, phi_new, *, dt, dx, dy):
            advect(phi, u, v, adv, dx=dx, dy=dy)
            euler(phi, adv, phi_new, dt=dt)
            return {"phi": phi_new, "phi_new": phi}

    ``backend_opts`` pass through to the merged stencils' build (the whole
    pass pipeline / codegen option surface of ``build_from_definition``).
    """

    def _impl(func: Callable) -> ProgramObject:
        return ProgramObject(func, backend, name=name, validate_args=validate_args, **backend_opts)

    if definition is not None:
        return _impl(definition)
    return _impl


# ---------------------------------------------------------------------------
# Distributed programs (mesh-sharded execution with planned halo exchanges)
# ---------------------------------------------------------------------------


class DistributedProgram:
    """A traced program compiled for a 2-D device mesh.

    The horizontal plane is block-decomposed exactly like
    ``stencils.distributed.DistributedStencil``, but the whole step runs as
    *one* ``shard_map``-wrapped jit with the minimal halo-exchange schedule
    computed by ``program.halo`` — a field is exchanged only before the
    first group that reads it off-center since its last write, at exactly
    the depth demanded.
    """

    def __init__(
        self,
        prog: ProgramObject,
        mesh,
        *,
        i_axis: str = "data",
        j_axis: str = "model",
        periodic: Tuple[bool, bool] = (False, False),
    ):
        if prog.backend not in ("jax", "pallas"):
            raise ProgramError("DistributedProgram requires a jax/pallas-backend program")
        self.prog = prog
        self.mesh = mesh
        self.i_axis, self.j_axis = i_axis, j_axis
        self.i_size = int(mesh.shape[i_axis])
        self.j_size = int(mesh.shape[j_axis])
        self.periodic = tuple(periodic)
        self._plans: Dict[Any, "DistributedStepPlan"] = {}
        self._cache: Dict[Any, Callable] = {}
        self._iter_cache: Dict[Any, Callable] = {}

    # -- compilation -------------------------------------------------------

    def _plan_local(
        self, fields: Dict[str, Any], scalars: Dict[str, Any], local_domain
    ) -> "DistributedStepPlan":
        """The per-shard step as a pure function — the shared core of
        ``__call__``, ``iterate`` and the ensemble layer's member-batched
        (``vmap``-wrapped) distributed execution."""
        graph = ProgramGraph(self.prog.trace(fields, scalars))
        pplan = ProgramPlan(
            f"{self.prog.name}_dist",
            graph,
            self.prog.backend,
            self.prog.backend_opts,
            False,  # geometry is planner-controlled; per-shard validation is meaningless
            distributed=True,
        )
        groups = pplan.groups
        plan = halo_planning.plan_halo_exchanges(graph, groups, pplan.markers)
        temp_internals = set(pplan.temp_internals)
        alloc_internals = pplan.alloc_internals
        group_objects = pplan.group_objects
        const_scalars = pplan.const_scalars
        outputs = pplan.outputs
        ni, nj, nk = local_domain
        i_axis, j_axis = self.i_axis, self.j_axis
        i_size, j_size, periodic = self.i_size, self.j_size, self.periodic
        report = {
            **pplan.base_report(),
            "backend": self.prog.backend,
            "mesh": dict(self.mesh.shape),
            "halo_plan": plan.summary(),
            "exchange_bytes_per_step": _shipped_bytes(plan, graph.buffers, local_domain, (i_size, j_size), periodic),
        }
        group_buffers = [[b for b in g.buffers() if b not in temp_internals] for g in groups]
        buffers = graph.buffers
        group_runs = [obj._run for obj in group_objects]
        used_inputs = sorted(
            n
            for n in fields
            if n in buffers and n not in temp_internals and n not in set(alloc_internals)
        )

        from repro.parallel.halo import exchange_halo_2d

        def run_groups(local_fields: Dict[str, Any], scalar_vals: Dict[str, Any]):
            """One per-shard step: planned exchanges + group runs.  Returns
            ``(state, outs)`` — the updated values of every used input, and
            the output binding."""
            import jax
            import jax.numpy as jnp

            scal = dict(const_scalars)
            scal.update(scalar_vals)
            vals = dict(local_fields)
            for b in alloc_internals:
                bi = buffers[b]
                vals[b] = jnp.zeros(_domain_shape(local_domain, bi.axes), dtype=bi.dtype)
            padded: Dict[str, Any] = {}
            depth: Dict[str, int] = {}
            for gi in range(len(groups)):
                for op in plan.before_group(gi):
                    # a stable name for the exchange's permutes and pads in a profile
                    with jax.named_scope(f"halo.exchange[{op.buffer}]"):
                        padded[op.buffer] = exchange_halo_2d(
                            vals[op.buffer], op.halo, i_axis, j_axis, i_size, j_size, periodic
                        )
                    depth[op.buffer] = op.halo
                read_padded = plan.read_depth[gi]
                gf: Dict[str, Any] = {}
                origins: Dict[str, Tuple[int, int, int]] = {}
                for b in group_buffers[gi]:
                    if b in read_padded:
                        d = depth[b]
                        gf[b] = padded[b]
                        origins[b] = (d, d, 0)
                    else:
                        gf[b] = vals[b]
                        origins[b] = (0, 0, 0)
                upd = group_runs[gi](gf, scal, local_domain, origins)
                for b, arr in upd.items():
                    if b in read_padded:
                        d = depth[b]
                        vals[b] = arr[d : d + ni, d : d + nj]
                    else:
                        vals[b] = arr
                    padded.pop(b, None)
                    depth.pop(b, None)
            state = {n: vals[n] for n in used_inputs}
            outs = {o: vals[b] for o, b in outputs.items()}
            return state, outs

        written = {b for n in pplan.stencil_nodes for b in graph.node_writes(n)}
        return DistributedStepPlan(
            run_groups=run_groups,
            used_inputs=used_inputs,
            written_inputs=sorted(written & set(used_inputs)),
            outputs=dict(outputs),
            buffers=buffers,
            report=report,
            iterable_reason=validate_iterable(graph),
        )

    def _spec_for(self, plan: "DistributedStepPlan", name: str, member_axis: Optional[str] = None):
        from jax.sharding import PartitionSpec as P

        axes = plan.buffers[name].axes
        m = (member_axis,) if member_axis is not None else ()
        if axes and axes[0] == "N":
            axes = axes[1:]
        if axes == ("K",):
            return P(*m, None)
        if len(axes) == 2:
            return P(*m, self.i_axis, self.j_axis)
        return P(*m, self.i_axis, self.j_axis, None)

    def _plan_for(self, fields, scalars, local, key) -> "DistributedStepPlan":
        if key not in self._plans:
            self._plans[key] = self._plan_local(fields, scalars, local)
        return self._plans[key]

    def _geometry(self, fields: Dict[str, Any]):
        """(local_domain, cache key) for GLOBAL interior-only field arrays."""
        # the vertical extent must come from a 3-D field — a 2-D (I, J)
        # buffer that happens to be listed first must not collapse nk to 1
        sample = next(
            (v for v in fields.values() if len(v.shape) == 3),
            next(v for v in fields.values() if len(v.shape) >= 2),
        )
        gi, gj = int(sample.shape[0]), int(sample.shape[1])
        if gi % self.i_size or gj % self.j_size:
            raise ProgramError(
                f"global domain ({gi}, {gj}) must tile over the ({self.i_size}, {self.j_size}) mesh"
            )
        nk = int(sample.shape[2]) if len(sample.shape) == 3 else 1
        local = (gi // self.i_size, gj // self.j_size, nk)
        key = (tuple(sorted((n, tuple(v.shape), str(v.dtype)) for n, v in fields.items())), local)
        return local, key

    def _compile(self, plan: "DistributedStepPlan") -> Callable:
        from repro.stencils.distributed import shard_map
        from jax.sharding import PartitionSpec as P
        import jax

        def body(local_fields: Dict[str, Any], scalar_vals: Dict[str, Any]):
            _state, outs = plan.run_groups(local_fields, scalar_vals)
            return outs

        in_specs = ({n: self._spec_for(plan, n) for n in plan.used_inputs}, P())
        out_specs = {o: self._spec_for(plan, b) for o, b in plan.outputs.items()}
        shard_fn = jax.jit(shard_map(body, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs))

        def fn(all_fields, scalar_vals):
            return shard_fn({n: all_fields[n] for n in plan.used_inputs}, scalar_vals)

        return fn

    def _compile_iterate(self, plan: "DistributedStepPlan", n: int) -> Callable:
        from repro.stencils.distributed import shard_map
        from jax.sharding import PartitionSpec as P
        import jax
        from jax import lax

        run_groups, used, outputs = plan.run_groups, plan.used_inputs, plan.outputs

        def body(local_fields: Dict[str, Any], scalar_vals: Dict[str, Any]):
            def step(_i, st):
                # per-step state: written buffers update, then the output
                # binding rebinds — the 2-exchange/step plan runs inside
                # run_groups on every iteration
                state, outs = run_groups(st, scalar_vals)
                return {**state, **outs}

            final = lax.fori_loop(0, n, step, {k: local_fields[k] for k in used})
            return {o: final[o] for o in outputs}

        in_specs = ({n: self._spec_for(plan, n) for n in used}, P())
        out_specs = {o: self._spec_for(plan, b) for o, b in outputs.items()}
        shard_fn = jax.jit(shard_map(body, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs))

        def fn(all_fields, scalar_vals):
            return shard_fn({n: all_fields[n] for n in used}, scalar_vals)

        return fn

    # -- execution ---------------------------------------------------------

    def __call__(
        self,
        fields: Dict[str, Any],
        scalars: Optional[Dict[str, Any]] = None,
        *,
        exec_info: Optional[dict] = None,
    ) -> Dict[str, Any]:
        """``fields``: GLOBAL (interior-only) arrays keyed by program field
        name.  Returns the output binding as global arrays."""
        scalars = dict(scalars or {})
        local, key = self._geometry(fields)
        plan = self._plan_for(fields, scalars, local, key)
        if key not in self._cache:
            self._cache[key] = self._compile(plan)
        fn = self._cache[key]
        if exec_info is not None:
            exec_info["program_report"] = dict(plan.report)
            exec_info["run_start_time"] = time.perf_counter()
        out = fn(fields, scalars)
        if exec_info is not None:
            for v in out.values():
                v.block_until_ready()
            exec_info["run_end_time"] = time.perf_counter()
        return out

    def iterate(
        self,
        n: int,
        fields: Dict[str, Any],
        scalars: Optional[Dict[str, Any]] = None,
        *,
        exec_info: Optional[dict] = None,
    ) -> Dict[str, Any]:
        """Run ``n`` sharded steps in ONE ``shard_map``-wrapped ``fori_loop``
        dispatch, the minimal halo-exchange plan applied on every iteration.

        Requires a rotation-closed output binding (same contract as
        ``ProgramObject.iterate``): every output name rebinds a program field
        of identical geometry, so the sharded step composes with itself.
        Returns the output binding as global arrays after step ``n``.
        """
        scalars = dict(scalars or {})
        local, key = self._geometry(fields)
        plan = self._plan_for(fields, scalars, local, key)
        if plan.iterable_reason is not None:
            raise ProgramError(f"distributed program {self.prog.name!r} cannot iterate: {plan.iterable_reason}")
        ikey = (key, int(n))
        if ikey not in self._iter_cache:
            self._iter_cache[ikey] = self._compile_iterate(plan, int(n))
        fn = self._iter_cache[ikey]
        if exec_info is not None:
            exec_info["program_report"] = dict(plan.report)
            exec_info["program_report"]["iterated_steps"] = int(n)
            exec_info["run_start_time"] = time.perf_counter()
        out = fn(fields, scalars)
        if exec_info is not None:
            for v in out.values():
                v.block_until_ready()
            exec_info["run_end_time"] = time.perf_counter()
        return out


class DistributedStepPlan:
    """The compiled-but-unwrapped per-shard step of a distributed program:
    everything ``shard_map`` wrappers (single-step, iterated, member-batched)
    need, with the planning done exactly once per argument geometry."""

    def __init__(self, *, run_groups, used_inputs, written_inputs, outputs, buffers, report, iterable_reason):
        self.run_groups = run_groups
        self.used_inputs = list(used_inputs)
        #: the used inputs some stencil of the step writes
        self.written_inputs = list(written_inputs)
        self.outputs = dict(outputs)
        self.buffers = buffers
        self.report = report
        self.iterable_reason = iterable_reason


def _shipped_bytes(plan, buffers, local_domain, mesh_sizes, periodic) -> int:
    """Bytes one chip sends per step under the exchange ``plan``, for one
    member, as the mean over the mesh's chips (a chip on a non-periodic edge
    has no neighbour on that side).  Each exchange ships ``halo``-deep I
    stripes, then J stripes that carry the I halo rows
    (``parallel.halo.exchange_halo_2d``)."""
    import numpy as np

    from repro.parallel.halo import _perm_down, _perm_up

    ni, nj, nk = local_domain
    sends = [(len(_perm_up(n, p)) + len(_perm_down(n, p))) / n for n, p in zip(mesh_sizes, periodic)]
    total = 0.0
    for op in plan.exchanges:
        bi, h = buffers[op.buffer], op.halo
        depth = nk if "K" in bi.axes else 1
        total += np.dtype(bi.dtype).itemsize * h * depth * (sends[0] * nj + sends[1] * (ni + 2 * h))
    return int(round(total))

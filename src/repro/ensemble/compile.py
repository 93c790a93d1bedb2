"""Ensemble compiler: N members of a ``@program`` in one batched dispatch.

``Ensemble(prog, members=N)`` turns the per-member step into a single
``jax.vmap``-batched, jit-cached dispatch:

1. the single-member program is compiled (and cached) exactly as if it were
   called on one member — ``Ensemble`` slices member-0 views out of the
   batched storages and reuses ``ProgramObject.compiled``, so the traced
   graph, program passes, fused groups, and generated orchestrator are all
   shared with the unbatched path;
2. the generated orchestrator's pure ``run`` is wrapped in ``jax.vmap``
   (member axis 0 for batched fields, broadcast for shared ones) and one
   ``jax.jit``: N members advance in ONE dispatch instead of N;
3. ``iterate(n)`` nests the vmapped step inside one ``lax.fori_loop`` — n
   steps × N members, still one dispatch;
4. the batched compilation is cached under a fingerprint that folds the
   member count and the batch pattern into the program fingerprint.

Fields may be member-batched (leading ``N`` axis — state being forecast) or
shared (no member axis — static forcing like winds or orography, broadcast
by vmap without materializing N copies).  Everything the program *writes*
must be batched: members would otherwise race on one buffer.

Scalars are shared by default; a 1-D array of length N is a *per-member*
scalar (e.g. a perturbed physics constant) and is mapped over.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.core import caching
from repro.core.storage import Storage
from repro.obs import trace as otrace
from repro.program.compile import CompiledProgram, DistributedProgram, ProgramObject
from repro.program.trace import ProgramError

from .batch import EnsembleError, member_sample
from .stats import EnsembleStatistics

_JAX_FAMILY = ("jax", "pallas")


class Ensemble:
    """N perturbed members of one program, advanced as a single dispatch."""

    def __init__(self, prog: ProgramObject, members: int, *, name: Optional[str] = None):
        if not isinstance(prog, ProgramObject):
            raise EnsembleError(f"Ensemble wraps a @program object, got {type(prog).__name__}")
        if prog.backend not in _JAX_FAMILY:
            raise EnsembleError(f"Ensemble requires the jax/pallas backends (vmap batching), not {prog.backend!r}")
        self.prog = prog
        self.members = int(members)
        if self.members < 1:
            raise EnsembleError(f"members must be positive, got {members}")
        self.name = name or f"{prog.name}_ens{self.members}"
        self._cache: Dict[Any, "_CompiledEnsemble"] = {}

    # -- binding / batching ------------------------------------------------

    def _bind(self, args, kwargs) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        return self.prog._bind(args, kwargs)

    def _batch_pattern(self, fields: Dict[str, Any]) -> Dict[str, bool]:
        pattern: Dict[str, bool] = {}
        for n, v in fields.items():
            batched = isinstance(v, Storage) and v.is_member_batched
            if batched and v.members != self.members:
                raise EnsembleError(f"field {n!r} holds {v.members} members, ensemble has {self.members}")
            pattern[n] = batched
        if not any(pattern.values()):
            raise EnsembleError(
                f"ensemble {self.name!r} called with no member-batched field: allocate "
                "state with repro.ensemble.batch (axes ('N', 'I', 'J', 'K')) or perturb()"
            )
        return pattern

    def _scalar_pattern(self, scalars: Dict[str, Any]) -> Dict[str, bool]:
        out: Dict[str, bool] = {}
        for n, v in scalars.items():
            per_member = getattr(v, "ndim", 0) == 1
            if per_member and int(v.shape[0]) != self.members:
                raise EnsembleError(
                    f"per-member scalar {n!r} has length {int(v.shape[0])}, "
                    f"ensemble has {self.members}"
                )
            out[n] = per_member
        return out

    # -- compilation -------------------------------------------------------

    def _key(self, fields: Dict[str, Any], pattern: Dict[str, bool]):
        """Cache key from metadata only — the hot path must not materialize
        member-0 device slices just to look up the compiled artifact."""
        parts = []
        for name in self.prog.field_params:
            v = fields[name]
            shape = tuple(v.shape)
            origin = tuple(v.default_origin) if isinstance(v, Storage) else None
            if pattern[name]:
                shape = shape[1:]
                origin = origin[1:] if origin is not None else None
            parts.append((name, shape, str(v.dtype), origin))
        return (tuple(parts), tuple(sorted(pattern.items())))

    def compiled(self, fields: Dict[str, Any], scalars: Dict[str, Any]) -> "_CompiledEnsemble":
        pattern = self._batch_pattern(fields)
        key = self._key(fields, pattern)
        ce = self._cache.get(key)
        if ce is None:
            samples = {n: member_sample(v) for n, v in fields.items()}
            cp = self.prog.compiled(samples, scalars)
            ce = _CompiledEnsemble(self, cp, pattern)
            self._cache[key] = ce
        return ce

    # -- execution ---------------------------------------------------------

    @staticmethod
    def _raw(value):
        return value.data if isinstance(value, Storage) else value

    def __call__(self, *args, exec_info: Optional[dict] = None, **kwargs) -> Dict[str, Any]:
        fields, scalars = self._bind(args, kwargs)
        ce = self.compiled(fields, scalars)
        raw = {n: self._raw(v) for n, v in fields.items()}
        outs, writes = ce.execute(raw, dict(scalars), exec_info)
        ProgramObject._writeback(fields, writes)
        ProgramObject._writeback(fields, outs)
        return outs

    def iterate(self, n: int, *args, exec_info: Optional[dict] = None, **kwargs) -> Dict[str, Any]:
        """n fused steps of all N members: ONE ``fori_loop`` dispatch."""
        fields, scalars = self._bind(args, kwargs)
        ce = self.compiled(fields, scalars)
        raw = {n_: self._raw(v) for n_, v in fields.items()}
        final = ce.execute_iterate(int(n), raw, dict(scalars), exec_info)
        ProgramObject._writeback(fields, {b: final[b] for b in fields if b in final})
        return {o: final[o] for o in ce.cp.outputs}

    # -- companions --------------------------------------------------------

    def statistics(self, dtype: str = "float64", **backend_opts: Any) -> EnsembleStatistics:
        """The fused statistics stencil sized for this ensemble."""
        return EnsembleStatistics(self.members, self.prog.backend, dtype=dtype, **backend_opts)

    def distribute(self, mesh, **kwargs) -> "DistributedEnsemble":
        return DistributedEnsemble(self, mesh, **kwargs)

    def __repr__(self) -> str:
        return f"Ensemble({self.prog.name!r}, members={self.members}, backend={self.prog.backend!r})"


class _CompiledEnsemble:
    """One batched specialization: (program geometry, batch pattern)."""

    def __init__(self, ensemble: Ensemble, cp: CompiledProgram, pattern: Dict[str, bool]):
        self.ensemble = ensemble
        self.cp = cp
        self.pattern = dict(pattern)
        self.members = ensemble.members
        shared = sorted(n for n, b in pattern.items() if not b)
        written = set(cp.written_buffers) | set(cp.outputs.values())
        # output names that rebind program fields receive batched values on
        # writeback, so they must be batched exactly like written buffers
        written |= {o for o in cp.outputs if o in pattern}
        bad = sorted(b for b in written if not pattern.get(b, False))
        if bad:
            raise EnsembleError(
                f"ensemble {ensemble.name!r}: program writes {bad}, but those fields are "
                "not member-batched — members would race on one shared buffer; allocate "
                "them with a leading 'N' axis (repro.ensemble.batch)"
            )
        self.fingerprint = caching.program_fingerprint(
            ensemble.name,
            cp.fingerprint,
            [cp.fingerprint],
            cp.backend,
            {"members": self.members, "batched": tuple(sorted(pattern.items()))},
        )
        self._group_runs = self._bind_group_runs()
        self._jit_cache: Dict[Any, Callable] = {}
        self._iter_cache: Dict[Any, Callable] = {}
        self.report = {
            "members": self.members,
            "batched_fields": sorted(n for n, b in pattern.items() if b),
            "shared_fields": shared,
            "fingerprint": self.fingerprint,
            "program_report": dict(cp.report),
        }

    def _bind_group_runs(self) -> List[Callable]:
        """Group runs with the pallas tile re-resolved for BATCHED operand
        shapes (the autotune store keys on the full geometry, so a batched
        run never reuses a tile tuned for unbatched shapes)."""
        cp = self.cp
        if cp.backend != "pallas":
            return list(cp._group_runs)
        runs: List[Callable] = []
        for obj, g in zip(cp.group_objects, cp.groups):
            run = obj._run
            shapes = []
            for b in g.buffers():
                if b not in obj.field_info:
                    continue
                shape = _member_shape(cp, b)
                if shape is None:
                    continue
                if self.pattern.get(b, False):
                    shape = (self.members,) + shape
                shapes.append((b, shape))
            block, _rec = obj._resolve_block(tuple(g.domain), shapes or None)
            if block is None:
                runs.append(run)
            else:
                runs.append(_with_block(run, tuple(block)))
        return runs

    def _axes(self, scalar_pattern: Dict[str, bool]):
        field_axes = {n: 0 if b else None for n, b in self.pattern.items()}
        scalar_axes = {n: 0 if b else None for n, b in scalar_pattern.items()}
        # runtime-bound const scalars are always shared
        scalar_axes.update({n: None for n in self.cp.const_scalars})
        return field_axes, scalar_axes

    def _jit(self, scalar_pattern: Dict[str, bool]) -> Callable:
        skey = tuple(sorted(scalar_pattern.items()))
        fn = self._jit_cache.get(skey)
        if fn is None:
            import jax

            module_run, group_runs = self.cp._module.run, self._group_runs
            field_axes, scalar_axes = self._axes(scalar_pattern)

            def _pure(fields, scalars):
                return module_run(fields, scalars, group_runs)

            fn = jax.jit(jax.vmap(_pure, in_axes=(field_axes, scalar_axes)))
            self._jit_cache[skey] = fn
        return fn

    def execute(
        self,
        raw_fields: Dict[str, Any],
        scalar_values: Dict[str, Any],
        exec_info: Optional[dict] = None,
    ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        scalars = self.cp.runtime_scalars(scalar_values)
        fn = self._jit(self.ensemble._scalar_pattern(scalar_values))
        if exec_info is not None:
            exec_info["ensemble_report"] = dict(self.report)
            exec_info["run_start_time"] = time.perf_counter()
        with otrace.span(
            "ensemble.dispatch", category="ensemble",
            ensemble=self.ensemble.name, members=self.members,
        ):
            outs, writes = fn(raw_fields, scalars)
        if exec_info is not None:
            for v in outs.values():
                v.block_until_ready()
            exec_info["run_end_time"] = time.perf_counter()
        return outs, writes

    def execute_iterate(
        self,
        n: int,
        raw_fields: Dict[str, Any],
        scalar_values: Dict[str, Any],
        exec_info: Optional[dict] = None,
    ) -> Dict[str, Any]:
        if self.cp.iterable_reason is not None:
            raise ProgramError(
                f"ensemble {self.ensemble.name!r} cannot iterate: {self.cp.iterable_reason}"
            )
        scalar_pattern = self.ensemble._scalar_pattern(scalar_values)
        ikey = (int(n), tuple(sorted(scalar_pattern.items())))
        steps = self._iter_cache.get(ikey)
        if steps is None:
            import jax
            from jax import lax

            module_run, group_runs = self.cp._module.run, self._group_runs
            field_axes, scalar_axes = self._axes(scalar_pattern)
            # only member-batched entries leave the loop: shared (broadcast)
            # fields must not come back N-replicated — vmap's out_axes=0
            # would hand every member's identical copy to the writeback
            keep = sorted(b for b, batched in self.pattern.items() if batched)

            def _steps(vals, scalars):
                def body(_i, vals):
                    outs, writes = module_run(vals, scalars, group_runs)
                    return {**vals, **writes, **outs}

                final = lax.fori_loop(0, n, body, vals)
                return {b: final[b] for b in keep}

            steps = jax.jit(jax.vmap(_steps, in_axes=(field_axes, scalar_axes)))
            self._iter_cache[ikey] = steps
        scalars = self.cp.runtime_scalars(scalar_values)
        if exec_info is not None:
            exec_info["ensemble_report"] = dict(self.report)
            exec_info["ensemble_report"]["iterated_steps"] = int(n)
            exec_info["run_start_time"] = time.perf_counter()
        with otrace.span(
            "ensemble.iterate", category="ensemble",
            ensemble=self.ensemble.name, members=self.members, steps=int(n),
        ):
            final = steps(raw_fields, scalars)
        if exec_info is not None:
            for v in final.values():
                v.block_until_ready()
            exec_info["run_end_time"] = time.perf_counter()
        return final


def _member_shape(cp: CompiledProgram, buffer: str) -> Optional[Tuple[int, ...]]:
    bi = cp.graph.buffers.get(buffer)
    if bi is None:
        return None
    return tuple(int(s) for s in bi.shape)


def _with_block(run: Callable, block: Tuple[int, int]) -> Callable:
    def _fn(fields, scalars, domain, origins):
        return run(fields, scalars, domain, origins, block=block)

    return _fn


# ---------------------------------------------------------------------------
# Member × domain sharding
# ---------------------------------------------------------------------------


class DistributedEnsemble:
    """Members × domain tiles co-sharded over a 3-D device mesh.

    The horizontal plane is block-decomposed exactly like
    :class:`~repro.program.compile.DistributedProgram` (same per-shard step,
    same minimal halo-exchange plan) while the member axis shards over
    ``member_axis``; within a shard the local members advance under
    ``jax.vmap``, which *batches the halo exchanges* — each planned
    ``ppermute`` ships one stripe carrying every local member instead of one
    collective per member.

    Call convention follows ``DistributedProgram``: a dict of GLOBAL
    interior-only arrays, member-batched fields with a leading ``N`` axis,
    shared fields without it.  For bare arrays only the rank-4
    ``(N, Ni, Nj, Nk)`` form is recognized as batched — a batched 2-D
    ``(I, J)`` field is rank-3 and indistinguishable from an unbatched
    volume, so it must be passed as a member-batched :class:`Storage`
    (whose axes disambiguate).
    """

    def __init__(
        self,
        ensemble: Ensemble,
        mesh,
        *,
        member_axis: str = "ens",
        i_axis: str = "data",
        j_axis: str = "model",
        periodic: Tuple[bool, bool] = (False, False),
    ):
        self.ensemble = ensemble
        self.dp = DistributedProgram(ensemble.prog, mesh, i_axis=i_axis, j_axis=j_axis, periodic=periodic)
        self.mesh = mesh
        self.member_axis = member_axis
        self.m_size = int(mesh.shape[member_axis])
        if ensemble.members % self.m_size:
            raise EnsembleError(
                f"{ensemble.members} members must tile over the {self.m_size}-way "
                f"{member_axis!r} mesh axis"
            )
        self._cache: Dict[Any, Tuple[Callable, dict]] = {}
        self._iter_cache: Dict[Any, Tuple[Callable, dict]] = {}

    def _bind(self, fields: Dict[str, Any]):
        """Raw arrays, which fields are member-batched, and the member-0
        global samples that key and plan the per-member step (shapes only:
        no member slice is materialized on the device)."""
        import jax

        raw = {n: (v.data if isinstance(v, Storage) else v) for n, v in fields.items()}
        samples = {}
        batched = {}
        for n, v in raw.items():
            if isinstance(fields[n], Storage):
                b = fields[n].is_member_batched
            else:
                b = len(v.shape) == 4  # (N, Ni, Nj, Nk) bare-array convention
            batched[n] = b
            samples[n] = jax.ShapeDtypeStruct(tuple(v.shape[1:]), v.dtype) if b else v
        if not any(batched.values()):
            raise EnsembleError(
                f"distributed ensemble {self.ensemble.name!r} called with no member-batched "
                "field (expected a leading axis of length N on the forecast state)"
            )
        for n, b in batched.items():
            if b and int(raw[n].shape[0]) != self.ensemble.members:
                raise EnsembleError(
                    f"field {n!r} holds {int(raw[n].shape[0])} members, "
                    f"ensemble has {self.ensemble.members}"
                )
        local, geo_key = self.dp._geometry(samples)
        return raw, batched, samples, local, (geo_key, tuple(sorted(batched.items())))

    def __call__(
        self,
        fields: Dict[str, Any],
        scalars: Optional[Dict[str, Any]] = None,
        *,
        exec_info: Optional[dict] = None,
    ) -> Dict[str, Any]:
        scalars = dict(scalars or {})
        raw, batched, samples, local, key = self._bind(fields)
        if key not in self._cache:
            self._cache[key] = self._compile(samples, scalars, local, batched, key)
        fn, report = self._cache[key]
        if exec_info is not None:
            exec_info["ensemble_report"] = dict(report)
            exec_info["run_start_time"] = time.perf_counter()
        out = fn(raw, scalars)
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
        """Run ``n`` member-batched sharded steps in ONE ``shard_map``-wrapped
        ``fori_loop`` dispatch, the halo-exchange plan applied on every
        iteration: the same results as ``n`` calls of ``__call__``.

        The member-batched fields the step uses are the loop's carried state
        and are **donated**: their buffers become the result's, so the caller's
        arrays are deleted and a batched :class:`Storage` is rebound to the
        result.  Returns that state after step ``n`` as global arrays keyed by
        field name (the output binding, for a rotation-closed program, plus
        the other carried fields), so ``fields.update(dens.iterate(n, fields))``
        feeds it back without a copy.  Shared fields are read, never donated.
        """
        scalars = dict(scalars or {})
        raw, batched, samples, local, key = self._bind(fields)
        ikey = (key, int(n))
        if ikey not in self._iter_cache:
            self._iter_cache[ikey] = self._compile_iterate(samples, scalars, local, batched, key, int(n))
        fn, report = self._iter_cache[ikey]
        if exec_info is not None:
            exec_info["ensemble_report"] = dict(report)
            exec_info["run_start_time"] = time.perf_counter()
        with otrace.span(
            "ensemble.mesh_iterate", category="ensemble",
            ensemble=self.ensemble.name, members=self.ensemble.members, steps=int(n),
        ):
            final = fn(raw, scalars)
        ProgramObject._writeback(fields, final)
        if exec_info is not None:
            for v in final.values():
                v.block_until_ready()
            exec_info["run_end_time"] = time.perf_counter()
        return final

    def _report(self, plan, batched) -> Dict[str, Any]:
        per_shard = self.ensemble.members // self.m_size
        return {
            "members": self.ensemble.members,
            "member_axis": self.member_axis,
            "members_per_shard": per_shard,
            "batched_fields": sorted(n for n, b in batched.items() if b),
            # one stripe carries every local member (the vmapped ppermute)
            "exchanges_per_step": len(plan.report["halo_plan"]["ops"]),
            "exchange_bytes_per_step": per_shard * plan.report["exchange_bytes_per_step"],
            "program_report": dict(plan.report),
        }

    def _spec(self, plan, name: str, is_batched: bool):
        return self.dp._spec_for(plan, name, self.member_axis if is_batched else None)

    def _compile(self, samples, scalars, local, batched, plan_key):
        import jax
        from jax.sharding import PartitionSpec as P

        from repro.stencils.distributed import shard_map

        plan = self.dp._plan_for(samples, scalars, local, plan_key)
        bad = sorted(b for o, b in plan.outputs.items() if not batched.get(b, False))
        if bad:
            raise EnsembleError(f"distributed ensemble outputs rebind {bad}, which are not member-batched")
        used = plan.used_inputs
        in_axes = {n: 0 if batched[n] else None for n in used}
        vstep = jax.vmap(lambda f, s: plan.run_groups(f, s)[1], in_axes=(in_axes, None))

        def body(local_fields, scalar_vals):
            return vstep(local_fields, scalar_vals)

        in_specs = ({n: self._spec(plan, n, batched[n]) for n in used}, P())
        out_specs = {o: self._spec(plan, b, True) for o, b in plan.outputs.items()}
        shard_fn = jax.jit(shard_map(body, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs))

        def fn(all_fields, scalar_vals):
            return shard_fn({n: all_fields[n] for n in used}, scalar_vals)

        return fn, self._report(plan, batched)

    def _compile_iterate(self, samples, scalars, local, batched, plan_key, n: int):
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from repro.stencils.distributed import shard_map

        plan = self.dp._plan_for(samples, scalars, local, plan_key)
        if plan.iterable_reason is not None:
            raise ProgramError(
                f"distributed ensemble {self.ensemble.name!r} cannot iterate: {plan.iterable_reason}"
            )
        carried = [b for b in plan.used_inputs if batched[b]]
        shared = [b for b in plan.used_inputs if not batched[b]]
        bad = sorted(set(plan.written_inputs) - set(carried))
        if bad:
            raise EnsembleError(
                f"distributed ensemble {self.ensemble.name!r} writes {bad}, which are not member-batched"
            )
        run_groups = plan.run_groups

        def member_steps(state, fixed, scalar_vals):
            def step(_i, st):
                # per-step state: written buffers update, then the output
                # binding rebinds (rotation wins over the write)
                new, outs = run_groups({**st, **fixed}, scalar_vals)
                merged = {**new, **outs}
                return {b: merged[b] for b in carried}

            return lax.fori_loop(0, n, step, state)

        vsteps = jax.vmap(member_steps, in_axes=(0, None, None))
        in_specs = (
            {b: self._spec(plan, b, True) for b in carried},
            {b: self._spec(plan, b, False) for b in shared},
            P(),
        )
        out_specs = {b: self._spec(plan, b, True) for b in carried}
        shard_fn = jax.jit(
            shard_map(vsteps, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs), donate_argnums=0
        )

        def fn(all_fields, scalar_vals):
            return shard_fn({b: all_fields[b] for b in carried}, {b: all_fields[b] for b in shared}, scalar_vals)

        report = self._report(plan, batched)
        report["iterated_steps"] = n
        return fn, report

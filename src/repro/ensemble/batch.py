"""Member-batched storages: allocation and member-axis plumbing.

An ensemble field is one :class:`repro.core.storage.Storage` whose leading
axis is the member axis ``N`` (``axes=("N", "I", "J", "K")``, origin 0 along
``N``).  Stencils and programs never see the member axis — the ensemble
compiler slices per-member views for compilation and batches execution with
``jax.vmap`` — so everything the single-member toolchain knows (halos,
origins, dtypes, (8, 128) alignment padding) is computed per member and is
identical between batched and unbatched allocations.

The host copies of the serving path are spans, one per phase, so a profile
tells them apart: ``ensemble.stack`` (numpy stacking and broadcast copies),
``ensemble.to_device`` (the put of a batched storage into its backend),
``ensemble.wait`` (the host waiting for a gathered member's device result)
and ``ensemble.to_host`` (device-to-host copies).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import numpy as np

from repro.core import storage as core_storage
from repro.core.storage import Storage
from repro.obs import trace as otrace
from repro.program.trace import ProgramError


class EnsembleError(ProgramError):
    """An ensemble was constructed or called inconsistently."""


MEMBER_AXIS = "N"


def batched_axes(axes: Sequence[str]) -> Tuple[str, ...]:
    if axes and axes[0] == MEMBER_AXIS:
        raise EnsembleError(f"axes {tuple(axes)} already carry a member axis")
    return (MEMBER_AXIS,) + tuple(axes)


def is_member_batched(value: Any) -> bool:
    return isinstance(value, Storage) and value.is_member_batched


def member_count(value: Any) -> Optional[int]:
    return value.members if isinstance(value, Storage) else None


def zeros(
    members, shape, dtype="float64", backend="numpy", default_origin=None, axes=None, alignment=None
) -> Storage:
    return _alloc_batched("zeros", members, shape, dtype, backend, default_origin, axes, alignment)


def ones(
    members, shape, dtype="float64", backend="numpy", default_origin=None, axes=None, alignment=None
) -> Storage:
    return _alloc_batched("ones", members, shape, dtype, backend, default_origin, axes, alignment)


def empty(
    members, shape, dtype="float64", backend="numpy", default_origin=None, axes=None, alignment=None
) -> Storage:
    return _alloc_batched("empty", members, shape, dtype, backend, default_origin, axes, alignment)


def _alloc_batched(fill, members, shape, dtype, backend, default_origin, axes, alignment) -> Storage:
    shape = tuple(int(s) for s in shape)
    if axes is None:
        axes = ("I", "J", "K")[: len(shape)]
    if default_origin is None:
        default_origin = (0,) * len(shape)
    return core_storage._alloc(
        (int(members),) + shape,
        dtype,
        backend,
        (0,) + tuple(default_origin),
        fill,
        batched_axes(axes),
        alignment,
    )


def storage_for_domain(
    members: int,
    domain: Tuple[int, int, int],
    halo: Tuple[int, int, int],
    dtype="float64",
    backend="numpy",
    fill="zeros",
    axes=("I", "J", "K"),
    alignment=None,
) -> Storage:
    """Member-batched twin of ``core.storage.storage_for_domain``."""
    return core_storage.storage_for_domain(
        domain, halo, dtype=dtype, backend=backend, fill=fill, axes=axes, alignment=alignment, members=int(members)
    )


def from_member_arrays(arrays, backend="numpy", default_origin=None, dtype=None, axes=None) -> Storage:
    """Stack per-member arrays (or per-member ``Storage``) into one batched
    storage — members must agree on shape and dtype."""
    raws = [np.asarray(a) for a in arrays]
    if not raws:
        raise EnsembleError("from_member_arrays() needs at least one member")
    if any(r.shape != raws[0].shape for r in raws):
        raise EnsembleError(f"member shapes disagree: {sorted({r.shape for r in raws})}")
    first = arrays[0]
    if isinstance(first, Storage):
        default_origin = default_origin if default_origin is not None else first.default_origin
        axes = axes if axes is not None else first.axes
    with otrace.span("ensemble.stack"):
        data = np.stack(raws, axis=0)
        if dtype is not None:
            data = data.astype(dtype)
    if axes is None:
        axes = ("I", "J", "K")[: raws[0].ndim]
    if default_origin is None:
        default_origin = (0,) * raws[0].ndim
    with otrace.span("ensemble.to_device"):
        return Storage(
            data, backend=backend, default_origin=(0,) + tuple(default_origin), axes=batched_axes(axes)
        )


def broadcast(value: Any, members: int, backend=None) -> Storage:
    """Replicate one field across ``members`` identical members (the batched
    form of an unperturbed initial condition)."""
    with otrace.span("ensemble.to_host"):
        arr = np.asarray(value.data if isinstance(value, Storage) else value)
    with otrace.span("ensemble.stack"):
        data = np.broadcast_to(arr, (int(members),) + arr.shape).copy()
    if isinstance(value, Storage):
        backend = backend or value.backend
        origin, axes = (0,) + tuple(value.default_origin), batched_axes(value.axes)
    else:
        backend = backend or "numpy"
        origin, axes = (0,) * (arr.ndim + 1), batched_axes(("I", "J", "K")[: arr.ndim])
    with otrace.span("ensemble.to_device"):
        return Storage(data, backend=backend, default_origin=origin, axes=axes)


def scatter_members(arrays, members: int, *, template: Storage, backend=None) -> Storage:
    """Scatter request-shaped arrays onto member slots of one batched storage.

    The serving path: ``arrays[i]`` (a plain per-request array shaped like
    ``template``) lands in member slot ``i``; slots ``len(arrays)..members-1``
    are padded with copies of the LAST array.  Padding is free correctness-wise
    because vmapped members are independent — padded members compute garbage
    nobody gathers — and it lets a partial batch reuse the jit artifact of the
    nearest tuned member count instead of compiling a new one per batch size.
    """
    arrays = list(arrays)
    if not arrays:
        raise EnsembleError("scatter_members() needs at least one request array")
    if len(arrays) > int(members):
        raise EnsembleError(f"cannot scatter {len(arrays)} requests onto {members} member slots")
    for i, a in enumerate(arrays):
        shape = tuple(np.asarray(a).shape)
        if shape != tuple(template.shape):
            raise EnsembleError(
                f"request array {i} has shape {shape}, template field expects {tuple(template.shape)}"
            )
    pad = [arrays[-1]] * (int(members) - len(arrays))
    return from_member_arrays(
        arrays + pad,
        backend=backend or template.backend,
        default_origin=template.default_origin,
        dtype=str(template.dtype),
        axes=template.axes,
    )


def gather_member(batched, m: int) -> np.ndarray:
    """Gather member ``m`` back out as a host numpy copy.

    The inverse of :func:`scatter_members` — used by the serving engine to
    peel request ``m``'s state out of a batched storage for streaming, so the
    returned array must not alias device or batch memory.  A device member is
    waited for (``ensemble.wait``) before it is copied (``ensemble.to_host``)."""
    if not isinstance(batched, Storage):
        with otrace.span("ensemble.to_host"):
            return np.array(np.asarray(batched)[int(m)], copy=True)
    if not batched.is_member_batched:
        raise EnsembleError(f"storage with axes {batched.axes} has no member axis to gather")
    data = batched.member(int(m)).data
    if hasattr(data, "block_until_ready"):
        with otrace.span("ensemble.wait"):
            data.block_until_ready()
    with otrace.span("ensemble.to_host"):
        return np.array(np.asarray(data), copy=True)


def member_view(batched: Storage, m: int) -> Storage:
    """The per-member storage for member ``m`` (copy-free on numpy)."""
    return batched.member(m)


def member_sample(value: Any):
    """The member-0 view used to key/compile the single-member program."""
    if is_member_batched(value):
        return value.member(0)
    return value

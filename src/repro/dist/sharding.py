"""Logical-axis sharding context for the whole model/train/launch stack.

Every mesh-aware module programs against three *logical* axes:

  * ``"dp"`` — data parallelism; resolves to every physical mesh axis that
    is not the tensor axis (``("data",)`` on a pod, ``("pod", "data")``
    multi-pod);
  * ``"tp"`` — tensor parallelism; resolves to ``("model",)``;
  * ``"sp"`` — sequence parallelism; resolves to ``("model",)`` only while
    a ``sequence_sharding(True)`` scope is active (long-context prefill
    shards the sequence over the tensor axis instead of heads), ``None``
    otherwise.

The active mesh lives in a thread-local stack managed by ``use_mesh``;
``current()`` returns a ``MeshContext`` whose ``tp``/``dp`` are always
``>= 1`` so call sites never need ``max(ctx.tp, 1)`` defenses.  With no
mesh active every operation degrades to a single-device no-op —
``shard(x, ...)`` returns ``x`` itself (identity, zero overhead).

``spec_for(shape, *axes)`` adds the divisibility fallback used everywhere
a concrete shape is known: a logical axis is dropped from the spec when
the resolved mesh-axis product does not divide the dimension, and size-1
mesh axes are dropped outright (sharding over them is a no-op that only
bloats the HLO).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# name of the physical tensor-parallel mesh axis; every other axis is data
TP_AXIS = "model"

LogicalAxis = Union[None, str, Tuple[str, ...]]


class _ThreadState(threading.local):
    def __init__(self):
        self.mesh_stack: list = []
        self.seq_sharding: bool = False


_STATE = _ThreadState()


def pad_to_multiple(n: int, m: int) -> int:
    """Round ``n`` up to the next multiple of ``m`` (``m < 1`` -> ``n``)."""
    if m <= 1:
        return n
    return ((n + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class MeshContext:
    """Resolved view of the active mesh (or the inactive singleton).

    ``tp``/``dp`` are guaranteed ``>= 1``; ``dp_axes``/``tp_axes`` are the
    physical axis-name tuples the logical axes resolve to (empty when
    inactive or when the mesh lacks the axis).
    """
    active: bool
    mesh: Optional[Mesh]
    tp: int
    dp: int
    dp_axes: Tuple[str, ...] = ()
    tp_axes: Tuple[str, ...] = ()

    @classmethod
    def from_mesh(cls, mesh: Mesh) -> "MeshContext":
        names = tuple(mesh.axis_names)
        tp_axes = tuple(n for n in names if n == TP_AXIS)
        dp_axes = tuple(n for n in names if n != TP_AXIS)
        tp = max(int(math.prod(mesh.shape[n] for n in tp_axes)), 1)
        dp = max(int(mesh.devices.size) // tp, 1)
        return cls(active=True, mesh=mesh, tp=tp, dp=dp,
                   dp_axes=dp_axes, tp_axes=tp_axes)

    def resolve(self, axis: LogicalAxis) -> Optional[Tuple[str, ...]]:
        """Logical axis -> physical mesh-axis tuple (``None`` = replicated)."""
        if axis is None or not self.active:
            return None
        if isinstance(axis, tuple):
            out: Tuple[str, ...] = ()
            for a in axis:
                r = self.resolve(a)
                if r:
                    out += r
            return out or None
        if axis == "dp":
            return self.dp_axes or None
        if axis == "tp":
            return self.tp_axes or None
        if axis == "sp":
            return (self.tp_axes or None) if _STATE.seq_sharding else None
        if self.mesh is not None and axis in self.mesh.axis_names:
            return (axis,)
        raise ValueError(f"unknown logical axis {axis!r} "
                         f"(mesh axes: {self.mesh and self.mesh.axis_names})")

    def pspec(self, *logical_axes: LogicalAxis) -> P:
        """Direct resolution (no shape, no divisibility fallback)."""
        entries = []
        for ax in logical_axes:
            r = self.resolve(ax)
            if not r:
                entries.append(None)
            elif len(r) == 1:
                entries.append(r[0])
            else:
                entries.append(r)
        return P(*entries)


_INACTIVE = MeshContext(active=False, mesh=None, tp=1, dp=1)


def current() -> MeshContext:
    """The innermost active MeshContext (thread-local), or the no-op one."""
    if _STATE.mesh_stack:
        return _STATE.mesh_stack[-1]
    return _INACTIVE


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Activate ``mesh`` for the current thread; yields the MeshContext."""
    ctx = MeshContext.from_mesh(mesh)
    _STATE.mesh_stack.append(ctx)
    try:
        yield ctx
    finally:
        _STATE.mesh_stack.pop()


@contextlib.contextmanager
def sequence_sharding(enabled: bool = True):
    """Scope in which the ``"sp"`` logical axis resolves to the tensor axis."""
    prev = _STATE.seq_sharding
    _STATE.seq_sharding = enabled
    try:
        yield
    finally:
        _STATE.seq_sharding = prev


def spec_for(shape: Sequence[int], *axes: LogicalAxis) -> P:
    """PartitionSpec for ``shape`` with the divisibility fallback.

    Per dimension: resolve the logical axis, drop size-1 mesh axes, and
    drop the whole entry when the remaining axis-size product does not
    divide the dimension (or the mesh axis was already used by an earlier
    dimension — a spec may name each mesh axis once).
    """
    ctx = current()
    ndim = len(shape)
    assert len(axes) <= ndim, (shape, axes)
    padded = tuple(axes) + (None,) * (ndim - len(axes))
    if not ctx.active:
        return P(*(None,) * ndim)
    mesh_shape = ctx.mesh.shape
    used: set = set()
    entries = []
    for dim, ax in zip(shape, padded):
        r = ctx.resolve(ax)
        names = tuple(n for n in (r or ())
                      if mesh_shape[n] > 1 and n not in used)
        if not names or dim % math.prod(mesh_shape[n] for n in names) != 0:
            entries.append(None)
            continue
        used.update(names)
        entries.append(names[0] if len(names) == 1 else names)
    return P(*entries)


def shard(x, *axes: LogicalAxis):
    """Constrain ``x`` to the logical-axis layout under the active mesh.

    Identity (returns ``x`` itself) when no mesh is active or when every
    axis falls back to replicated, so single-device paths pay nothing.
    """
    ctx = current()
    if not ctx.active:
        return x
    spec = spec_for(x.shape, *axes)
    if all(e is None for e in spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


def shard_map(f, mesh: Mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with ``check_vma`` off by default, because the
    MoE/embedding bodies do manual psums over "model"."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)

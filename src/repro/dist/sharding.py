"""Mesh-aware placement: logical axes → mesh axes → shardings.

The models layer annotates arrays with *logical* axis names
(``shard(x, "batch", None, "ff")``, ``axes_mlp() -> {"w_in": ("fsdp",
"ff"), ...}``).  This module owns the translation to physical placement:

  * a :class:`ShardingCtx` (mesh + logical→mesh rules) is installed with
    the :func:`use_sharding` context manager;
  * :func:`shard` applies a ``with_sharding_constraint`` when a mesh is
    active and is an exact no-op otherwise — the models stay importable
    and correct on a single device;
  * :func:`resolve_spec` / :func:`named_sharding` / :func:`tree_shardings`
    build ``PartitionSpec`` / ``NamedSharding`` trees for pjit in/out
    shardings (the dry-run and the checkpoint restore path use these).

Resolution is *safe by construction*: a logical axis whose mesh axis is
absent from the active mesh, already used by an earlier dimension, or
does not divide the dimension size is silently dropped (the array stays
replicated along that dimension).  That is what lets one set of model
annotations serve the 512-chip dry-run mesh, an 8-device host mesh, and
the single-CPU smoke tests without per-target configuration.
"""

from __future__ import annotations

import contextlib
import math
from contextvars import ContextVar
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

AxisName = Union[str, None]
# one logical name may map to several mesh axes (e.g. batch → (pod, data))
Rules = Dict[str, Union[str, Tuple[str, ...], None]]

# Default logical→mesh rules for the production meshes
# (("data", "model") single-pod, ("pod", "data", "model") multi-pod).
# "seq_sp" (Megatron-style sequence parallelism) and "fsdp" are off by
# default; a hillclimb enables them via ``use_sharding(mesh, rules=...)``.
DEFAULT_RULES: Rules = {
    "batch": ("pod", "data"),
    "ff": "model",
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "experts": "model",
    "fsdp": None,
    "seq_sp": None,
    "cache_seq": None,
}


@dataclass(frozen=True)
class ShardingCtx:
    """Active placement context: a mesh plus logical→mesh axis rules."""

    mesh: Optional[Mesh] = None
    rules: Rules = field(default_factory=dict)

    def mesh_axes_for(self, logical: AxisName) -> Tuple[str, ...]:
        """Mesh axes a logical axis maps to on *this* mesh (may be ())."""
        if logical is None or self.mesh is None:
            return ()
        if logical in self.rules:
            mapped = self.rules[logical]
        elif logical in self.mesh.axis_names:
            mapped = logical          # direct mesh-axis reference
        else:
            mapped = None
        if mapped is None:
            return ()
        if isinstance(mapped, str):
            mapped = (mapped,)
        return tuple(a for a in mapped if a in self.mesh.axis_names)


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis ``Auto``.

    Placement in this package is declared with ``with_sharding_constraint``
    and left to GSPMD, which only accepts ``Auto`` axes; ``jax.make_mesh``
    builds ``Explicit`` ones.  Called where a ``NamedSharding`` is built
    (``use_sharding`` and :mod:`repro.dist.ivm_shard`), so callers may pass
    any mesh.  Same devices, same axis names and sizes."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


_CTX: ContextVar[ShardingCtx] = ContextVar(
    "repro_sharding_ctx", default=ShardingCtx(mesh=None, rules=DEFAULT_RULES))


def current_ctx() -> ShardingCtx:
    """The innermost active context (mesh is None outside use_sharding)."""
    return _CTX.get()


@contextlib.contextmanager
def use_sharding(mesh: Mesh, rules: Optional[Rules] = None):
    """Install ``mesh`` (plus optional rule overrides) for the duration.

    >>> with use_sharding(jax.make_mesh((4, 2), ("data", "model"))) as ctx:
    ...     state = init_train_state(model, rng)      # annotations resolve
    ...     step = jax.jit(make_train_step(model))
    """
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    ctx = ShardingCtx(mesh=auto_axes(mesh), rules=merged)
    token = _CTX.set(ctx)
    try:
        yield ctx
    finally:
        _CTX.reset(token)


def resolve_spec(axes: Sequence[AxisName],
                 shape: Optional[Sequence[int]],
                 ctx: Optional[ShardingCtx] = None) -> P:
    """Logical axes (one per dimension) → a PartitionSpec valid on the
    active mesh.

    Drops (replicates) any dimension whose mapped mesh axes are absent,
    already claimed by an earlier dimension, or do not divide the
    dimension size (checked when ``shape`` is given).
    """
    ctx = ctx or current_ctx()
    if ctx.mesh is None:
        return P()
    used: set = set()
    out = []
    for i, logical in enumerate(axes):
        mesh_axes = []
        for a in ctx.mesh_axes_for(logical):
            if a in used:
                continue
            size = ctx.mesh.shape[a]
            if shape is not None:
                dim = int(shape[i])
                span = size * math.prod(ctx.mesh.shape[x] for x in mesh_axes)
                if dim % span != 0 or span > dim:
                    continue
            mesh_axes.append(a)
            used.add(a)
        if not mesh_axes:
            out.append(None)
        elif len(mesh_axes) == 1:
            out.append(mesh_axes[0])
        else:
            out.append(tuple(mesh_axes))
    while out and out[-1] is None:          # trailing Nones are implicit
        out.pop()
    return P(*out)


def named_sharding(axes: Sequence[AxisName],
                   shape: Optional[Sequence[int]] = None,
                   ctx: Optional[ShardingCtx] = None) -> NamedSharding:
    """A :class:`NamedSharding` on the active mesh for one array.

    ``named_sharding((), None)`` is the replicated sharding (scalars,
    RNG keys, step counters).
    """
    ctx = ctx or current_ctx()
    if ctx.mesh is None:
        raise ValueError("named_sharding needs an active mesh "
                         "(wrap in use_sharding)")
    return NamedSharding(ctx.mesh, resolve_spec(axes, shape, ctx))


def _is_axes_leaf(x: Any) -> bool:
    return isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)


def tree_shardings(axes_tree: Any, shapes_tree: Any,
                   ctx: Optional[ShardingCtx] = None) -> Any:
    """Map a logical-axes pytree + matching shapes pytree → NamedShardings.

    ``axes_tree`` mirrors the parameter tree with per-leaf logical-axis
    tuples (``model.param_axes()``); ``shapes_tree`` holds arrays or
    ``ShapeDtypeStruct``s.  Used for pjit in/out shardings and for
    resharding a restored checkpoint onto a new mesh.
    """
    ctx = ctx or current_ctx()
    return jax.tree.map(
        lambda ax, s: named_sharding(ax, tuple(s.shape), ctx),
        axes_tree, shapes_tree, is_leaf=_is_axes_leaf)


def shard(x: jax.Array, *axes: AxisName) -> jax.Array:
    """Constrain ``x``'s placement by logical axis names, one per dim.

    A no-op when no mesh is active (single-device tests) or when no axis
    resolves on the current mesh — the annotation is declarative, the
    context decides whether it binds.
    """
    ctx = current_ctx()
    if ctx.mesh is None:
        return x
    if len(axes) != getattr(x, "ndim", None):
        return x
    spec = resolve_spec(axes, x.shape, ctx)
    if all(a is None for a in spec):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))

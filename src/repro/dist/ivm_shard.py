"""Row-sharded IVM execution (paper §6, Data Partitioning / Fig. 3f).

The paper's parallelization claim, executed: a compiled trigger is a
straight-line chain of (big × skinny) matmuls followed by rank-k view
sweeps, so placing every maintained n×m view **row-sharded** across the
mesh makes each firing embarrassingly parallel —

  * factor blocks like ``A·u`` read only local rows of ``A``;
  * transposed reads (``Aᵀ·q``) reduce to an all-gather of a *skinny*
    (n × k) intermediate, O(n·k) on the wire;
  * the ``M += U Vᵀ`` sweeps are purely local row updates.

Re-evaluation on the same layout moves whole matrices: one n×n matmul
between two row-sharded operands all-gathers O(n²) bytes.  That gap is
the paper's Fig. 3f finding (INCR is far less sensitive to cluster size
than REEVAL), reproduced structurally by ``benchmarks/bench_scaling.py``
from the compiled collective schedules of the two functions below.

Placement is declared with ``with_sharding_constraint`` inside the staged
computation and GSPMD inserts the minimal collectives — the trigger body
itself is the *same* code the single-device engine runs
(:func:`repro.core.codegen.evaluate`), so distributed output matches
single-device output to fp32 tolerance by construction.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.codegen import (evaluate, firing_name, matmul, named,
                                trigger_touched_views)
from repro.core.compiler import Trigger
from repro.core.program import Program
from repro.dist.sharding import auto_axes

Array = jax.Array
Env = Dict[str, Array]


def row_spec(mesh: Mesh, axis: str, shape: Tuple[int, ...]) -> P:
    """Row-sharding spec when the leading dim divides the mesh axis,
    else replicated (skinny factors, scalars, ragged views)."""
    n_shards = mesh.shape[axis]
    if len(shape) == 2 and shape[0] >= n_shards and shape[0] % n_shards == 0:
        return P(axis, None)
    return P()


def _constrainer(mesh: Mesh, axis: str) -> Callable[[Array], Array]:
    mesh = auto_axes(mesh)

    def constrain(x: Array) -> Array:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, row_spec(mesh, axis, x.shape)))
    return constrain


def _replicate(mesh: Mesh, x: Array) -> Array:
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(auto_axes(mesh), P()))


def shard_views(views: Env, mesh: Mesh, axis: Optional[str] = None) -> Env:
    """Place a view store row-sharded on ``mesh`` (eager ``device_put``).

    The engine calls this once at initialize time so steady-state trigger
    firings start from device-resident shards instead of resharding per
    call.
    """
    mesh = auto_axes(mesh)
    axis = axis or mesh.axis_names[0]
    out = {}
    for name, x in views.items():
        x = jnp.asarray(x)
        out[name] = jax.device_put(
            x, NamedSharding(mesh, row_spec(mesh, axis, x.shape)))
    return out


def build_distributed_trigger(trigger: Trigger, program: Program, mesh: Mesh,
                              *, jit: bool = True,
                              axis: Optional[str] = None
                              ) -> Callable[[Env, Array, Array], Env]:
    """Stage a compiled trigger for row-sharded execution on ``mesh``.

    Returns ``fn(views, U, V) -> views`` with the same contract as
    :func:`repro.core.codegen.build_trigger_fn`: ``views`` must contain
    every view the trigger touches; the returned dict carries the updated
    values (untouched views pass through).  ``axis`` defaults to the
    mesh's first axis name.

    With ``jit=False`` the returned function is a pure trace-able body
    (no internal jit) so callers can ``jax.jit(fn).lower(...)`` it to
    inspect the collective schedule.
    """
    axis = axis or mesh.axis_names[0]
    binding = dict(program.dims)
    written, read_only = trigger_touched_views(trigger)
    constrain = _constrainer(mesh, axis)

    def core(written_vals: Tuple[Array, ...], read_vals: Tuple[Array, ...],
             u: Array, v: Array) -> Tuple[Array, ...]:
        env: Env = {}
        for name, val in zip(written + read_only,
                             tuple(written_vals) + tuple(read_vals)):
            env[name] = constrain(val)
        # update factors are skinny: replicate them to every shard
        env[trigger.u_var.name] = _replicate(mesh, u)
        env[trigger.v_var.name] = _replicate(mesh, v)
        cache: Dict[int, Array] = {}
        for a in trigger.assigns:
            env[a.name] = evaluate(a.expr, env, binding, cache)
        for up in trigger.updates:
            if up.kind == "lowrank":
                new = env[up.view] + matmul(env[up.u], env[up.v].T)
            else:
                new = env[up.view] + env[up.d]
            env[up.view] = constrain(new)
        return tuple(env[name] for name in written)

    if jit:
        core = jax.jit(named(core, firing_name(trigger, "sharded")))

    def run(views: Env, u: Array, v: Array) -> Env:
        new_vals = core(tuple(views[n] for n in written),
                        tuple(views[n] for n in read_only),
                        jnp.asarray(u), jnp.asarray(v))
        out = dict(views)
        out.update(zip(written, new_vals))
        return out

    return run


def build_distributed_planned_trigger(trigger: Trigger, program: Program,
                                      mesh: Mesh, *, reeval_views=(),
                                      lazy_views=(), jit: bool = True,
                                      axis: Optional[str] = None
                                      ) -> Callable[[Env, Array, Array], Env]:
    """The planned firing (per-view incremental/reeval/lazy partition,
    see :func:`repro.core.codegen.build_planned_trigger_fn`) staged for
    row-sharded execution on ``mesh``.

    The plan partition changes *what* is computed, not *where*: factor
    blocks and rank-k sweeps stay row-local, and an in-firing
    re-evaluation of a view is the same row-sharded matmul chain the
    re-evaluation baseline runs — GSPMD inserts the collectives either
    way, so distributed planned output matches the single-device
    planned output to fp32 tolerance by construction.  Plans carry the
    mesh key (``repro.plan.trigger_cache.mesh_cache_key``) so engines
    on identical meshes share these compiled firings through the
    trigger cache instead of re-jitting per instance.
    """
    from repro.core.codegen import build_planned_trigger_fn
    axis = axis or mesh.axis_names[0]
    return build_planned_trigger_fn(
        trigger, program, dict(program.dims),
        reeval_views=reeval_views, lazy_views=lazy_views, jit=jit,
        apply_backend="xla", donate=False,
        constrain=_constrainer(mesh, axis),
        replicate=lambda x: _replicate(mesh, x))


def distributed_reeval_matmul(mesh: Mesh, *, jit: bool = True,
                              axis: Optional[str] = None
                              ) -> Callable[[Array, Array], Array]:
    """The re-evaluation baseline on the same layout: ``A @ B`` with both
    operands row-sharded.

    GSPMD must all-gather the right operand (O(n·m) wire bytes) before
    the local matmuls — exactly the re-evaluation data movement the paper
    charges against REEVAL in §6.  Output stays row-sharded, matching the
    view store layout.
    """
    axis = axis or mesh.axis_names[0]
    constrain = _constrainer(mesh, axis)

    def fn(a: Array, b: Array) -> Array:
        return constrain(matmul(constrain(a), constrain(b)))

    return jax.jit(fn) if jit else fn

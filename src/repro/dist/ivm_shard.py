"""Row-sharded IVM execution (paper §6, Data Partitioning / Fig. 3f).

The paper's parallelization claim, executed: a compiled trigger is a
straight-line chain of (big × skinny) matmuls followed by rank-k view
sweeps, so placing every maintained n×m view **row-sharded** across the
mesh makes each firing embarrassingly parallel —

  * factor blocks like ``A·u`` read only local rows of ``A``, and the
    next level all-gathers the *skinny* (n × k) result, O(n·k) on the
    wire;
  * transposed reads (``Aᵀ·q``) are a local product and an all-reduce
    of an n × k result;
  * the ``M += U Vᵀ`` sweeps are purely local row updates.

Re-evaluation on the same layout moves whole matrices: one n×n matmul
between two row-sharded operands all-gathers O(n²) bytes.  That gap is
the paper's Fig. 3f finding (INCR is far less sensitive to cluster size
than REEVAL), visible in the compiled collective schedules of the two
functions below (:func:`collective_bytes` counts a compiled
program's collective operand bytes).

Placement is declared with ``with_sharding_constraint`` inside the staged
computation (views by rows, the sweeps' right factors replicated: see
:func:`right_factors`), and GSPMD inserts the collectives of the factor
blocks and of the plain sweeps, which :func:`repro.core.codegen.evaluate`
stages as on one device.  The views that
:func:`repro.core.codegen.fused_view_passes` picks for the local row
block instead run :func:`repro.kernels.ops.apply_project` once per block
under ``jax.shard_map``, as the one-device firing runs it on the whole
view: one read of each block gives the new view and the products
``P·X`` (in rows) and ``Pᵀ·Y``, whose per-block partial sums the body
all-reduces with an explicit ``psum``.  Either way distributed output
matches single-device output to fp32 tolerance.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.codegen import (_run_fused_pass, build_evaluator, evaluate,
                                firing_name, fused_view_passes, matmul,
                                named, trigger_touched_views)
from repro.core.compiler import Trigger
from repro.core.program import Program
from repro.dist.sharding import auto_axes
from repro.roofline.analysis import parse_collectives

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


def _replicator(mesh: Mesh) -> Callable[[Array], Array]:
    sharding = NamedSharding(auto_axes(mesh), P())

    def replicate(x: Array) -> Array:
        return jax.lax.with_sharding_constraint(x, sharding)
    return replicate


def right_factors(trigger: Trigger) -> frozenset:
    """The right factors ``V`` of the trigger's sweeps ``M += U Vᵀ``.

    The distributed firings pin them replicated: every row block's sweep
    needs the whole of ``V``, and ``Mᵀ·V`` on a replicated ``V`` is a
    local product and one all-reduce of its n×k result.  Left to the
    compiler's propagation they were split by rows and gathered again
    for each sweep, and on the CPU backend A's shards were transposed
    through an all-to-all at the first level: a whole view's shard over
    the wire every firing."""
    return frozenset(up.v for up in trigger.updates if up.kind == "lowrank")


def shard_views(views: Env, mesh: Mesh, axis: Optional[str] = None) -> Env:
    """Place a view store row-sharded on ``mesh`` (eager ``device_put``).

    A mesh engine's ``initialize`` calls this on its inputs, before it
    evaluates anything: an input held whole on the host or on one device
    goes to its row blocks, one already placed so stays where it is.
    Every view is then computed on its shards by
    :func:`build_distributed_evaluator`, so no chip holds a whole view,
    and steady-state firings start from device-resident shards instead
    of resharding per call.
    """
    mesh = auto_axes(mesh)
    axis = axis or mesh.axis_names[0]
    out = {}
    for name, x in views.items():
        if not isinstance(x, jax.Array):
            x = np.asarray(x)   # from the host: each chip gets its rows only
        out[name] = jax.device_put(
            x, NamedSharding(mesh, row_spec(mesh, axis, x.shape)))
    return out


def build_distributed_evaluator(program: Program, mesh: Mesh, *,
                                jit: bool = True,
                                axis: Optional[str] = None
                                ) -> Callable[[Env], Env]:
    """The program's full re-evaluation staged for row-sharded views:
    :func:`repro.core.codegen.build_evaluator` with every input and
    every statement's value pinned to its :func:`row_spec`.  A product
    of two row-sharded views gathers its right operand (one view, once)
    and writes its own row block, so the outputs come back row-sharded
    whatever the compiler's propagation would have chosen."""
    axis = axis or mesh.axis_names[0]
    return build_evaluator(program, dict(program.dims), jit=jit,
                           constrain=_constrainer(mesh, axis))


def collective_bytes(hlo_text: str) -> int:
    """Operand bytes of every collective in a compiled (per-device)
    program's text, as :func:`repro.roofline.analysis.parse_collectives`
    reads them, each collective once however many computations print
    it."""
    seen, total = set(), 0
    for op in parse_collectives(hlo_text).ops:
        if op.channel_id is not None:
            if op.channel_id in seen:
                continue
            seen.add(op.channel_id)
        total += op.operand_bytes
    return total


def _row_block_apply_project(mesh: Mesh, axis: str) -> Callable:
    """``project(p, u, v, x, y) -> (p + u vᵀ, p·x, pᵀ·y)`` for a view
    ``p`` in row blocks over ``axis``: each device runs
    :func:`repro.kernels.ops.apply_project` on its block, with the rows
    of ``u`` and ``y`` that match it and the whole of ``v`` and ``x``,
    and all-reduces its partial ``pᵀ·y``.  The new view and ``p·x`` come
    back in row blocks, ``pᵀ·y`` replicated; ``x`` or ``y`` may be
    ``None``, and so is its product then."""
    from repro.kernels import ops as rk_ops
    mesh = auto_axes(mesh)
    rows, whole = P(axis, None), P()

    def body(p, u, v, x, y):
        new, px, pty = rk_ops.apply_project(p, u, v, x, y)
        return new, px, None if pty is None else jax.lax.psum(pty, axis)

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(rows, rows, whole, whole, rows),
                         out_specs=(rows, rows, whole), check_vma=False)


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

    The views :func:`repro.core.codegen.fused_view_passes` picks for a
    device's row block are applied and projected in one pass per block
    (:func:`_row_block_apply_project`), at the same point of the trigger
    as the one-device firing runs them; ``run.fused_views`` names them.

    With ``jit=False`` the returned function is a pure trace-able body
    (no internal jit) so callers can ``jax.jit(fn).lower(...)`` it to
    inspect the collective schedule.
    """
    axis = axis or mesh.axis_names[0]
    binding = dict(program.dims)
    written, read_only = trigger_touched_views(trigger)
    constrain = _constrainer(mesh, axis)
    replicate = _replicator(mesh)
    right = right_factors(trigger)
    passes = fused_view_passes(trigger, binding, mesh.shape[axis])
    project = _row_block_apply_project(mesh, axis) if passes else None

    def core(written_vals: Tuple[Array, ...], read_vals: Tuple[Array, ...],
             u: Array, v: Array) -> Tuple[Array, ...]:
        env: Env = {}
        for name, val in zip(written + read_only,
                             tuple(written_vals) + tuple(read_vals)):
            env[name] = constrain(val)
        # update factors are skinny: replicate them to every shard
        env[trigger.u_var.name] = replicate(u)
        env[trigger.v_var.name] = replicate(v)
        cache: Dict[int, Array] = {}
        applied: Env = {}
        for i, a in enumerate(trigger.assigns):
            for fp in passes:
                if fp.at == i:
                    _run_fused_pass(fp, env, binding, cache, applied,
                                    False, project=project)
            val = evaluate(a.expr, env, binding, cache)
            env[a.name] = replicate(val) if a.name in right else val
        for up in trigger.updates:
            if up.view in applied:
                new = applied[up.view]
            elif up.kind == "lowrank":
                new = env[up.view] + matmul(env[up.u], env[up.v].T)
            else:
                new = env[up.view] + env[up.d]
            env[up.view] = constrain(new)
        return tuple(env[name] for name in written)

    staged = jax.jit(named(core, firing_name(trigger, "sharded")))

    def run(views: Env, u: Array, v: Array) -> Env:
        args = (tuple(views[n] for n in written),
                tuple(views[n] for n in read_only), u, v)
        if not jit:
            new_vals = core(*args[:2], jnp.asarray(u), jnp.asarray(v))
        else:
            if run.executable is None:
                # compiled ahead of the first call, so that the program
                # the firings run is the one whose collectives are counted
                run.executable = staged.lower(*args).compile()
                run.collective_bytes = collective_bytes(
                    run.executable.as_text())
            new_vals = run.executable(*args)
        out = dict(views)
        out.update(zip(written, new_vals))
        return out

    run.executable = None
    run.collective_bytes = 0
    run.fused_views = tuple(fp.view for fp in passes)
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
        constrain=_constrainer(mesh, axis), replicate=_replicator(mesh))


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

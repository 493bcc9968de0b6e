"""Codegen: symbolic expressions / triggers → jitted JAX callables.

The evaluator stages a trigger body into a single XLA program: every factor
block is a chain of (big × skinny) or (skinny × skinny) matmuls, and the
``+=`` updates donate the view buffers so the update happens in place.

Backends for the rank-k apply (``M += U Vᵀ``) are pluggable
(:class:`Applier`):
  - "xla": plain jnp (default everywhere),
  - "pallas": the VMEM-tiled TPU kernel from ``repro.kernels.rank_update``
    (interpret-mode on CPU; the kernel is the TPU hot path).
On a TPU, a written view whose factor products the trigger takes at a
small rank is instead applied and projected in one pass
(:func:`fused_view_passes`), whichever the backend.
"""

from __future__ import annotations

import functools
import re
import warnings
from collections import Counter
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import expr as ex
from .compiler import Assign, CompiledProgram, Trigger, ViewUpdate
from .expr import Expr
from .factored import ColSlice, HStack
from .program import Program


Array = jax.Array
Env = Dict[str, Array]

# Views are float32 and must agree with re-evaluation to f32 accuracy.
# The TPU's default precision for an f32 dot rounds its operands to
# bfloat16, so every matmul on the engine's path asks for full f32.
PRECISION = jax.lax.Precision.HIGHEST


def named(fn: Callable, name: str) -> Callable:
    """Give ``fn`` a stable name before ``jax.jit``: its HLO module is
    then ``jit_<name>``, which is what a profiler trace's ``XLA Modules``
    line shows for each execution."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def firing_name(trigger: Trigger, path: str = "") -> str:
    """``fire_[<path>_]<input>_r<rank>``: the jit name of one firing
    program (``path`` names the variant: planned, rowlocal, guarded,
    sharded; the dense firing has none)."""
    head = f"fire_{path}_" if path else "fire_"
    return head + re.sub(r"\W", "_", trigger.input_name) + f"_r{trigger.rank}"


def matmul(a: Array, b: Array) -> Array:
    """``a @ b`` in full float32 on every backend."""
    return jnp.matmul(a, b, precision=PRECISION,
                      preferred_element_type=jnp.float32)


def _dim(d, binding: Dict[str, int]) -> int:
    return binding[d.name] if isinstance(d, ex.Dim) else int(d)


def evaluate(e: Expr, env: Env, binding: Dict[str, int],
             cache: Optional[Dict[int, Array]] = None) -> Array:
    """Evaluate a symbolic expression against concrete arrays.

    ``cache`` keyed by interned node id gives cross-expression CSE: blocks
    of the same trigger share subcomputations for free.
    """
    if cache is None:
        cache = {}

    def go(x: Expr) -> Array:
        hit = cache.get(id(x))
        if hit is not None:
            return hit
        out = _eval_node(x, env, binding, go)
        cache[id(x)] = out
        return out

    return go(e)


def _eval_node(x: Expr, env: Env, binding, go) -> Array:
    if isinstance(x, ex.Var):
        try:
            return env[x.name]
        except KeyError:
            raise KeyError(f"unbound variable {x.name}; have {sorted(env)}")
    if isinstance(x, ex.Zero):
        return jnp.zeros((_dim(x.shape[0], binding), _dim(x.shape[1], binding)),
                         dtype=jnp.float32)
    if isinstance(x, ex.Identity):
        return jnp.eye(_dim(x.shape[0], binding), dtype=jnp.float32)
    if isinstance(x, ex.Const):
        return jnp.full((1, 1), x.value, dtype=jnp.float32)
    if isinstance(x, ex.MatMul):
        return matmul(go(x.lhs), go(x.rhs))
    if isinstance(x, ex.Add):
        terms = [go(t) for t in x.terms]
        return functools.reduce(jnp.add, terms)
    if isinstance(x, ex.Scale):
        f = go(x.factor)
        if f.ndim == 2:  # (1,1) scalar view
            f = f[0, 0]
        return f * go(x.operand)
    if isinstance(x, ex.Transpose):
        return go(x.operand).T
    if isinstance(x, ex.Inverse):
        a = go(x.operand)
        if a.shape == (1, 1):
            return 1.0 / a
        return jnp.linalg.inv(a)
    if isinstance(x, HStack):
        return jnp.concatenate([go(b) for b in x.blocks], axis=1)
    if isinstance(x, ColSlice):
        return go(x.operand)[:, x.col:x.col + 1]
    raise TypeError(f"cannot evaluate {type(x).__name__}")


# ---------------------------------------------------------------------------
# program re-evaluation (the paper's baseline strategy)
# ---------------------------------------------------------------------------


def build_evaluator(program: Program,
                    binding: Optional[Dict[str, int]] = None,
                    jit: bool = True,
                    constrain: Optional[Callable] = None
                    ) -> Callable[[Env], Env]:
    """Full re-evaluation: returns {view name: value} for all statements.

    ``constrain`` is the sharding hook of the distributed path
    (:mod:`repro.dist.ivm_shard`): it pins every input and every
    statement's value where it is produced; without it the program is
    staged as it always was."""
    binding = dict(program.dims if binding is None else binding)

    def run(inputs: Env) -> Env:
        env: Env = dict(inputs)
        if constrain is not None:
            env = {k: constrain(v) for k, v in env.items()}
        cache: Dict[int, Array] = {}
        out: Env = {}
        for st in program.statements:
            val = evaluate(st.expr, env, binding, cache)
            if constrain is not None:
                val = constrain(val)
            env[st.target.name] = val
            out[st.target.name] = val
        return out

    return jax.jit(named(run, "evaluate")) if jit else run


# ---------------------------------------------------------------------------
# trigger execution (the incremental strategy)
# ---------------------------------------------------------------------------


def _apply_lowrank_xla(view: Array, u: Array, v: Array) -> Array:
    return view + matmul(u, v.T)


class Applier:
    """The rank-k apply ``view + u vᵀ`` of one staged trigger.

    ``"xla"`` is plain jnp; ``"pallas"`` is the one-pass
    ``rank_update_batched`` kernel, which subsumes the single-update
    case (a 2-D ``(n, k)`` pair is the T=1 stack).  A Pallas apply whose
    shapes no kernel blocking fits takes the XLA reference instead;
    ``fallbacks`` maps each such view to the reason, filled as the
    trigger is traced, so the engine can count every firing that ran it.
    """

    def __init__(self, backend: str):
        if backend not in ("xla", "pallas"):
            raise ValueError(f"unknown apply backend {backend!r}")
        self.pallas = backend == "pallas"
        self.fallbacks: Dict[str, str] = {}

    def __call__(self, name: str, view: Array, u: Array, v: Array) -> Array:
        if not self.pallas:
            return _apply_lowrank_xla(view, u, v)
        from repro.kernels import ops as rk_ops
        return rk_ops.rank_update_batched(
            view, u, v,
            on_fallback=lambda why: self.fallbacks.__setitem__(name, why))


# Delta ranks at which a view's apply and its factor products share one
# pass.  On a v5e the pass beat XLA's staging of the same three results
# at every rank from 1 (where XLA also reads the view once) to 8; at 16
# its float32 VPU work outlasts XLA's three passes, and wider deltas
# belong on the MXU (PERF.md §6).
FUSED_PASS_RANKS = (1, 8)


def on_tpu() -> bool:
    """Whether the default backend is a TPU, the only place the
    apply-and-project pass is staged (interpreted elsewhere, it is a
    test tool, not a path)."""
    return jax.default_backend() == "tpu"


@dataclass(frozen=True)
class FusedPass:
    """A written view applied and projected in one pass: ``view += u vᵀ``,
    and ``px = view·X``, ``pty = viewᵀ·Y`` (``MatMul`` nodes of the
    assigns, or ``None``) taken on the old view before assign ``at``,
    the first that reads one of them."""

    view: str
    u: str
    v: str
    at: int
    px: Optional[Expr]
    pty: Optional[Expr]


def _product_of(x: Expr) -> Optional[Tuple[str, str]]:
    """``(view, "x")`` for ``MatMul(view, ·)`` and ``(view, "y")`` for
    ``MatMul(viewᵀ, ·)``, where ``view`` is a variable."""
    if not isinstance(x, ex.MatMul):
        return None
    lhs = x.lhs
    if isinstance(lhs, ex.Var):
        return lhs.name, "x"
    if isinstance(lhs, ex.Transpose) and isinstance(lhs.operand, ex.Var):
        return lhs.operand.name, "y"
    return None


def fused_view_passes(trigger: Trigger, binding: Dict[str, int]
                      ) -> Tuple[FusedPass, ...]:
    """The written views of ``trigger`` to apply through
    :func:`repro.kernels.ops.apply_project`, in trigger order.

    On a TPU, a view qualifies when it is the target of one low-rank
    ``+=`` and the big operand of one ``view·X`` and/or one ``viewᵀ·Y``
    in the assigns, where the update's factors and ``X``, ``Y`` are
    skinny values that do not read the view and exist before the first
    assign that reads a product; when the update's rank and the
    products' widths lie in :data:`FUSED_PASS_RANKS`; and when a
    blocking of the view fits VMEM.  In matrix powers, ``P_j``'s
    products are taken against its own ``dU_j``/``dV_j``."""
    if not on_tpu():
        return ()
    from repro.kernels.ops import apply_project_blocks
    # every product node, once, with the first assign that reads it
    products: Dict[str, Dict[str, List[Tuple[int, Expr]]]] = {}
    seen = set()
    for idx, a in enumerate(trigger.assigns):
        stack = [a.expr]
        while stack:
            x = stack.pop()
            if id(x) in seen:
                continue
            seen.add(id(x))
            hit = _product_of(x)
            if hit is not None:
                products.setdefault(hit[0], {"x": [], "y": []})[
                    hit[1]].append((idx, x))
            stack.extend(x.children)
    order = {a.name: i for i, a in enumerate(trigger.assigns)}
    shapes = {a.name: a.expr.shape for a in trigger.assigns}
    for var in (trigger.u_var, trigger.v_var):
        shapes[var.name] = var.shape
    targets = Counter(up.view for up in trigger.updates)
    lo, hi = FUSED_PASS_RANKS
    out = []
    for up in trigger.updates:
        found = products.get(up.view)
        if (up.kind != "lowrank" or targets[up.view] > 1 or found is None
                or len(found["x"]) > 1 or len(found["y"]) > 1):
            continue
        at = min(idx for side in found.values() for idx, _ in side)
        px, pty = (found[s][0][1] if found[s] else None for s in "xy")
        operands = [e.rhs for e in (px, pty) if e is not None]
        needed = {up.u, up.v}.union(*(e.free_vars() for e in operands))
        if any(order.get(name, -1) >= at for name in needed):
            continue
        if any(_expr_refs(e, {up.view}) for e in operands):
            continue
        widths = [_dim(shapes[up.u][1], binding)]
        widths += [_dim(e.shape[1], binding) for e in operands]
        if not all(lo <= w <= hi for w in widths):
            continue
        big = (px.lhs if px is not None else pty.lhs.operand).shape
        kx = _dim(px.rhs.shape[1], binding) if px is not None else 0
        ky = _dim(pty.rhs.shape[1], binding) if pty is not None else 0
        if apply_project_blocks(_dim(big[0], binding), _dim(big[1], binding),
                                widths[0], kx, ky) is None:
            continue
        out.append(FusedPass(up.view, up.u, up.v, at, px, pty))
    return tuple(out)


@functools.lru_cache(maxsize=256)
def _finite_check_jit(names: Tuple[str, ...]) -> Callable:
    def check(views: Env) -> Array:
        return jnp.stack([jnp.isfinite(views[n]).all() for n in names])
    return jax.jit(check)


def build_finite_check(names) -> Callable:
    """Jitted fused finiteness probe over the views in ``names``.

    Returns ``fn(views) -> bool[len(names)]`` (True = all-finite), one
    fused XLA program and one device sync for the whole set — the
    post-firing output validation (:func:`repro.guard.txn.check_finite`)
    runs this on every guarded firing, so it must not retrace or probe
    view-by-view.  Cached on the name tuple; views may hold extra keys.
    """
    return _finite_check_jit(tuple(names))


def trigger_touched_views(trigger: Trigger) -> Tuple[Tuple[str, ...],
                                                     Tuple[str, ...]]:
    """(written, read-only) view names a trigger actually touches.

    ``written`` are the ``+=`` targets; ``read-only`` are views referenced
    by the factor-block assigns but never updated.  Everything else in the
    store is invisible to the trigger and must not cross the jit boundary.
    """
    local = {trigger.u_var.name, trigger.v_var.name}
    local.update(a.name for a in trigger.assigns)
    written = tuple(dict.fromkeys(up.view for up in trigger.updates))
    read = set()
    for a in trigger.assigns:
        read |= set(a.expr.free_vars())
    read -= local
    read -= set(written)
    return written, tuple(sorted(read))


_donation_warned = False


def _warn_donation_ignored() -> None:
    """One-time capability warning: ``donate=True`` on a backend that
    silently ignores donation (CPU) still pays a full copy of every
    written view per firing.  Roofline comparisons of the dense vs
    row-slab sweeps are misread without this — the "in-place" dense
    sweep is really write-allocate + copy there, flattering the slab
    path by exactly one ``n·m`` write.  Fires once per process."""
    global _donation_warned
    if _donation_warned:
        return
    if jax.default_backend() == "cpu":
        _donation_warned = True
        warnings.warn(
            "buffer donation requested but the CPU backend silently "
            "ignores it: written views are copied, not updated in place. "
            "Interpret sweep rooflines (dense vs row-slab) accordingly; "
            "donation is honored on TPU/GPU.",
            RuntimeWarning, stacklevel=3)


def build_trigger_fn(trigger: Trigger, program: Program,
                     binding: Optional[Dict[str, int]] = None,
                     jit: bool = True,
                     apply_backend: str = "xla",
                     donate: bool = False) -> Callable[[Env, Array, Array], Env]:
    """Stage a trigger into ``(views, U, V) -> views``.

    ``views`` must contain the input matrices and every maintained view;
    the dict is updated **in place** with the new values and returned.
    Only the views the trigger touches cross the jit boundary — the
    untouched rest of the store is never copied, traced, or dispatched
    (the old implementation round-tripped the whole dict through XLA on
    every firing).  With ``donate=True`` the written views' buffers are
    donated, so the update is genuinely in-place on device; read-only
    views are never donated (callers may hold references).

    The views :func:`fused_view_passes` picks are applied and projected
    in one pass each, run before the first assign that reads one of
    their products; the products enter the CSE cache, so the assigns
    take them as they stand.  ``run.fused_views`` names those views.
    """
    binding = dict(program.dims if binding is None else binding)
    apply = Applier(apply_backend)
    written, read_only = trigger_touched_views(trigger)
    if donate:
        _warn_donation_ignored()
    passes = fused_view_passes(trigger, binding)

    def core(written_vals: Tuple[Array, ...], read_vals: Tuple[Array, ...],
             u: Array, v: Array) -> Tuple[Array, ...]:
        env: Env = dict(zip(written, written_vals))
        env.update(zip(read_only, read_vals))
        env[trigger.u_var.name] = u
        env[trigger.v_var.name] = v
        cache: Dict[int, Array] = {}
        applied: Env = {}
        for i, a in enumerate(trigger.assigns):
            for fp in passes:
                if fp.at == i:
                    _run_fused_pass(fp, env, binding, cache, applied, donate)
            env[a.name] = evaluate(a.expr, env, binding, cache)
        for up in trigger.updates:
            if up.view in applied:
                env[up.view] = applied[up.view]
            elif up.kind == "lowrank":
                env[up.view] = apply(up.view, env[up.view], env[up.u],
                                     env[up.v])
            else:
                env[up.view] = env[up.view] + env[up.d]
        return tuple(env[name] for name in written)

    if jit:
        core = jax.jit(named(core, firing_name(trigger)),
                       donate_argnums=(0,) if donate else ())

    def run(views: Env, u: Array, v: Array) -> Env:
        new_vals = core(tuple(views[n] for n in written),
                        tuple(views[n] for n in read_only), u, v)
        views.update(zip(written, new_vals))
        return views

    run.fallbacks = apply.fallbacks
    run.fused_views = tuple(fp.view for fp in passes)
    return run


def _run_fused_pass(fp: FusedPass, env: Env, binding: Dict[str, int],
                    cache: Dict[int, Array], applied: Env,
                    donate: bool) -> None:
    """One apply-and-project pass: the new view goes to ``applied`` (the
    assigns still read the old one), the products to the CSE cache."""
    from repro.kernels import ops as rk_ops
    x, y = (None if e is None else evaluate(e.rhs, env, binding, cache)
            for e in (fp.px, fp.pty))
    applied[fp.view], px, pty = rk_ops.apply_project(
        env[fp.view], env[fp.u], env[fp.v], x, y, alias=donate)
    for node, val in ((fp.px, px), (fp.pty, pty)):
        if node is not None:
            cache[id(node)] = val


# ---------------------------------------------------------------------------
# row-slab trigger execution (row-local carriers, §3–§5 containment)
# ---------------------------------------------------------------------------


def _expr_refs(e: Expr, names) -> bool:
    """Whether ``e`` references any :class:`~repro.core.expr.Var` in
    ``names`` (iterative — factor chains can be deep)."""
    stack = [e]
    while stack:
        x = stack.pop()
        if isinstance(x, ex.Var) and x.name in names:
            return True
        stack.extend(x.children)
    return False


def _compact_left_safe(e: Expr, left) -> bool:
    """Whether a left factor-block expression can be evaluated with the
    update's **compact** ``(r, k)`` row block bound in place of the dense
    ``(n, k)`` scattered factor.

    This is :func:`~repro.core.delta.row_support_preserved` sharpened
    into an execution contract: every constructor that preserves row
    support also *commutes with the row gather* — ``(α·L)[rows] =
    α·L[rows]``, ``(L @ B)[rows] = L[rows] @ B``, and ``Add`` /
    ``HStack`` / ``ColSlice`` act per-row or per-column — provided no
    compact-shaped value ever reaches a dense position (a ``MatMul``
    right operand, a ``Scale`` factor).  ``Zero`` is excluded: its
    staged shape comes from the binding's dense dims.  A ``False`` here
    only costs the dense-chain rebuild the trigger always supported.
    """
    if isinstance(e, ex.Var):
        return e.name in left
    if isinstance(e, ex.Scale):
        return (not _expr_refs(e.factor, left)
                and _compact_left_safe(e.operand, left))
    if isinstance(e, ex.MatMul):
        return (_compact_left_safe(e.lhs, left)
                and not _expr_refs(e.rhs, left))
    if isinstance(e, ex.Add):
        return all(_compact_left_safe(t, left) for t in e.terms)
    if isinstance(e, HStack):
        return all(_compact_left_safe(b, left) for b in e.blocks)
    if isinstance(e, ColSlice):
        return _compact_left_safe(e.operand, left)
    return False


def compact_chain_names(trigger: Trigger):
    """The trigger's left-factor vars that stay compact end to end, or
    ``None`` if this trigger cannot run its factor chain compactly.

    A trigger qualifies when every maintained view is a row-local
    low-rank update and every assign that (transitively) consumes the
    update's left factor is :func:`_compact_left_safe` — then the whole
    chain can be evaluated on the ``(r, k)`` row block and no dense
    ``(n, k)`` factor is ever materialized."""
    if any(up.kind != "lowrank" for up in trigger.updates):
        return None
    if any(trigger.carriers.get(up.view) != "row_local"
           for up in trigger.updates):
        return None
    left = {trigger.u_var.name}
    for a in trigger.assigns:
        if not _expr_refs(a.expr, left):
            continue
        if not _compact_left_safe(a.expr, left):
            return None
        left.add(a.name)
    for up in trigger.updates:
        if up.u not in left or up.v in left:
            return None
    return left


def build_rowlocal_trigger_fn(trigger: Trigger, program: Program,
                              binding: Optional[Dict[str, int]] = None,
                              row_bucket: int = 8,
                              jit: bool = True,
                              apply_backend: str = "xla",
                              donate: bool = False
                              ) -> Callable[[Env, Array, Array, Array], Env]:
    """Stage a trigger for row-local carriers: ``(views, rows, B, V) -> views``.

    ``rows`` is the affected-row index vector padded to the static
    ``row_bucket`` with the **out-of-bounds sentinel** ``n`` (``B``
    padded with zero rows).  JAX's scatter drops out-of-bounds indices
    and its gather clamps them, so the padding is exact end-to-end: the
    scattered dense-shaped ``u`` never sees the sentinel rows, and the
    clamped garbage a factor gather picks up is scattered right back
    out of bounds.

    Execution has two regimes.  When the whole trigger is row-local
    and every left factor-block expression is compact-safe
    (:func:`compact_chain_names`), the factor chain runs **compactly**:
    the ``(row_bucket, k)`` block is bound directly as the update's
    left factor, every downstream left factor stays ``(row_bucket, k)``
    (row-preserving constructors commute with the row gather), and each
    view updates by ``view.at[rows].add(L_compact @ Rᵀ)`` — no dense
    ``(n, k)`` factor is ever materialized, so the firing's traffic is
    the written views plus ``O(r·(k + m))``.  Otherwise the dense-shaped
    ``u`` is rebuilt by scatter, the chain is evaluated exactly as the
    dense trigger would, row-local views take the row-slab gather-GER-
    scatter (``view.at[rows].add(L[rows] @ Rᵀ)``) and widened views the
    ordinary dense sweep.  With ``apply_backend="pallas"`` the row-slab
    update of closed views goes through the touched-slab Pallas kernel
    (:func:`repro.kernels.rank_update_rows_pallas`) whenever the
    concrete rows admit a slab plan (the kernel consumes the
    dense-shaped factor, so the slab-plan path keeps the dense chain).

    Bit-exactness caveat: ``at[].add`` sums ``L[rows] @ Rᵀ`` into the
    view rather than forming ``view + u vᵀ``, so float rounding can
    differ from the dense path by ~1 ulp; the property suite pins the
    agreement tolerance.
    """
    binding = dict(program.dims if binding is None else binding)
    written, read_only = trigger_touched_views(trigger)
    if donate:
        _warn_donation_ignored()
    x = program.inputs[trigger.input_name]
    n_in = _dim(x.shape[0], binding)
    k = trigger.rank
    use_pallas = Applier(apply_backend).pallas  # validates the name too
    compact_names = compact_chain_names(trigger)

    def _no_kernel(apply: Applier, name: str, why: str) -> None:
        if use_pallas:
            apply.fallbacks[name] = why

    def _compact_core(apply: Applier):
        # fully row-local trigger: the factor chain runs on the compact
        # (row_bucket, k) block — sentinel-padded rows carry zero block
        # rows through every preserving constructor and their scatter
        # contributions are dropped as out-of-bounds, so no dense (n, k)
        # factor exists anywhere in the program
        def core(written_vals, read_vals, rows, block, v, slab_ids):
            env: Env = dict(zip(written, written_vals))
            env.update(zip(read_only, read_vals))
            env[trigger.u_var.name] = block
            env[trigger.v_var.name] = v
            cache: Dict[int, Array] = {}
            for a in trigger.assigns:
                env[a.name] = evaluate(a.expr, env, binding, cache)
            for up in trigger.updates:
                L, R = env[up.u], env[up.v]
                _no_kernel(apply, up.view, "compact row-slab chain: no "
                           "slab plan for these rows")
                env[up.view] = env[up.view].at[rows].add(
                    matmul(L, R.T), indices_are_sorted=True)
            return tuple(env[name] for name in written)

        if jit:
            return jax.jit(named(core, firing_name(trigger, "rowlocal")),
                           donate_argnums=(0,) if donate else ())
        return core

    def _core(slab: Optional[int], num_slabs: int, apply: Applier):
        if slab is None and compact_names is not None:
            return _compact_core(apply)
        # one staged body per slab plan shape (None = XLA scatter path)
        def core(written_vals, read_vals, rows, block, v, slab_ids):
            env: Env = dict(zip(written, written_vals))
            env.update(zip(read_only, read_vals))
            u = jnp.zeros((n_in, k), jnp.float32).at[rows].add(
                block, indices_are_sorted=True)
            env[trigger.u_var.name] = u
            env[trigger.v_var.name] = v
            cache: Dict[int, Array] = {}
            for a in trigger.assigns:
                env[a.name] = evaluate(a.expr, env, binding, cache)
            for up in trigger.updates:
                if up.kind != "lowrank":
                    env[up.view] = env[up.view] + env[up.d]
                    continue
                L, R = env[up.u], env[up.v]
                if trigger.carriers.get(up.view) != "row_local":
                    env[up.view] = apply(up.view, env[up.view], L, R)
                    continue
                view = env[up.view]
                if slab is not None and view.shape[0] % slab == 0:
                    from repro.kernels import ops as rk_ops
                    bn = rk_ops.rank_update_rows_block(slab, view.shape[1],
                                                       R.shape[1])
                    if bn is not None:
                        from repro.kernels.rank_update_rows import \
                            rank_update_rows_pallas
                        env[up.view] = rank_update_rows_pallas(
                            view, slab_ids, L, R, slab=slab, bn=bn,
                            interpret=rk_ops.interpret_mode())
                        continue
                _no_kernel(apply, up.view, "rank_update_rows: no slab "
                           f"plan or blocking for {view.shape}")
                # gather-GER-scatter: clamped OOB gather rows are
                # dropped again by the OOB scatter — exact
                env[up.view] = view.at[rows].add(
                    matmul(L[rows], R.T), indices_are_sorted=True)
            return tuple(env[name] for name in written)

        if jit:
            return jax.jit(named(core, firing_name(trigger, "rowlocal")),
                           donate_argnums=(0,) if donate else ())
        return core

    cores: Dict[Tuple[Optional[int], int], Tuple[Callable, Applier]] = {}

    def run(views: Env, rows, block, v) -> Env:
        import numpy as np
        rows = np.asarray(rows, dtype=np.int32)
        slab = None
        slab_ids = np.zeros((0,), np.int32)
        if use_pallas:
            from repro.kernels import ops as rk_ops
            plan = rk_ops.slab_plan(n_in, rows[rows < n_in])
            if plan is not None:
                slab, slab_ids = plan
        key = (slab, int(np.shape(slab_ids)[0]))
        hit = cores.get(key)
        if hit is None:
            apply = Applier(apply_backend)
            hit = cores[key] = (_core(*key, apply), apply)
        core, apply = hit
        new_vals = core(tuple(views[n] for n in written),
                        tuple(views[n] for n in read_only),
                        rows, block, v, slab_ids)
        views.update(zip(written, new_vals))
        run.fallbacks = apply.fallbacks  # this firing's slab-plan variant
        return views

    run.fallbacks = {}
    return run


# ---------------------------------------------------------------------------
# planned trigger execution (repro.plan: per-view strategy in one firing)
# ---------------------------------------------------------------------------


def planned_trigger_sets(trigger: Trigger, program: Program,
                         reeval_views=(), lazy_views=()):
    """Partition a trigger's work under a maintenance plan.

    ``reeval_views`` are re-evaluated from their defining statements
    inside the firing (the §7 fallback for views whose delta lost to
    recomputation); ``lazy_views`` are skipped entirely (unmaterialized
    intermediates, recomputed on read) — unless a re-evaluated view's
    statement reads them, in which case they are pulled into the
    recompute closure so re-evaluation stays exact.

    Returns ``(kept_assigns, kept_updates, recompute_stmts, skipped)``:
    the dead-code-eliminated factor-block assigns and ``+=`` updates
    that still run incrementally, the statements to re-evaluate in
    program order, and the lazy views this firing leaves stale.
    """
    reeval = set(reeval_views)
    lazy = set(lazy_views) - reeval
    if trigger.input_name in reeval or trigger.input_name in lazy:
        raise ValueError(
            f"input {trigger.input_name!r} is the base fact: it cannot be "
            f"re-evaluated or left unmaterialized")
    kept_updates = [up for up in trigger.updates
                    if up.view not in reeval and up.view not in lazy]
    # recompute closure, discovered right-to-left: a lazy view is
    # recomputed only if a later recomputed statement reads it
    needed: set = set()
    recompute_names: set = set()
    for st in reversed(program.statements):
        name = st.target.name
        if name in reeval or (name in lazy and name in needed):
            recompute_names.add(name)
            needed |= set(st.expr.free_vars())
    recompute = [st for st in program.statements
                 if st.target.name in recompute_names]
    skipped = tuple(sorted(lazy - recompute_names))
    # assign DCE, same direction: keep only blocks the kept updates
    # (transitively) reference
    need: set = set()
    for up in kept_updates:
        need |= {x for x in (up.u, up.v, up.d) if x}
    kept_assigns: List[Assign] = []
    for a in reversed(trigger.assigns):
        if a.name in need:
            kept_assigns.append(a)
            need |= set(a.expr.free_vars())
    kept_assigns.reverse()
    return kept_assigns, kept_updates, recompute, skipped


def build_planned_trigger_fn(trigger: Trigger, program: Program,
                             binding: Optional[Dict[str, int]] = None,
                             *, reeval_views=(), lazy_views=(),
                             jit: bool = True, apply_backend: str = "xla",
                             donate: bool = False,
                             constrain: Optional[Callable] = None,
                             replicate: Optional[Callable] = None
                             ) -> Callable[[Env, Array, Array], Env]:
    """Stage one *planned* firing: incremental updates for the winning
    views, in-firing re-evaluation for the losing ones, lazy skip for
    unmaterialized intermediates — one XLA program, same ``(views, U,
    V) -> views`` contract as :func:`build_trigger_fn`.

    Execution order keeps the firing exact: factor blocks are evaluated
    against *old* view values (the delta derivation's contract), the
    surviving ``+=`` updates land, then re-evaluated statements are
    recomputed **in program order** against the already-updated store —
    every view ends at its exact post-update value either way.

    ``constrain`` / ``replicate`` are sharding hooks for the
    distributed path (:mod:`repro.dist.ivm_shard`): ``constrain`` pins
    the views, ``replicate`` the update factors and the sweeps' right
    factors (:func:`repro.dist.ivm_shard.right_factors`); identity when
    None.
    """
    binding = dict(program.dims if binding is None else binding)
    apply = Applier(apply_backend)
    assigns, updates, recompute, skipped = planned_trigger_sets(
        trigger, program, reeval_views, lazy_views)
    written = tuple(dict.fromkeys(
        [up.view for up in updates] + [st.target.name for st in recompute]))
    local = {trigger.u_var.name, trigger.v_var.name}
    local.update(a.name for a in assigns)
    read: set = set()
    for a in assigns:
        read |= set(a.expr.free_vars())
    for st in recompute:
        read |= set(st.expr.free_vars())
    read -= local
    read -= set(written)
    read_only = tuple(sorted(read))
    cst = constrain if constrain is not None else (lambda x: x)
    rep = replicate if replicate is not None else (lambda x: x)
    right = {up.v for up in updates if up.kind == "lowrank"}

    def core(written_vals: Tuple[Array, ...], read_vals: Tuple[Array, ...],
             u: Array, v: Array) -> Tuple[Array, ...]:
        env: Env = {}
        for name, val in zip(written + read_only,
                             tuple(written_vals) + tuple(read_vals)):
            env[name] = cst(val)
        env[trigger.u_var.name] = rep(u)
        env[trigger.v_var.name] = rep(v)
        cache: Dict[int, Array] = {}
        for a in assigns:
            val = evaluate(a.expr, env, binding, cache)
            env[a.name] = rep(val) if a.name in right else val
        for up in updates:
            if up.kind == "lowrank":
                env[up.view] = cst(apply(up.view, env[up.view], env[up.u],
                                         env[up.v]))
            else:
                env[up.view] = cst(env[up.view] + env[up.d])
        # fresh cache: the assign-phase cache holds pre-update values
        rcache: Dict[int, Array] = {}
        for st in recompute:
            env[st.target.name] = cst(evaluate(st.expr, env, binding, rcache))
        return tuple(env[name] for name in written)

    if jit:
        core = jax.jit(named(core, firing_name(trigger, "planned")),
                       donate_argnums=(0,) if donate else ())

    def run(views: Env, u: Array, v: Array) -> Env:
        if not jit:  # jitted cores convert np factors on the C++ arg path
            u, v = jnp.asarray(u), jnp.asarray(v)
        new_vals = core(tuple(views[n] for n in written),
                        tuple(views[n] for n in read_only), u, v)
        views.update(zip(written, new_vals))
        return views

    run.fallbacks = apply.fallbacks
    run.reeval_views = tuple(sorted(reeval_views))
    run.recomputes = tuple(st.target.name for st in recompute)
    run.skipped = skipped
    run.incr_views = tuple(up.view for up in updates)
    return run


def trigger_flops(trigger: Trigger, program: Program,
                  binding: Optional[Dict[str, int]] = None) -> float:
    """Analytic FLOP count of one trigger firing (cost-model §3)."""
    from .cost import apply_update_cost, expr_cost, shape_of
    binding = dict(program.dims if binding is None else binding)
    total = 0.0
    seen: Dict[int, bool] = {}
    from .cost import _expr_cost_shared
    for a in trigger.assigns:
        total += _expr_cost_shared(a.expr, binding, seen).flops
    name_to_var = {**{k: v for k, v in program.inputs.items()},
                   **{s.target.name: s.target for s in program.statements}}
    for up in trigger.updates:
        base = up.view
        if base not in name_to_var and base.startswith("__d"):
            # ΔᵈV auxiliary views share the base view's shape
            base = base.split("__", 2)[-1]
        view = name_to_var[base]
        n, m = shape_of(view, binding)
        if up.kind == "lowrank":
            k = next(a.expr for a in trigger.assigns if a.name == up.u).shape[1] \
                if any(a.name == up.u for a in trigger.assigns) else trigger.rank
            k = k if isinstance(k, int) else binding[k.name]
            total += apply_update_cost((n, m), k).flops
        else:
            total += n * m
    return total

"""LINVIEW runtime: materialized-view store + incremental engine.

The engine owns the compiled program, the jitted re-evaluator, and one
jitted trigger per dynamic input.  ``apply_update`` fires a trigger;
``apply_updates`` coalesces a whole update stream into one batched trigger
firing (stacked factors, §6 batching); ``reevaluate`` is the paper's
baseline strategy for comparison/validation.

With ``mesh=`` the engine routes every trigger firing — per-update and
batched — through the row-sharded apply (:mod:`repro.dist.ivm_shard`).
``initialize`` first places each input in row blocks across the mesh,
then evaluates every view there in one program whose outputs are pinned
row-sharded, so no chip ever holds a whole view; re-evaluations
(``refresh``, folds, ``reevaluate``) run that same program.  Each firing
is the §6 distributed trigger, numerically identical to the
single-device path.

With ``plan=`` (:mod:`repro.plan`) every firing executes a cost-based
**maintenance plan**: per view, factored delta propagation while it
wins, in-firing re-evaluation past the §7 crossover, a rank/staleness
hybrid in between, and lazy (recompute-on-read) refresh for
unmaterialized intermediates.  Compiled triggers are shared across
engine instances through the plan trigger cache.  Engines with
``flush_policy="cost"`` and no explicit plan still get the per-view
re-evaluation fallback: a firing whose stacked rank puts some view past
its crossover re-evaluates that view instead of sweeping it.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from .codegen import (Applier, build_evaluator,
                      build_planned_trigger_fn, build_rowlocal_trigger_fn,
                      build_trigger_fn, evaluate, trigger_flops)
from .compiler import (CompiledProgram, Trigger, batch_bucket,
                       compile_batched_trigger, compile_delta_trigger,
                       compile_program)
from .factored import (DeltaCarrier, LowRankCarrier, RowLocalCarrier,
                       as_carrier, pad_factors_to_rank, recompress_factors,
                       row_support, stack_carriers, stack_update_arrays)
from .program import Program

Array = jax.Array


@dataclass
class EngineStats:
    """Engine counters.

    ``trigger_seconds`` only accumulates for *blocked* firings (an async
    dispatch has no meaningful wall time).
    """

    updates_applied: int = 0      # logical updates (a T-batch counts T)
    triggers_fired: int = 0       # trigger firings (a T-batch counts 1)
    trigger_seconds: float = 0.0
    batches_applied: int = 0
    recompressions: int = 0
    support_recompressions: int = 0  # of those, on the row support
    reevals: int = 0
    reeval_seconds: float = 0.0
    plan_reevals: int = 0         # views re-evaluated inside planned firings
    lazy_skips: int = 0           # unmaterialized views left stale by firings
    replans: int = 0              # adaptive plan hot-swaps
    # FLOPs behind the timed seconds above — the observed wall-clock
    # rates (trigger_seconds/sweep_flops_timed vs
    # reeval_seconds/reeval_flops_timed) are what
    # AdaptivePlanner.refit_from_stats turns into an online cost_scale.
    sweep_flops_timed: float = 0.0
    reeval_flops_timed: float = 0.0
    # deferred-cascade (depth >= 2) maintenance counters
    folds: int = 0                # window folds (all tiers folded = 1)
    fold_sweeps: int = 0          # views folded via one stacked sweep
    fold_reevals: int = 0         # views folded via re-evaluation
    fold_aborts: int = 0          # folds rolled back (guard/chaos), then redone
    reads: int = 0                # output() calls — the read-rate signal that
                                  # online depth selection divides firings by
    # sparsity-aware carrier counters (repro.core.factored.DeltaCarrier)
    noop_skips: int = 0           # no-op carriers dropped before any firing
    rowlocal_firings: int = 0     # firings that swept only touched row slabs
    widened_carriers: int = 0     # row-local carriers that fell back dense
    # apply_backend="pallas": view applies that took the XLA path because
    # no kernel blocking fits their shapes (one per view per firing)
    pallas_fallbacks: int = 0
    # firing ranks, summed over committed firings: as stacked, as fired
    # after re-compression, and as padded to the bucket of the trigger
    stacked_rank: int = 0
    fired_rank: int = 0
    padded_rank: int = 0
    # trigger fns built (trigger-cache misses).  A rollback keeps it, as
    # it keeps the cache (guard.txn.restore_snapshot)
    trigger_builds: int = 0
    # collective operand bytes of the compiled program, summed over
    # committed row-sharded firings (mesh engines)
    collective_bytes: int = 0
    # views applied and projected in one pass (codegen.fused_view_passes),
    # summed over committed firings
    fused_view_passes: int = 0


class IncrementalEngine:
    """Maintains all program views under factored updates to the inputs."""

    def __init__(self, program: Program,
                 update_ranks: Optional[Dict[str, int]] = None,
                 *, force_rep: Optional[str] = None,
                 sequential_sm: bool = False,
                 apply_backend: str = "xla",
                 jit: bool = True,
                 donate: bool = False,
                 max_batch_rank: Optional[int] = None,
                 recompress_tol: float = 1e-6,
                 rowlocal_fraction: float = 0.25,
                 flush_size: int = 16,
                 flush_age: float = 0.1,
                 flush_policy: str = "fixed",
                 mesh=None,
                 mesh_axis: Optional[str] = None,
                 plan=None,
                 trigger_cache=None,
                 guard=None,
                 chaos=None,
                 order=None,
                 fold_window: int = 8,
                 max_fold_rank: Optional[int] = 64):
        """``flush_policy`` picks how :meth:`enqueue_update` decides to
        flush: ``"fixed"`` trips on the ``flush_size``/``flush_age``
        thresholds; ``"cost"`` asks the §4/§7 cost model instead — the
        queue flushes at the first stacked rank where
        :func:`repro.core.cost.batched_strategy` stops answering
        ``"stacked"`` for some maintained view (``flush_age`` remains as
        the latency bound), and the flushed firing re-evaluates any view
        whose crossover the stacked rank did pass (the per-view
        fallback; flushing early merely *bounds* how far past the
        crossover a view can get).  ``mesh`` routes every trigger firing
        through the row-sharded distributed apply
        (``repro.dist.ivm_shard``); ``mesh_axis`` names the row axis
        (default: the mesh's first).

        ``plan`` attaches a :class:`repro.plan.MaintenancePlan` (or a
        :class:`~repro.plan.WorkloadDescriptor` to plan here, or an
        :class:`~repro.plan.AdaptivePlanner` for online re-planning);
        planned engines share compiled triggers through
        ``trigger_cache`` (default: the process-global
        :func:`repro.plan.global_trigger_cache`), so a second engine
        with an identical plan key never re-jits.

        ``guard`` attaches the :mod:`repro.guard` failure-containment
        layer (a :class:`~repro.guard.GuardConfig`, or ``True`` for the
        defaults): update validation + quarantine at every admission
        point, transactional firings (snapshot → validate outputs →
        atomic rollback), and an optional drift sentinel.  ``chaos``
        (a :class:`~repro.guard.ChaosConfig` or shared
        :class:`~repro.guard.ChaosMonkey`) injects deterministic
        faults — update poisoning and in-trigger raises — so the guard's
        recovery paths are exercised, not trusted.

        ``order`` turns on higher-order (deferred-cascade) maintenance:
        an int applies the depth to every view, a ``{view: depth}`` dict
        assigns per view.  Views with effective depth ``o >= 2`` are not
        swept per firing; their window of updates accumulates in factored
        form and is **folded** — one stacked sweep (or re-evaluation,
        whichever the §7 crossover prefers) from the window-start base —
        every ``fold_window**(o-1)`` firings or at the next read, which is
        the operational form of DBToaster's Δᵏ hierarchy in LINVIEW's
        continuous setting (the first-order coefficient views are already
        materialized; what the hierarchy buys is fold amortization).
        Depth assignments are resolved so a producer view is never
        staler than its consumers.  ``max_fold_rank`` caps the stacked
        window rank via QR/SVD re-compression.  When a maintenance
        ``plan`` carries per-view ``order`` fields (depth-priced by
        ``plan_program``), the plan's depths are authoritative.

        ``rowlocal_fraction`` is the affected-fraction crossover for
        row-local carriers (:mod:`repro.core.factored`): a
        :class:`~repro.core.factored.RowLocalCarrier` touching at most
        this fraction of its input's rows fires the row-slab trigger
        variant (sweeps only the touched rows of every view the
        compiler proved row-local); above it the carrier widens to the
        dense factored path, which stays the bit-exact oracle.
        """
        if flush_policy not in ("fixed", "cost"):
            raise ValueError(f"unknown flush_policy {flush_policy!r}")
        if isinstance(order, dict):
            requested_orders = {k: int(v) for k, v in order.items()}
            compile_order = max([1, *requested_orders.values()])
        elif order is not None:
            compile_order = max(1, int(order))
            requested_orders = None  # all views, filled after compile
        else:
            compile_order, requested_orders = 1, {}
        self.compiled: CompiledProgram = compile_program(
            program, update_ranks, force_rep=force_rep,
            sequential_sm=sequential_sm, order=compile_order)
        self.program = self.compiled.program
        self.binding = dict(self.program.dims)
        if requested_orders is None:
            requested_orders = {st.target.name: compile_order
                                for st in self.program.statements}
        else:
            unknown = set(requested_orders) - {
                st.target.name for st in self.program.statements}
            if unknown:
                raise KeyError(f"order assigns unknown views: {sorted(unknown)}")
        self.fold_window = max(2, int(fold_window))
        self.max_fold_rank = max_fold_rank
        self._delta_fns: Dict[Tuple, Callable] = {}
        self._view_orders: Dict[str, int] = \
            self._resolve_view_orders(requested_orders)
        self._deferred: frozenset = frozenset(
            n for n, o in self._view_orders.items() if o >= 2)
        self._tiers: Tuple[int, ...] = tuple(
            sorted({o for o in self._view_orders.values() if o >= 2}))
        self._tier_factors: Dict[int, Dict[str, List]] = \
            {o: {} for o in self._tiers}
        self._tier_firings: Dict[int, int] = {o: 0 for o in self._tiers}
        self._tier_base: Dict[int, Dict[str, Array]] = \
            {o: {} for o in self._tiers}
        self._jit = jit
        self._apply_backend = apply_backend
        self._donate = donate
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.stats = EngineStats()
        if mesh is None:
            self._evaluator = build_evaluator(self.program, self.binding,
                                              jit=jit)
        else:
            from repro.dist.ivm_shard import build_distributed_evaluator
            self._evaluator = build_distributed_evaluator(
                self.program, mesh, jit=jit, axis=mesh_axis)
        # planned execution state (repro.plan)
        self.plan = None
        self.planner = None
        self._cache_ns: Optional[Tuple] = None
        self._trigger_cache = trigger_cache
        self._accum_rank: Dict[str, int] = {}   # hybrid staleness counters
        self._stale: set = set()                # lazy views awaiting refresh
        self._view_costs: Dict[str, List[Tuple[str, Tuple[int, int], float]]] = {}
        if plan is not None and trigger_cache is None:
            from repro.plan import global_trigger_cache
            self._trigger_cache = global_trigger_cache()
        if plan is not None:
            self._attach_plan(plan)
        self._trigger_fns: Dict[str, Callable] = {
            name: self._cached_build(("base", name, trig.rank),
                                     lambda trig=trig: self._build_trigger(trig))
            for name, trig in self.compiled.triggers.items()
        }
        # batched triggers, keyed by (input, bucket rank); compiled lazily
        # so only the buckets a workload actually hits pay compile time.
        self._batched_triggers: Dict[Tuple[str, int], Callable] = {}
        self._bucket_trigger_ir: Dict[Tuple[str, int], Trigger] = {}
        self._planned_fns: Dict[Tuple, Callable] = {}
        # row-slab trigger variants, keyed (input, rank bucket, row bucket)
        self._rowlocal_fns: Dict[Tuple, Callable] = {}
        self.rowlocal_fraction = float(rowlocal_fraction)
        # batching policy: cap the stacked rank (QR/SVD re-compression past
        # it) and the queue flush thresholds (size in stacked rank,
        # staleness in seconds).
        self.max_batch_rank = max_batch_rank
        self.recompress_tol = recompress_tol
        self.flush_size = flush_size
        self.flush_age = flush_age
        self.flush_policy = flush_policy
        self._cost_flush_rank: Dict[str, int] = {}
        self._pending: Dict[str, List[Tuple[np.ndarray, np.ndarray]]] = {}
        self._pending_since: Dict[str, float] = {}
        self.views: Dict[str, Array] = {}
        # failure containment (repro.guard): imported lazily so unguarded
        # engines never pay the import and the core↔guard layering stays
        # one-directional at module load.
        self.chaos = None
        self.guard = None
        if chaos is not None:
            from repro.guard import as_monkey
            self.chaos = as_monkey(chaos)
        if guard is not None:
            from repro.guard import EngineGuard, GuardConfig
            if guard is True:
                guard = GuardConfig()
            if donate and guard.transactional:
                raise ValueError(
                    "guard+donate are incompatible: transactional firings "
                    "keep the pre-firing view buffers alive for rollback, "
                    "and donation would let XLA overwrite them")
            self.guard = EngineGuard(guard, self)
        # whether guarded firings take the fused in-program path (trigger
        # + finite-check + select-commit in one dispatch) — admission can
        # then defer its own finite screen into that same program
        self._guard_fast_path = (
            self.guard is not None and self.guard.fused_path_ok
            and self.plan is None and self.flush_policy != "cost"
            and not self._deferred)

    # -- higher-order (deferred-cascade) maintenance ---------------------------
    def _resolve_view_orders(self, requested: Dict[str, int]
                             ) -> Dict[str, int]:
        """Effective per-view depth: a producer may never be staler than
        its consumers, so each view's requested depth is clamped to the
        minimum effective depth of the views that read it (inputs are
        always first-order)."""
        names = {st.target.name for st in self.program.statements}
        consumers: Dict[str, List[str]] = {}
        for st in self.program.statements:
            for vname in st.expr.free_vars():
                if vname in names and vname != st.target.name:
                    consumers.setdefault(vname, []).append(st.target.name)
        eff: Dict[str, int] = {}
        for st in reversed(self.program.statements):
            name = st.target.name
            o = max(1, int(requested.get(name, 1)))
            for c in consumers.get(name, ()):
                o = min(o, eff[c])
            eff[name] = o
        return eff

    def _window(self, o: int) -> int:
        return max(1, self.fold_window ** (o - 1))

    def _cascade_pending(self) -> bool:
        return any(fs for o in self._tiers
                   for fs in self._tier_factors[o].values())

    def _cascade_rebase_all(self) -> None:
        self._pending_input = {}
        for o in self._tiers:
            self._tier_factors[o] = {}
            self._tier_firings[o] = 0
            self._tier_base[o] = dict(self.views)

    def _cascade_snapshot(self):
        """Cascade state for transactional rollback (window factors,
        window-start bases, firing counters) — pointer copies only."""
        if not self._tiers:
            return None
        return ({o: {k: list(v) for k, v in self._tier_factors[o].items()}
                 for o in self._tiers},
                {o: dict(self._tier_base[o]) for o in self._tiers},
                dict(self._tier_firings))

    def _cascade_restore(self, snap) -> None:
        if snap is None:
            return
        factors, base, firings = snap
        self._tier_factors = {o: {k: list(v) for k, v in factors[o].items()}
                              for o in factors}
        self._tier_base = {o: dict(base[o]) for o in base}
        self._tier_firings = dict(firings)

    def _recompress(self, P, Q, max_rank: int):
        """:func:`~repro.core.factored.recompress_factors` at the
        engine's tolerance, counted, and counted again where it took the
        row-support route."""
        rows = row_support(P)
        P, Q = recompress_factors(P, Q, max_rank=max_rank,
                                  tol=self.recompress_tol, rows=rows)
        self.stats.recompressions += 1
        self.stats.support_recompressions += rows is not None
        return P, Q

    def _cascade_accumulate(self, input_name: str, pairs,
                            defer_input: bool = False) -> None:
        """Append one admitted firing's (pre-padding) factors to every
        tier's window, re-compressing at the rank cap, then fold any tier
        whose window is due.  ``pairs`` is the firing's update list (a
        whole batch still ticks each window once).  With ``defer_input``
        the factors are also banked — exactly, outside any rank cap —
        for :meth:`_apply_pending_inputs` to replay onto the input at
        the next fold."""
        norm = []
        for u, v in pairs:
            u = np.asarray(u, dtype=np.float32)
            v = np.asarray(v, dtype=np.float32)
            if u.ndim == 1:
                u = u[:, None]
            if v.ndim == 1:
                v = v[:, None]
            norm.append((u, v))
        if defer_input:
            self._pending_input.setdefault(input_name, []).extend(norm)
        for o in self._tiers:
            fs = self._tier_factors[o].setdefault(input_name, [])
            fs.extend(norm)
            self._tier_firings[o] += 1
            if self.max_fold_rank is not None:
                rank = sum(a.shape[1] for a, _ in fs)
                if rank > self.max_fold_rank:
                    P, Q = self._recompress(*stack_update_arrays(fs),
                                            self.max_fold_rank)
                    self._tier_factors[o][input_name] = \
                        [(np.asarray(P), np.asarray(Q))]
        self._maybe_fold()

    def _inputs_deferrable(self, input_name: str) -> bool:
        """True when nothing this trigger maintains needs to be current
        between folds: every maintained target is a deferred (depth >= 2)
        view and no guard/chaos/plan layer expects a per-firing
        transaction or partition decision.  The firing then banks its
        raw factors — no stacking, no padding, no device dispatch — and
        the input apply itself becomes part of the fold."""
        if not self._tiers or self.guard is not None \
                or self.chaos is not None or self.plan is not None \
                or self.planner is not None or self.mesh is not None:
            return False
        targets = {up.view for up in
                   self.compiled.triggers[input_name].updates}
        return (targets - {input_name}) <= self._deferred

    def _apply_pending_inputs(self) -> Dict[str, Tuple]:
        """Materialize deferred input state: one stacked GEMM per input
        applies everything banked since the last fold.  The banked
        factors are exact (never rank-capped), so the input is bitwise
        a function of the update stream alone — replay engines folding
        on the same cadence reproduce it identically.  Returns the
        stacked factors per input (``(P, Q, n_pairs)``) so the fold's
        sweep can reuse them instead of re-stacking the same window."""
        stacked: Dict[str, Tuple] = {}
        for input_name, pairs in self._pending_input.items():
            if not pairs:
                continue
            P, Q = stack_update_arrays(pairs)
            apply = Applier(self._apply_backend)
            self.views[input_name] = apply(
                input_name, self.views[input_name], jnp.asarray(P),
                jnp.asarray(Q))
            self._note_firing(apply)
            stacked[input_name] = (P, Q, len(pairs))
            pairs.clear()
        return stacked

    def _maybe_fold(self) -> None:
        due = [o for o in self._tiers
               if self._tier_firings[o] >= self._window(o)]
        if due:
            self._fold(max(due))

    def _fold(self, upto: int) -> None:
        """Fold the pending windows of every tier <= ``upto``, lowest
        first (a tier's fold reads its ancestors' *current* values, and
        lower tiers are never staler than higher ones).

        Guarded engines run the fold transactionally: snapshot → (chaos)
        → fold → finite-check, with rollback + an exact re-evaluation
        fallback on failure — a fold is a firing as far as containment
        is concerned."""
        tiers = [o for o in self._tiers if o <= upto]
        if not tiers:
            return
        # deferred-input engines bank the raw input factors per firing;
        # the fold is where the input state materializes (one stacked
        # GEMM — the same FLOPs as the per-firing applies it replaces)
        self._fold_prestacked = self._apply_pending_inputs()
        guarded = self.guard is not None and self.guard.config.transactional
        if guarded or self.chaos is not None:
            from repro.guard.txn import (FiringAborted, check_finite,
                                         restore_snapshot, take_snapshot)
            snap = take_snapshot(self) if guarded else None
            try:
                if self.chaos is not None:
                    self.chaos.maybe_raise_in_trigger()
                folded: set = set()
                for o in tiers:
                    folded |= self._fold_tier(o)
                if guarded and folded:
                    reason = check_finite(self.views, folded)
                    if reason is not None:
                        raise FiringAborted(reason, "<fold>", "validate")
            except Exception:
                if snap is None:
                    raise  # unguarded chaos: propagate like any kernel error
                restore_snapshot(self, snap)
                self.stats.fold_aborts += 1
                self.guard.stats.rollbacks += 1
                # exact, chaos-free fallback: re-evaluate the deferred
                # views from their (current) ancestors
                for o in tiers:
                    self._fold_tier(o, force_reeval=True)
        else:
            for o in tiers:
                self._fold_tier(o)
        self._fold_prestacked = {}
        self.stats.folds += 1

    def _fold_tier(self, o: int, force_reeval: bool = False) -> set:
        """Fold one tier's window and rebase it on the resulting store.
        Returns the set of view names the fold wrote."""
        targets = {n for n, oo in self._view_orders.items() if oo == o}
        factors = self._tier_factors.get(o, {})
        touched = [n for n, fs in factors.items() if fs]
        folded: set = set()
        if targets and touched:
            affected: set = set()
            for input_name in touched:
                affected |= {up.view for up in
                             self.compiled.triggers[input_name].updates}
            affected &= targets
            if affected:
                if force_reeval or len(touched) > 1:
                    # multi-input windows interleave updates to different
                    # inputs; re-evaluation from current ancestors is the
                    # always-exact fold for any mix
                    folded = self._fold_reeval(affected)
                else:
                    folded = self._fold_sweep(o, touched[0], affected)
        self._tier_factors[o] = {}
        self._tier_firings[o] = 0
        self._tier_base[o] = dict(self.views)
        self._stale -= targets
        return folded

    def _fold_reeval(self, affected: set) -> set:
        # one fused jitted re-evaluation from the (current) inputs
        # instead of an eager per-statement walk: at fold time the walk
        # pays ~2x the evaluator's cost in per-op dispatch alone, and
        # the fold IS the amortized price the depth-2 plan is built on.
        # Non-affected targets the evaluator recomputes are simply not
        # written back; replay/oracle engines fold through this same
        # path, so determinism comparisons stay bit-identical.
        computed = self._evaluator({k: self.views[k]
                                    for k in self.program.inputs})
        for name in affected:
            self.views[name] = computed[name]
        self.stats.fold_reevals += len(affected)
        return set(affected)

    def _fold_sweep(self, o: int, input_name: str, affected: set) -> set:
        """Single-input window fold: stack the window's factors and sweep
        each affected view ONCE from the tier's window-start base (the
        trigger's pre-update contract makes this exact), falling back to
        re-evaluation per view past its §7 crossover at the window rank."""
        from .cost import batched_strategy
        fs = self._tier_factors[o][input_name]
        pre = getattr(self, "_fold_prestacked", {}).get(input_name)
        if pre is not None and pre[2] == len(fs):
            # this tier's window is exactly the pending-input set the
            # fold just applied (both are "every update since time X"
            # append-only logs, so equal length ⇒ equal content): reuse
            # its stacked factors instead of re-concatenating the window
            P, Q = pre[0], pre[1]
        else:
            P, Q = stack_update_arrays(fs)
        r = int(P.shape[1])
        costs = {name: (shape, re) for name, shape, re
                 in self._factored_view_costs(input_name)}
        sweep: set = set()
        reeval: set = set()
        for name in affected:
            info = costs.get(name)
            if info is None:
                reeval.add(name)  # dense-rep views: no factored sweep
                continue
            shape, re_flops = info
            if batched_strategy(shape, r, r, re_flops) == "stacked":
                sweep.add(name)
            else:
                reeval.add(name)
        if sweep:
            bucket = batch_bucket(r)
            Pb, Qb = pad_factors_to_rank(P, Q, bucket)
            trig_targets = {up.view for up in
                            self.compiled.triggers[input_name].updates}
            maintained = {st.target.name for st in self.program.statements}
            lazy = frozenset((maintained & trig_targets) - sweep)
            fn = self._planned_trigger_fn(input_name, bucket,
                                          frozenset(), lazy)
            base = dict(self._tier_base[o])
            out = fn(base, np.asarray(Pb), np.asarray(Qb))
            self._note_firing(fn)
            for name in sweep:
                self.views[name] = out[name]
            self.stats.fold_sweeps += len(sweep)
        if reeval:
            self._fold_reeval(reeval)
        return sweep | reeval

    def _build_trigger(self, trig) -> Callable:
        """Single-device jitted trigger, or the row-sharded distributed
        one when the engine was given a mesh."""
        if self.mesh is not None:
            from repro.dist.ivm_shard import build_distributed_trigger
            return build_distributed_trigger(trig, self.program, self.mesh,
                                             jit=self._jit,
                                             axis=self.mesh_axis)
        return build_trigger_fn(trig, self.program, self.binding,
                                jit=self._jit,
                                apply_backend=self._apply_backend,
                                donate=self._donate)

    # -- maintenance plans (repro.plan) ---------------------------------------
    def _attach_plan(self, plan) -> None:
        from repro.plan import AdaptivePlanner, WorkloadDescriptor
        if isinstance(plan, WorkloadDescriptor):
            from repro.plan import plan_for_engine
            plan = plan_for_engine(self, plan)
        if isinstance(plan, AdaptivePlanner):
            self.planner = plan
            plan = plan.bind(self.compiled, self.binding,
                             mesh=self.mesh, mesh_axis=self.mesh_axis)
        self.set_plan(plan)

    def set_plan(self, plan) -> None:
        """Hot-swap the maintenance plan.

        Pending queues, hybrid staleness counters and lazy-view
        staleness all survive the swap — a re-plan changes how future
        firings refresh views, never the values they produce — so a
        serving engine can adopt a re-plan mid-stream without dropping
        its staleness contract.  Raises if the plan was priced for a
        different (program, dims) fingerprint.
        """
        from repro.plan import global_trigger_cache, program_fingerprint
        fp = program_fingerprint(self.program, self.binding)
        if plan.fingerprint != fp:
            raise ValueError(
                f"plan fingerprint {plan.fingerprint} does not match this "
                f"engine's program ({fp}); plans are not portable across "
                f"program structures or dimension bindings")
        if self._trigger_cache is None:
            self._trigger_cache = global_trigger_cache()
        self.plan = plan
        # a plan with per-view depth assignments is authoritative for the
        # deferred cascade: adopt (and re-resolve) its orders, settling
        # any pending windows under the old depths first
        plan_orders = {name: int(getattr(vp, "order", 1) or 1)
                       for name, vp in plan.views.items()}
        if any(o > 1 for o in plan_orders.values()):
            if any(not vp.materialize for vp in plan.views.values()):
                raise ValueError(
                    "a plan assigning depth >= 2 must materialize every "
                    "view: deferred folds sweep from window-start base "
                    "snapshots, which lazy (recompute-on-read) views "
                    "would leave inconsistent")
            self._adopt_orders(plan_orders)
        elif getattr(self, "_deferred", frozenset()):
            self._adopt_orders({})  # re-plan back down to first order
        # planned firings leave the guard's fused fast path (their
        # per-view partitioning runs under the snapshot/rollback path);
        # getattr: set_plan also runs mid-__init__, before the guard
        # (and flush policy) fields exist
        guard = getattr(self, "guard", None)
        self._guard_fast_path = (
            guard is not None and guard.fused_path_ok
            and self.plan is None
            and getattr(self, "flush_policy", None) != "cost"
            and not getattr(self, "_deferred", frozenset()))
        if self.planner is not None and self.planner.plan is not plan:
            # keep the attached adaptive planner's baseline in sync so
            # its next drift check does not silently revert a hot-swap
            self.planner.adopt(plan)

    def _adopt_orders(self, requested: Dict[str, int]) -> None:
        """Hot-swap the per-view depth assignment (adaptive re-plans).

        Pending windows are folded under the OLD depths first so no
        accumulated update is lost, then the cascade state and the
        trigger-cache namespace (which carries the order signature) are
        rebuilt."""
        eff = self._resolve_view_orders(requested)
        if getattr(self, "_view_orders", None) == eff:
            return
        if getattr(self, "_tiers", ()) and getattr(self, "views", None) \
                and self._cascade_pending():
            self._fold(self._tiers[-1])
        self._view_orders = eff
        self._deferred = frozenset(n for n, o in eff.items() if o >= 2)
        self._tiers = tuple(sorted({o for o in eff.values() if o >= 2}))
        self._pending_input = {}
        self._fold_prestacked = {}
        self._tier_factors = {o: {} for o in self._tiers}
        self._tier_firings = {o: 0 for o in self._tiers}
        self._tier_base = {o: dict(getattr(self, "views", None) or {})
                           for o in self._tiers}
        self._cache_ns = None  # namespace embeds the order signature

    def _cache_key(self, tail: Tuple) -> Tuple:
        if self._cache_ns is None:
            from repro.plan import mesh_cache_key, program_fingerprint
            # the namespace includes the compile-time delta depth and the
            # per-view deferral signature: a depth-2 engine must never
            # reuse (or poison) a first-order engine's compiled fns in a
            # shared TriggerCache
            order_sig = tuple(sorted(
                (n, o) for n, o in self._view_orders.items() if o > 1))
            self._cache_ns = (
                program_fingerprint(self.program, self.binding),
                self._apply_backend, self._jit, self._donate,
                self.compiled.force_rep, self.compiled.sequential_sm,
                mesh_cache_key(self.mesh, self.mesh_axis),
                self.compiled.order, order_sig)
        return self._cache_ns + tail

    def _cached_build(self, tail: Tuple, builder: Callable) -> Callable:
        """Build a trigger fn through the shared cache (identical plan
        keys across engine instances reuse the jitted callable — no
        re-trace, no re-compile)."""
        def build() -> Callable:
            self.stats.trigger_builds += 1
            with obs.span("engine.build"):
                return builder()

        if self._trigger_cache is None:
            return build()
        return self._trigger_cache.get_or_build(self._cache_key(tail), build)

    def _bucket_trigger(self, input_name: str, bucket: int) -> Trigger:
        """The trigger IR for (input, stacked-rank bucket)."""
        base = self.compiled.triggers[input_name]
        if bucket == base.rank:
            return base
        key = (input_name, bucket)
        trig = self._bucket_trigger_ir.get(key)
        if trig is None:
            trig = compile_batched_trigger(self.compiled, input_name, bucket)
            self._bucket_trigger_ir[key] = trig
        return trig

    def _factored_view_costs(self, input_name: str
                             ) -> List[Tuple[str, Tuple[int, int], float]]:
        """(view, shape, reeval FLOPs) per factored-maintained view of
        one trigger; cached per input (used on every cost-policy
        firing)."""
        cached = self._view_costs.get(input_name)
        if cached is None:
            from .cost import expr_cost, shape_of
            trig = self.compiled.triggers[input_name]
            by_name = {s.target.name: s for s in self.program.statements}
            cached = []
            for up in trig.updates:
                st = by_name.get(up.view)
                if up.kind != "lowrank" or st is None:
                    continue
                cached.append((up.view, shape_of(st.target, self.binding),
                               expr_cost(st.expr, self.binding).flops))
            self._view_costs[input_name] = cached
        return cached

    def _plan_decision(self, input_name: str, rank: int
                       ) -> Tuple[frozenset, frozenset]:
        """(views to re-evaluate, views to lazily skip) for a firing of
        ``input_name`` at stacked rank ``rank``."""
        if self.plan is not None:
            reeval, lazy = self.plan.decide(rank, self._accum_rank)
        elif self.flush_policy == "cost":
            # planless cost-policy engines still get the per-view §7
            # fallback: re-evaluate any view the stacked rank pushed
            # past its crossover instead of sweeping it
            from .cost import batched_strategy
            reeval = frozenset(
                name for name, shape, re in
                self._factored_view_costs(input_name)
                if batched_strategy(shape, rank, rank, re) == "reeval")
            lazy = frozenset()
        elif not self._deferred:
            return frozenset(), frozenset()
        else:
            reeval, lazy = frozenset(), frozenset()
        if self._deferred:
            # deferred (depth >= 2) views are never swept per firing:
            # they skip like lazy views and are refreshed by window
            # folds instead of on-read recomputation
            reeval = reeval - self._deferred
            lazy = lazy | self._deferred
        targets = {up.view for up in self.compiled.triggers[input_name].updates}
        # keep the partition scoped to this trigger's targets, EXCEPT
        # that a lazy view left stale by an earlier firing (possibly of
        # a different input's trigger) must stay visible so the planned
        # codegen pulls it into the recompute closure when a view
        # re-evaluated here reads it — otherwise the in-firing reeval
        # would silently consume the stale value
        return reeval & targets, (lazy & targets) | (self._stale & lazy)

    def _planned_trigger_fn(self, input_name: str, bucket: int,
                            reeval: frozenset, lazy: frozenset) -> Callable:
        key = (input_name, bucket, tuple(sorted(reeval)),
               tuple(sorted(lazy)))
        fn = self._planned_fns.get(key)
        if fn is None:
            fn = self._cached_build(
                ("planned",) + key,
                lambda: self._build_planned_trigger(input_name, bucket,
                                                    reeval, lazy))
            self._planned_fns[key] = fn
        return fn

    def _build_planned_trigger(self, input_name: str, bucket: int,
                               reeval: frozenset, lazy: frozenset
                               ) -> Callable:
        trig = self._bucket_trigger(input_name, bucket)
        if self.mesh is not None:
            from repro.dist.ivm_shard import build_distributed_planned_trigger
            return build_distributed_planned_trigger(
                trig, self.program, self.mesh, reeval_views=reeval,
                lazy_views=lazy, jit=self._jit, axis=self.mesh_axis)
        return build_planned_trigger_fn(
            trig, self.program, self.binding, reeval_views=reeval,
            lazy_views=lazy, jit=self._jit,
            apply_backend=self._apply_backend, donate=self._donate)

    def _fire(self, input_name: str, bucket: int, P: Array, Q: Array,
              screened: bool = False) -> None:
        """One trigger firing, transactional when the engine is guarded:
        snapshot → (chaos) → execute → validate outputs → commit, with
        an atomic rollback on any failure (:mod:`repro.guard.txn`).

        ``screened=True`` promises the factors already passed the host
        NaN/Inf screen (batch admission), so the fused fast path can
        drop its redundant in-program input screen — one fewer full
        pass over ``(P, Q)`` on device."""
        if self.guard is not None:
            return self.guard.fire(self, input_name, bucket, P, Q,
                                   screened=screened)
        if self.chaos is not None:
            # unguarded chaos: the injected fault propagates, exactly as
            # a real kernel error would without the guard layer
            self.chaos.maybe_raise_in_trigger()
        return self._fire_inner(input_name, bucket, P, Q)

    def _fire_inner(self, input_name: str, bucket: int, P: Array,
                    Q: Array) -> None:
        """One (possibly planned) trigger firing at stacked rank
        ``bucket``: partition views per the plan, execute, and keep the
        hybrid/lazy bookkeeping current."""
        reeval, lazy = self._plan_decision(input_name, bucket)
        # numpy factors go straight into the jitted trigger: its C++
        # argument path converts (and canonicalizes) them far cheaper
        # than an explicit host-side jnp.asarray/device_put round
        if not self._jit:  # unjitted bodies still need real jax arrays
            P, Q = jnp.asarray(P), jnp.asarray(Q)
        elif isinstance(P, (list, tuple)) or isinstance(Q, (list, tuple)):
            P, Q = np.asarray(P), np.asarray(Q)  # jit rejects raw lists
        if not reeval and not lazy:
            fn = self._batched_trigger_fn(input_name, bucket)
            with obs.span("engine.dispatch"):
                self.views = fn(self.views, P, Q)
            self._note_firing(fn)
            if self.plan is not None:
                for up in self.compiled.triggers[input_name].updates:
                    self._accum_rank[up.view] = \
                        self._accum_rank.get(up.view, 0) + bucket
            return
        fn = self._planned_trigger_fn(input_name, bucket, reeval, lazy)
        with obs.span("engine.dispatch"):
            self.views = fn(self.views, P, Q)
        self._note_firing(fn)
        recomputed = set(fn.recomputes)
        # count only plan-DIRECTED re-evaluations; recomputed also holds
        # lazy views pulled into the recompute closure for exactness
        self.stats.plan_reevals += len(reeval)
        self.stats.lazy_skips += len(fn.skipped)
        self._stale |= set(fn.skipped)
        self._stale -= recomputed
        for name in fn.incr_views:
            self._accum_rank[name] = self._accum_rank.get(name, 0) + bucket
        for name in recomputed:
            self._accum_rank[name] = 0

    def refresh(self, block: bool = False) -> Dict[str, Array]:
        """Recompute lazily-materialized views left stale by planned
        firings (program order, so stale ancestors refresh first).  On a
        deferred-cascade engine this is a read point: any pending window
        is folded first, so every deferred view is exact on return."""
        if self._tiers and self._cascade_pending():
            self._fold(self._tiers[-1])
        if not self._stale:
            return self.views
        if self.mesh is not None:
            # the row-sharded evaluator, never an eager product whose
            # placement the compiler would pick
            computed = self._evaluator({k: self.views[k]
                                        for k in self.program.inputs})
            for name in self._stale:
                self.views[name] = computed[name]
        else:
            for st in self.program.statements:
                if st.target.name in self._stale:
                    self.views[st.target.name] = evaluate(
                        st.expr, self.views, self.binding)
        if block:
            jax.block_until_ready(self.views)
        self._stale.clear()
        return self.views

    # -- lifecycle -----------------------------------------------------------
    def initialize(self, inputs: Dict[str, Array]) -> Dict[str, Array]:
        """Full evaluation of the program; materializes every view.

        On a mesh the inputs go to their row blocks first, whether they
        come from the host, from one device or already sharded, and the
        views are computed there (see the module docstring)."""
        missing = set(self.program.inputs) - set(inputs)
        if missing:
            raise KeyError(f"missing inputs: {sorted(missing)}")
        with obs.span("engine.initialize", request=True):
            if self.mesh is None:
                inputs = {k: jnp.asarray(v) for k, v in inputs.items()}
            else:
                from repro.dist.ivm_shard import shard_views
                with obs.span("engine.place"):
                    inputs = shard_views(inputs, self.mesh,
                                         axis=self.mesh_axis)
            with obs.span("engine.evaluate"):
                computed = self._evaluator(dict(inputs))
        self.views = {**inputs, **computed}
        self._stale.clear()
        self._accum_rank.clear()
        self._cascade_rebase_all()
        return dict(computed)

    # -- incremental path ------------------------------------------------------
    def apply_update(self, input_name: str, u: Array,
                     v: Optional[Array] = None,
                     block: bool = False) -> Dict[str, Array]:
        """Fire the trigger for ``input_name += u @ v.T`` (executing the
        engine's maintenance plan, when one is attached).

        ``u`` may be a :class:`~repro.core.factored.DeltaCarrier`
        instead of a raw left factor (``v`` then stays ``None``): a
        no-op carrier skips the firing entirely, a row-local carrier
        under the engine's ``rowlocal_fraction`` fires the row-slab
        trigger variant, and everything else widens to this dense path
        — which remains bit-identical to what it was before carriers
        existed.

        On a guarded engine the update is validated first (rejects go
        to quarantine, views untouched) and the firing is transactional
        (a chaos fault or non-finite output rolls back and returns the
        pre-firing views)."""
        with obs.span("engine.apply_update", request=True):
            if isinstance(u, DeltaCarrier) or v is None:
                return self._apply_carrier(input_name, as_carrier(u, v),
                                           block=block)
            return self._apply_update(input_name, u, v, block)

    def _apply_update(self, input_name: str, u: Array, v: Array,
                      block: bool) -> Dict[str, Array]:
        """The dense firing of :meth:`apply_update`."""
        rank = self.compiled.triggers[input_name].rank
        if self._tiers and self._inputs_deferrable(input_name):
            # deferred-input fast path: bank the factors and return —
            # the fold materializes the input along with the views
            self._cascade_accumulate(input_name, [(u, v)],
                                     defer_input=True)
            self.stats.updates_applied += 1
            self.stats.triggers_fired += 1
            if block:
                jax.block_until_ready(self.views)
            return self.views
        if self.chaos is not None:
            u, v = self.chaos.poison_update(u, v)
        if self.guard is not None:
            admitted = self.guard.admit(input_name, u, v,
                                        defer_finite=self._guard_fast_path)
            if admitted is None:
                return self.views
            u, v = admitted
        t0 = time.perf_counter()
        if self.guard is not None or self.chaos is not None:
            from repro.guard.txn import FiringAborted
            try:
                self._fire(input_name, rank, u, v)
            except FiringAborted as e:
                self.guard.on_abort(input_name, u, v, e.reason)
                return self.views
        elif self.plan is None and self.flush_policy != "cost" \
                and not self._deferred:
            fn = self._trigger_fns[input_name]
            # np factors feed the jit directly — see _fire_inner
            if not self._jit:
                u, v = jnp.asarray(u), jnp.asarray(v)
            elif isinstance(u, (list, tuple)) or isinstance(v, (list, tuple)):
                u, v = np.asarray(u), np.asarray(v)
            with obs.span("engine.dispatch"):
                self.views = fn(self.views, u, v)
            self._note_firing(fn)
        else:
            self._fire(input_name, rank, u, v)
        if self._tiers:
            self._cascade_accumulate(input_name, [(u, v)])
        if block:
            with obs.span("engine.block"):
                jax.block_until_ready(self.views)
            self.stats.trigger_seconds += time.perf_counter() - t0
            self.stats.sweep_flops_timed += self._sweep_flops(input_name, rank)
        self.stats.updates_applied += 1
        self.stats.triggers_fired += 1
        self.stats.stacked_rank += rank
        self.stats.fired_rank += rank
        self.stats.padded_rank += rank
        self._observe_firing(input_name, rank, 1)
        if self.guard is not None:
            self.guard.after_firing(self)
        return self.views

    # -- sparsity-aware carrier path (repro.core.factored.DeltaCarrier) --------
    def _rowlocal_ok(self, input_name: str, carrier: DeltaCarrier) -> bool:
        """Whether a row-local carrier may fire the row-slab trigger.

        Requires: a single-device, non-deferred engine (sharded and
        depth>=2 engines widen — the dense path is their oracle), an
        affected fraction under the ``rowlocal_fraction`` crossover, at
        least one maintained view the compiler proved row-local (else
        slab sweeping buys nothing), and an empty plan partition (a
        firing the plan wants to re-evaluate or skip must go through
        the planned dense codegen).  When *every* maintained view is
        row-local the plan/§7 decision is priced at the containment-
        scaled rank ``ceil(rank · frac)`` — a row-slab sweep touches
        ``r·m`` elements where the dense sweep the crossover was solved
        for touches ``n·m``, so a high-rank contained burst must not be
        kicked to re-evaluation at the full-rank price (the same
        ``K*/frac`` scaling the planner applies; docs/sparse_deltas.md).
        Triggers with any widened view keep the full-rank price: those
        views really do pay the dense sweep."""
        if self.mesh is not None or self._tiers:
            return False
        frac = carrier.affected_fraction()
        if frac > self.rowlocal_fraction:
            return False
        trig = self.compiled.triggers[input_name]
        kinds = [trig.carriers.get(up.view) for up in trig.updates
                 if up.kind == "lowrank" and up.view != input_name]
        if not any(kd == "row_local" for kd in kinds):
            # only the input's own (trivially row-local) self-update is
            # contained — every maintained view widens, so the slab
            # trigger buys nothing over the dense sweep
            return False
        rank = max(carrier.rank, 1)
        if all(kd == "row_local" for kd in kinds):
            rank = max(1, int(np.ceil(rank * frac)))
        reeval, lazy = self._plan_decision(input_name, rank)
        return not reeval and not lazy

    def _rowlocal_trigger_fn(self, input_name: str, rank_bucket: int,
                             row_bucket: int) -> Callable:
        """The jitted row-slab trigger for (input, rank bucket, row
        bucket), compiled on first use and shared through the trigger
        cache like every other variant."""
        key = (input_name, rank_bucket, row_bucket)
        fn = self._rowlocal_fns.get(key)
        if fn is None:
            trig = self._bucket_trigger(input_name, rank_bucket)
            fn = self._cached_build(
                ("rowlocal", input_name, rank_bucket, row_bucket),
                lambda: build_rowlocal_trigger_fn(
                    trig, self.program, self.binding,
                    row_bucket=row_bucket, jit=self._jit,
                    apply_backend=self._apply_backend,
                    donate=self._donate))
            self._rowlocal_fns[key] = fn
        return fn

    def _apply_carrier(self, input_name: str, carrier: DeltaCarrier,
                       block: bool = False) -> Dict[str, Array]:
        """Dispatch one carrier: no-op → skip, contained row-local →
        row-slab firing, anything else → widen to the dense factored
        path (``carrier.factors()`` is exact, so widening never changes
        the result — only the traffic)."""
        if input_name not in self.compiled.triggers:
            raise KeyError(f"no trigger for input {input_name!r}; have "
                           f"{sorted(self.compiled.triggers)}")
        if carrier.kind == "noop":
            # legally skip the firing: a no-op moves no view, so there
            # is nothing for chaos to poison or the guard to validate
            self.stats.noop_skips += 1
            self.stats.updates_applied += 1
            if block:
                jax.block_until_ready(self.views)
            return self.views
        if carrier.kind == "row_local":
            if self._rowlocal_ok(input_name, carrier):
                return self._apply_rowlocal(input_name, carrier,
                                            block=block)
            self.stats.widened_carriers += 1
        P, Q = carrier.factors()
        return self._apply_update(input_name, P, Q, block)

    def _apply_rowlocal(self, input_name: str, carrier: RowLocalCarrier,
                        block: bool = False, t_count: int = 1,
                        poisoned: bool = False,
                        stacked_rank: Optional[int] = None
                        ) -> Dict[str, Array]:
        """Fire the row-slab trigger for one (possibly stacked)
        row-local carrier: chaos poisoning and guard admission run on
        the *compact* ``(block, V)`` factors (same call sequence as the
        dense path — one poison gate per logical update stream entry is
        preserved by the batch path poisoning members before stacking),
        then the rank is padded to its power-of-two bucket and the row
        set to a power-of-two row bucket (out-of-bounds sentinel ``n``,
        zero block rows — exact, see
        :func:`~repro.core.codegen.build_rowlocal_trigger_fn`)."""
        rows = np.asarray(carrier.rows, dtype=np.int32)
        B = np.asarray(carrier.block, dtype=np.float32)
        V = np.asarray(carrier.V, dtype=np.float32)
        if self.chaos is not None and not poisoned:
            B, V = self.chaos.poison_update(B, V)
            B = np.asarray(B, dtype=np.float32)
            V = np.asarray(V, dtype=np.float32)
        if self.guard is not None:
            admitted = self.guard.admit_carrier(input_name, rows, B, V,
                                                count=t_count)
            if admitted is None:
                return self.views
            B, V = admitted
        t0 = time.perf_counter()
        rows0, B0, V0 = rows, B, V  # pre-padding (what an abort keeps)
        rank = B.shape[1]
        n_in = int(carrier.nm[0])
        base = self.compiled.triggers[input_name].rank
        rank_bucket = rank if rank == base else batch_bucket(rank)
        if rank_bucket != rank:
            B = np.concatenate(
                [B, np.zeros((B.shape[0], rank_bucket - rank),
                             np.float32)], axis=1)
            V = np.concatenate(
                [V, np.zeros((V.shape[0], rank_bucket - rank),
                             np.float32)], axis=1)
        r = int(rows.shape[0])
        row_bucket = max(8, 1 << (r - 1).bit_length())
        if row_bucket > r:
            rows = np.concatenate(
                [rows, np.full(row_bucket - r, n_in, np.int32)])
            B = np.concatenate(
                [B, np.zeros((row_bucket - r, rank_bucket), np.float32)],
                axis=0)
        fn = self._rowlocal_trigger_fn(input_name, rank_bucket, row_bucket)
        if self.guard is not None or self.chaos is not None:
            from repro.guard.txn import FiringAborted
            try:
                if self.guard is not None:
                    self.guard.fire_rowlocal(self, input_name, fn,
                                             rows, B, V)
                else:
                    self.chaos.maybe_raise_in_trigger()
                    self.views = fn(self.views, rows, B, V)
            except FiringAborted as e:
                P0 = np.zeros((n_in, B0.shape[1]), np.float32)
                P0[rows0] = B0
                self.guard.on_abort(input_name, P0, V0, e.reason)
                return self.views
        else:
            with obs.span("engine.dispatch"):
                self.views = fn(self.views, rows, B, V)
        self._note_firing(fn)
        return self._rowlocal_epilogue(input_name, carrier, rank_bucket, r,
                                       t0, block, t_count, stacked_rank)

    def _note_firing(self, fn) -> None:
        """Count what this firing's program reports: its Pallas applies
        that ran the XLA reference (``fn.fallbacks``, see
        :class:`~repro.core.codegen.Applier`), its views applied in one
        pass with their products (``fn.fused_views``, see
        :func:`~repro.core.codegen.fused_view_passes`) and, for a
        row-sharded firing, the collective bytes of its compiled program
        (``fn.collective_bytes``, see
        :func:`repro.dist.ivm_shard.build_distributed_trigger`)."""
        self.stats.pallas_fallbacks += len(getattr(fn, "fallbacks", ()))
        self.stats.fused_view_passes += len(getattr(fn, "fused_views", ()))
        self.stats.collective_bytes += getattr(fn, "collective_bytes", 0)

    def _rowlocal_epilogue(self, input_name: str, carrier: RowLocalCarrier,
                           rank: int, r: int, t0: float, block: bool,
                           t_count: int, stacked_rank: Optional[int]
                           ) -> Dict[str, Array]:
        """Shared accounting tail of a row-slab firing: plan
        staleness, timed-sweep stats, firing counters, and the planner's
        observed affected fraction.  ``rank`` is the rank fired (padded
        to its bucket);
        ``stacked_rank`` the batch's rank before re-compression, where
        it had one."""
        if self.plan is not None:
            for up in self.compiled.triggers[input_name].updates:
                self._accum_rank[up.view] = \
                    self._accum_rank.get(up.view, 0) + rank
        if block:
            with obs.span("engine.block"):
                jax.block_until_ready(self.views)
            self.stats.trigger_seconds += time.perf_counter() - t0
            self.stats.sweep_flops_timed += \
                self._rowlocal_sweep_flops(input_name, rank, r)
        self.stats.updates_applied += t_count
        self.stats.triggers_fired += 1
        self.stats.rowlocal_firings += 1
        if t_count > 1:
            self.stats.batches_applied += 1
        self.stats.stacked_rank += (carrier.rank if stacked_rank is None
                                    else stacked_rank)
        self.stats.fired_rank += carrier.rank
        self.stats.padded_rank += rank
        self._observe_firing(input_name, carrier.rank, t_count,
                             affected_fraction=carrier.affected_fraction())
        if self.guard is not None:
            self.guard.after_firing(self)
        return self.views

    def _rowlocal_sweep_flops(self, input_name: str, rank: int,
                              r: int) -> float:
        """FLOPs of one row-slab sweep: row-local views pay
        ``2·rank·r·m``, widened views the full ``2·rank·n·m``."""
        trig = self.compiled.triggers[input_name]
        total = 0.0
        for name, (n, m), _ in self._factored_view_costs(input_name):
            rows_eff = r if trig.carriers.get(name) == "row_local" else n
            total += 2.0 * rank * rows_eff * m
        return total

    def _apply_carrier_batch(self, input_name: str, updates,
                             block: bool = False) -> Dict[str, Array]:
        """Batched carrier path: drop no-ops, stack the rest
        (:func:`~repro.core.factored.stack_carriers` — union row
        support while everything stays row-local), and fire once.  A
        stack that widens — any dense member, or a union past the
        crossover — expands to factor pairs and rides the ordinary
        batched path, whose per-update poisoning/admission semantics it
        then inherits verbatim."""
        carriers = [x if isinstance(x, DeltaCarrier)
                    else as_carrier(x[0], x[1]) for x in updates]
        live = [c for c in carriers if c.kind != "noop"]
        skipped = len(carriers) - len(live)
        self.stats.noop_skips += skipped
        self.stats.updates_applied += skipped
        if not live:
            if block:
                jax.block_until_ready(self.views)
            return self.views
        probe = stack_carriers(live)
        if not (probe.kind == "row_local"
                and self._rowlocal_ok(input_name, probe)):
            self.stats.widened_carriers += \
                sum(1 for c in live if c.kind == "row_local")
            return self._apply_updates(input_name,
                                       [c.factors() for c in live], block)
        # row-local fast path: poison each member compactly (one chaos
        # gate per logical update — the same draw count as the dense
        # batched path), restack, optionally re-compress the compact
        # factors (QR touches only the r affected rows, so the row
        # support is preserved exactly), then one row-slab firing
        if self.chaos is not None:
            repl = []
            for c in live:
                Bp, Vp = self.chaos.poison_update(c.block, c.V)
                repl.append(RowLocalCarrier(
                    c.rows, np.asarray(Bp, np.float32),
                    np.asarray(Vp, np.float32), c.n))
            live = repl
            probe = stack_carriers(live)
        stacked = probe
        if (self.max_batch_rank is not None
                and stacked.rank > self.max_batch_rank):
            with obs.span("engine.recompress"):
                B2, V2 = self._recompress(stacked.block, stacked.V,
                                          self.max_batch_rank)
            stacked = RowLocalCarrier(stacked.rows,
                                      np.asarray(B2, np.float32),
                                      np.asarray(V2, np.float32),
                                      stacked.n)
        return self._apply_rowlocal(input_name, stacked, block=block,
                                    t_count=len(live), poisoned=True,
                                    stacked_rank=probe.rank)

    # -- batched incremental path ---------------------------------------------
    def apply_updates(self, input_name: str,
                      updates: Sequence[Tuple[Array, Array]],
                      block: bool = False) -> Dict[str, Array]:
        """Apply a whole update stream ``[(u_1, v_1) … (u_T, v_T)]`` to one
        input in a single batched trigger firing (§6 batching).

        The factors are stacked into ``P = [u_1 … u_T]``, ``Q = [v_1 … v_T]``
        (one rank-ΣkT update), optionally re-compressed when the stacked
        rank exceeds ``max_batch_rank``, then zero-padded up to the next
        power-of-two bucket so the per-bucket jit cache stays warm across
        ragged batch sizes.  Every maintained view is swept ONCE per batch
        instead of once per update — the whole point of the pipeline.
        """
        if input_name not in self.compiled.triggers:
            raise KeyError(f"no trigger for input {input_name!r}; have "
                           f"{sorted(self.compiled.triggers)}")
        with obs.span("engine.apply_updates", request=True):
            updates = list(updates)
            if any(isinstance(x, DeltaCarrier) for x in updates):
                return self._apply_carrier_batch(input_name, updates,
                                                 block=block)
            return self._apply_updates(input_name, updates, block)

    def _apply_updates(self, input_name: str, updates: list,
                       block: bool) -> Dict[str, Array]:
        """The dense batched firing of :meth:`apply_updates`."""
        if self.chaos is not None:
            updates = [self.chaos.poison_update(u, v) for u, v in updates]
        if not updates:
            return self.views
        if self._tiers and self._inputs_deferrable(input_name):
            # deferred-input fast path: every maintained target of this
            # trigger is folded from the window anyway, so the firing
            # banks its raw factors and does no stacking, padding, or
            # device dispatch at all; flush()/output() (and any due
            # fold) first materialize the pending input state
            self._cascade_accumulate(input_name, updates, defer_input=True)
            self.stats.updates_applied += len(updates)
            self.stats.triggers_fired += 1
            self.stats.batches_applied += 1
            if block:
                jax.block_until_ready(self.views)
            return self.views
        t0 = time.perf_counter()  # before admission+stacking: host-side
        # concat (and any device sync from jax-array factors) is part of
        # the batch cost — the guard's fast path fuses admission INTO
        # the concat the trigger needs anyway
        with obs.span("engine.stack"):
            P = Q = None
            if self.guard is not None:
                stacked = self.guard.admit_batch_stacked(input_name, updates)
                if stacked is not None:
                    P, Q = stacked
                else:
                    # careful walk: one poisoned update quarantines alone
                    # and the healthy remainder still batches
                    updates = self.guard.admit_batch(input_name, updates)
                    if not updates:
                        return self.views
            if P is None:
                P, Q = stack_update_arrays(updates)
        t_count = len(updates)
        stacked_rank = P.shape[1]
        if self.max_batch_rank is not None and P.shape[1] > self.max_batch_rank:
            with obs.span("engine.recompress"):
                P, Q = self._recompress(P, Q, self.max_batch_rank)
        P0, Q0 = P, Q  # pre-padding factors (what a rollback quarantines)
        bucket = batch_bucket(P.shape[1])
        P, Q = pad_factors_to_rank(P, Q, bucket)
        if self.guard is not None or self.chaos is not None:
            from repro.guard.txn import FiringAborted
            try:
                # batch admission already host-screened the factors
                self._fire(input_name, bucket, P, Q, screened=True)
            except FiringAborted as e:
                self.guard.on_abort(input_name, P0, Q0, e.reason)
                return self.views
        else:
            self._fire(input_name, bucket, P, Q)
        if self._tiers:
            self._cascade_accumulate(input_name, [(P0, Q0)])
        if block:
            with obs.span("engine.block"):
                jax.block_until_ready(self.views)
            self.stats.trigger_seconds += time.perf_counter() - t0
            self.stats.sweep_flops_timed += self._sweep_flops(input_name,
                                                              bucket)
        self.stats.updates_applied += t_count
        self.stats.triggers_fired += 1
        self.stats.batches_applied += 1
        self.stats.stacked_rank += stacked_rank
        self.stats.fired_rank += P0.shape[1]
        self.stats.padded_rank += bucket
        self._observe_firing(input_name, stacked_rank, t_count)
        if self.guard is not None:
            self.guard.after_firing(self)
        return self.views

    def _sweep_flops(self, input_name: str, rank: int) -> float:
        """FLOPs of one factored sweep over this trigger's maintained
        views at stacked rank ``rank`` — the denominator behind
        ``stats.trigger_seconds`` that online cost_scale refitting
        (:meth:`repro.plan.AdaptivePlanner.refit_from_stats`) divides by."""
        return sum(2.0 * rank * n * m for _, (n, m), _
                   in self._factored_view_costs(input_name))

    def _observe_firing(self, input_name: str, stacked_rank: int,
                        t_count: int,
                        affected_fraction: Optional[float] = None) -> None:
        """Report one firing to the attached adaptive planner (both the
        per-update and the batched path), adopting a re-plan if due.
        Row-local firings also report their affected fraction, which
        the adaptive planner folds into the observed workload; a custom
        planner whose ``observe`` predates the kwarg still works."""
        if self.planner is None:
            return
        if affected_fraction is not None:
            try:
                self.planner.observe(input_name, stacked_rank, t_count,
                                     affected_fraction=affected_fraction)
            except TypeError:
                self.planner.observe(input_name, stacked_rank, t_count)
        else:
            self.planner.observe(input_name, stacked_rank, t_count)
        if hasattr(self.planner, "refit_from_stats"):
            self.planner.refit_from_stats(self.stats)
        new_plan = self.planner.maybe_replan()
        if new_plan is not None:
            self.set_plan(new_plan)
            self.stats.replans += 1

    def _batched_trigger_fn(self, input_name: str, bucket: int) -> Callable:
        """The jitted trigger for (input, bucket), compiled on first use."""
        key = (input_name, bucket)
        fn = self._batched_triggers.get(key)
        if fn is None:
            base = self.compiled.triggers[input_name]
            if bucket == base.rank:
                fn = self._trigger_fns[input_name]
            else:
                fn = self._cached_build(
                    ("batched", input_name, bucket),
                    lambda: self._build_trigger(
                        self._bucket_trigger(input_name, bucket)))
            self._batched_triggers[key] = fn
        return fn

    # -- update queue (serving-path coalescing) --------------------------------
    def enqueue_update(self, input_name: str, u: Array, v: Array
                       ) -> Optional[Dict[str, Array]]:
        """Queue ``input_name += u @ v.T`` for the next coalesced flush.

        Flushes automatically per the engine's ``flush_policy`` —
        ``"fixed"``: pending stacked rank reaches ``flush_size``;
        ``"cost"``: the cost model's crossover (:meth:`cost_flush_rank`);
        both: the oldest queued update is older than ``flush_age``
        seconds.  Returns the refreshed views on flush, else ``None``
        (views are stale until the next :meth:`flush`).
        """
        if input_name not in self.compiled.triggers:
            raise KeyError(f"no trigger for input {input_name!r}; have "
                           f"{sorted(self.compiled.triggers)}")
        u = np.asarray(u, dtype=np.float32)
        v = np.asarray(v, dtype=np.float32)
        if self.chaos is not None:
            u, v = self.chaos.poison_update(u, v)
        if self.guard is not None:
            admitted = self.guard.admit(input_name, u, v)
            if admitted is None:
                return None
            u, v = admitted
        q = self._pending.setdefault(input_name, [])
        if not q:
            self._pending_since[input_name] = time.perf_counter()
        q.append((u, v))
        return self.maybe_flush(input_name)

    def pending_rank(self, input_name: str) -> int:
        return sum(u.shape[1] if u.ndim == 2 else 1
                   for u, _ in self._pending.get(input_name, ()))

    def pending_age(self, input_name: str) -> float:
        if not self._pending.get(input_name):
            return 0.0
        return time.perf_counter() - self._pending_since[input_name]

    def maybe_flush(self, input_name: str) -> Optional[Dict[str, Array]]:
        """Flush one input's queue if the active policy says so.

        ``"fixed"``: the stacked-rank/staleness thresholds.  ``"cost"``:
        the cost model — flush at the first stacked rank where some
        maintained view's :func:`~repro.core.cost.batched_strategy` stops
        answering ``"stacked"`` (the §7 crossover); staleness still
        bounds latency.  Flushing at the crossover does NOT by itself
        re-evaluate the losing view — it bounds the stacked rank; the
        flushed firing then makes the per-view choice (:meth:`_fire`),
        re-evaluating exactly the views the rank pushed past their
        crossover and sweeping the rest.
        """
        if self.pending_age(input_name) >= self.flush_age:
            return self.flush(input_name)
        threshold = (self.cost_flush_rank(input_name)
                     if self.flush_policy == "cost" else self.flush_size)
        if self.pending_rank(input_name) >= threshold:
            return self.flush(input_name)
        return None

    def _lowrank_view_costs(self, input_name: str
                            ) -> List[Tuple[Tuple[int, int], float]]:
        """(view shape, per-view reeval FLOPs) for every maintained view
        the trigger updates in factored form (the input itself has no
        re-evaluation expression and is excluded)."""
        return [(shape, reeval) for _, shape, reeval
                in self._factored_view_costs(input_name)]

    def cost_flush_rank(self, input_name: str) -> int:
        """The stacked rank at which the ``"cost"`` policy flushes: the
        first K where ``batched_strategy(shape, K, K, reeval)`` stops
        answering ``"stacked"`` for some maintained view, i.e. one past
        the smallest §7 crossover (first integer K with
        reeval_flops < 2·K·n·m).  Computed once per input and cached;
        triggers with no factored views fall back to ``flush_size``.
        The firing this flush triggers re-evaluates any view actually
        past its own crossover (per-view fallback) rather than sweeping
        it at the losing rank.
        """
        cached = self._cost_flush_rank.get(input_name)
        if cached is None:
            firsts = [int(reeval / (2.0 * n * m)) + 1
                      for (n, m), reeval
                      in self._lowrank_view_costs(input_name)]
            cached = min(firsts) if firsts else self.flush_size
            self._cost_flush_rank[input_name] = cached
        return cached

    def flush(self, input_name: Optional[str] = None,
              block: bool = False) -> Dict[str, Array]:
        """Apply all pending updates (for one input, or every input).

        The exactness point before a read: also recomputes any lazily
        maintained views that planned firings left stale, so every view
        in :attr:`views` is current when this returns."""
        names = [input_name] if input_name is not None else \
            [n for n, q in self._pending.items() if q]
        for name in names:
            q = self._pending.get(name)
            if q:
                # apply before popping: if the trigger raises, the queue
                # survives for a retry instead of silently vanishing
                self.apply_updates(name, q, block=block)
            self._pending.pop(name, None)
            self._pending_since.pop(name, None)
        if self._stale or (self._tiers and self._cascade_pending()):
            self.refresh(block=block)
        return self.views

    # -- baseline path ---------------------------------------------------------
    def reevaluate(self, block: bool = False) -> Dict[str, Array]:
        """The paper's re-evaluation strategy: recompute from the current
        inputs (which the triggers have been keeping up to date)."""
        self._apply_pending_inputs()  # deferred-input engines: make current
        inputs = {k: self.views[k] for k in self.program.inputs}
        t0 = time.perf_counter()
        computed = self._evaluator(inputs)
        if block:
            jax.block_until_ready(computed)
            self.stats.reeval_seconds += time.perf_counter() - t0
            self.stats.reeval_flops_timed += self.reeval_flops()
        self.views.update(computed)
        self._stale.clear()
        self._accum_rank.clear()
        self._cascade_rebase_all()  # windows are void: every view is current
        self.stats.reevals += 1
        return dict(computed)

    # -- introspection -----------------------------------------------------------
    def output(self, name: Optional[str] = None) -> Array:
        self.stats.reads += 1
        if self.planner is not None and \
                hasattr(self.planner, "observe_read"):
            self.planner.observe_read()
        if self._stale or (self._tiers and self._cascade_pending()):
            self.refresh()
        name = name or self.program.output_names()[0]
        return self.views[name]

    def trigger_flops(self, input_name: str) -> float:
        return trigger_flops(self.compiled.triggers[input_name], self.program,
                             self.binding)

    # -- materialized Δᵈ views (symbolic hierarchy) ----------------------------
    def delta_trigger_fn(self, input_name: str, depth: int,
                         rank: Optional[int] = None) -> Callable:
        """Jitted trigger maintaining the ``__d{depth}__V`` views.

        The shared-cache key carries the depth explicitly (plus the
        engine namespace's order signature) — the latent collision this
        fixes: the old tails ``("base", input, rank)`` would have let a
        depth-2 trigger silently reuse a first-order compiled fn."""
        if rank is None:
            rank = self.compiled.triggers[input_name].rank
        bucket = batch_bucket(rank)
        if depth == 1:
            return self._batched_trigger_fn(input_name, bucket)
        key = (input_name, depth, bucket)
        fn = self._delta_fns.get(key)
        if fn is None:
            fn = self._cached_build(
                ("delta", input_name, depth, bucket),
                lambda: self._build_trigger(compile_delta_trigger(
                    self.compiled, input_name, depth, bucket)))
            self._delta_fns[key] = fn
        return fn

    def materialize_delta_views(self, input_name: str, depth: int,
                                rank: Optional[int] = None
                                ) -> Tuple[str, ...]:
        """Zero-initialize the ΔᵈV auxiliary views the depth-``depth``
        trigger for ``input_name`` maintains; returns their names."""
        from .cost import shape_of
        if rank is None:
            rank = self.compiled.triggers[input_name].rank
        trig = compile_delta_trigger(self.compiled, input_name, depth,
                                     batch_bucket(rank))
        by_name = {st.target.name: st.target
                   for st in self.program.statements}
        names = []
        for up in trig.updates:
            base = up.view.split("__", 2)[-1]
            n, m = shape_of(by_name[base], self.binding)
            self.views.setdefault(up.view,
                                  jnp.zeros((n, m), dtype=jnp.float32))
            names.append(up.view)
        return tuple(names)

    def reeval_flops(self) -> float:
        from .cost import expr_cost
        seen: Dict[int, bool] = {}
        from .cost import _expr_cost_shared
        return sum(_expr_cost_shared(s.expr, self.binding, seen).flops
                   for s in self.program.statements)


class ReevalEngine:
    """Pure re-evaluation baseline: applies the update to the input, then
    recomputes every view from scratch (paper's REEVAL strategy)."""

    def __init__(self, program: Program, jit: bool = True):
        self.program = program
        self.binding = dict(program.dims)
        self._evaluator = build_evaluator(program, self.binding, jit=jit)
        self.views: Dict[str, Array] = {}

    def initialize(self, inputs: Dict[str, Array]) -> Dict[str, Array]:
        computed = self._evaluator(dict(inputs))
        self.views = {**{k: jnp.asarray(v) for k, v in inputs.items()},
                      **computed}
        return dict(computed)

    def apply_update(self, input_name: str, u: Array, v: Array,
                     block: bool = False) -> Dict[str, Array]:
        self.views[input_name] = self.views[input_name] + u @ v.T
        inputs = {k: self.views[k] for k in self.program.inputs}
        computed = self._evaluator(inputs)
        if block:
            jax.block_until_ready(computed)
        self.views.update(computed)
        return self.views

    def output(self, name: Optional[str] = None) -> Array:
        name = name or self.program.output_names()[0]
        return self.views[name]


def max_abs_diff(a: Dict[str, Array], b: Dict[str, Array],
                 keys: Optional[Tuple[str, ...]] = None) -> float:
    keys = keys or tuple(set(a) & set(b))
    worst = 0.0
    for k in keys:
        worst = max(worst, float(jnp.max(jnp.abs(a[k] - b[k]))))
    return worst

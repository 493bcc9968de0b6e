"""repro.guard — failure containment around every trigger firing.

Five cooperating layers (see docs/robustness.md for the failure matrix):

  1. :mod:`repro.guard.validate` — admission checks + quarantine for
     incoming ``(u, v)`` update factors;
  2. :mod:`repro.guard.txn`      — transactional firings: snapshot,
     post-firing NaN/Inf validation, atomic rollback;
  3. :mod:`repro.guard.sentinel` — stochastic drift probes + targeted
     exactness recovery, feeding the adaptive planner;
  4. :mod:`repro.guard.chaos`    — deterministic seeded fault injection
     threaded through the engine / checkpoints / fault tolerance;
  5. :mod:`repro.guard.degrade`  — serve-path retries, circuit breaker,
     last-good-snapshot fallback with explicit staleness.

Attach to an engine with ``IncrementalEngine(prog, guard=GuardConfig())``
(:class:`EngineGuard` is the per-engine runtime the engine drives);
inject faults with ``IncrementalEngine(prog, chaos=ChaosConfig(...))``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import numpy as np

from .chaos import ChaosConfig, ChaosError, ChaosMonkey, as_monkey
from .degrade import (CircuitBreaker, DegradePolicy, GuardedView,
                      retry_with_backoff)
from .sentinel import DriftSentinel, SentinelConfig
from .txn import (FiringAborted, FiringSnapshot, changed_views,
                  check_finite, restore_snapshot, take_snapshot)
from .validate import (QuarantinedUpdate, QuarantineQueue, ValidationPolicy,
                       validate_carrier, validate_update)

__all__ = [
    "GuardConfig", "GuardStats", "EngineGuard",
    "ValidationPolicy", "QuarantineQueue", "QuarantinedUpdate",
    "validate_update", "validate_carrier",
    "FiringAborted", "FiringSnapshot", "take_snapshot", "restore_snapshot",
    "changed_views", "check_finite",
    "SentinelConfig", "DriftSentinel",
    "ChaosConfig", "ChaosError", "ChaosMonkey", "as_monkey",
    "DegradePolicy", "CircuitBreaker", "GuardedView", "retry_with_backoff",
]


@dataclass(frozen=True)
class GuardConfig:
    """Everything one guarded engine enforces.

    ``transactional=False`` keeps validation/quarantine but lets a
    failed firing propagate (debugging); ``sentinel=None`` disables
    drift probing.  The default — validation + transactional firings,
    no sentinel — is the cheapest configuration that still guarantees
    the store never goes non-finite.
    """

    validation: ValidationPolicy = field(default_factory=ValidationPolicy)
    sentinel: Optional[SentinelConfig] = None
    transactional: bool = True
    quarantine_capacity: int = 1024


@dataclass
class GuardStats:
    """Failure-log counters — deliberately NOT part of
    :class:`~repro.core.runtime.EngineStats`, so a rollback can restore
    the engine's stats bit-identically while the guard still remembers
    what went wrong.

    On the fused fast path the counters are *eventually consistent*:
    a firing's outcome lives on device until the next sync window
    (every 32 firings) or an explicit :meth:`EngineGuard.sync`.  The
    store itself is always protected immediately — only the accounting
    is deferred."""

    admitted: int = 0
    quarantined: int = 0
    noop_skips: int = 0          # updates dropped by the no-op gate (legal
                                 # skips, NOT faults — never quarantined)
    aborted_firings: int = 0
    rollbacks: int = 0
    probes: int = 0
    drift_recoveries: int = 0
    max_drift: float = 0.0


class EngineGuard:
    """Per-engine guard runtime; driven by
    :class:`~repro.core.runtime.IncrementalEngine` at its admission,
    firing, and post-commit hooks."""

    def __init__(self, config: GuardConfig, engine):
        import dataclasses
        from repro.core.cost import shape_of
        self.config = config
        self.quarantine = QuarantineQueue(config.quarantine_capacity)
        self.stats = GuardStats()
        self.sentinel = (DriftSentinel(config.sentinel, engine.program,
                                       engine.binding)
                         if config.sentinel is not None else None)
        self._input_shapes = {
            name: shape_of(var, engine.binding)
            for name, var in engine.program.inputs.items()}
        # this config can run firings through the fused in-program path
        # (trigger + finite-check + select-commit in one dispatch)
        self.fused_path_ok = (config.transactional
                              and config.validation.check_outputs)
        # admission policy minus the finite screen — what the host still
        # checks when the finite screen is deferred into the fused
        # firing program
        self._structural_policy = dataclasses.replace(
            config.validation, check_finite=False)
        # fused trigger+finite-check programs, keyed by (input, bucket)
        self._fused: dict = {}
        # fused firings whose outcome has not been fetched yet: the
        # select-commit already kept the store safe on device, so only
        # the *accounting* (reject/rollback counters + quarantine) is
        # deferred
        self._pending: list = []
        # device-resident cumulative [input-rejects, output-aborts]
        # counts, threaded through every fused firing; sync() learns
        # "all clean" from ONE fetch regardless of how many firings are
        # pending, and only walks per-firing records when a count moved
        self._nbad = None
        self._nbad_seen = (0, 0)

    # -- admission (layer 1) -------------------------------------------------
    def admit(self, input_name: str, u, v, defer_finite: bool = False
              ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Validate one update; quarantine and return None on reject.

        With ``defer_finite=True`` (the engine's fused fast path) the
        host checks only structure — shape/dtype/rank conformance — and
        the NaN/Inf screen runs inside the firing program itself, where
        a poisoned update rolls back via the select-commit and is
        reclassified as an admission reject at the next :meth:`sync`.
        A norm budget keeps the full host-side check (the budget needs
        the values anyway)."""
        u = np.asarray(u)
        v = np.asarray(v)
        policy = self.config.validation
        if policy.noop_tol > 0.0 and self._noop_gate(u, v):
            return None
        if defer_finite and policy.max_norm is None:
            policy = self._structural_policy
        reason = validate_update(input_name, u, v,
                                 self._input_shapes[input_name], policy)
        if reason is not None:
            self.quarantine.put(input_name, u, v, reason)
            self.stats.quarantined += 1
            return None
        self.stats.admitted += 1
        return u, v

    def admit_batch_stacked(self, input_name: str, updates
                            ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Fast-path batch admission that also *stacks*: returns the
        concatenated ``(P, Q)`` factors ready for one rank-ΣkT firing,
        or ``None`` to send the batch down the careful per-update walk
        (:meth:`admit_batch`).  The concat IS the validation vehicle —
        numpy refuses ragged rows, the stacked dtype exposes any
        non-float32 factor, and one vectorized NaN/Inf reduction over
        ``(P, Q)`` replaces T per-update screens — so the guarded clean
        path stacks once where the unguarded engine would stack anyway,
        instead of concatenating for admission and again for the
        trigger."""
        policy = self.config.validation
        if (policy.max_norm is not None
                or policy.max_update_rank is not None
                or policy.noop_tol > 0.0 or not updates):
            # budgets and the no-op gate need per-update values — the
            # careful walk applies them one update at a time
            return None
        n, m = self._input_shapes[input_name]
        try:
            P = np.concatenate([u for u, _ in updates], axis=1)
            Q = np.concatenate([v for _, v in updates], axis=1)
            # equal stacked ranks can still hide misaligned pairs
            # (u_i, v_i); a mispairing silently changes the delta
            if [u.shape[1] for u, _ in updates] != \
                    [v.shape[1] for _, v in updates]:
                return None
        except Exception:  # noqa: BLE001 — ragged, 1-D, or odd factors
            return None
        if (P.shape[0] != n or Q.shape[0] != m
                or P.shape[1] != Q.shape[1]
                or P.dtype != np.float32 or Q.dtype != np.float32):
            return None
        if policy.check_finite and not (np.isfinite(P).all()
                                        and np.isfinite(Q).all()):
            return None
        self.stats.admitted += len(updates)
        return P, Q

    def _noop_gate(self, u: np.ndarray, v: np.ndarray) -> bool:
        """The no-op gate (runs BEFORE quarantine screening): an update
        whose delta norm bound sits under ``policy.noop_tol`` is a legal
        skip, not a fault — it must never land in quarantine, where an
        operator would read it as an anomaly.  Sound by construction:
        ``‖u‖_F·‖v‖_F ≥ ‖u vᵀ‖_F`` bounds how far ANY maintained view
        can move, and a NaN/Inf norm fails the ``<=`` so poisoned
        updates fall through to the finite screen instead of being
        silently dropped."""
        norm = float(np.linalg.norm(u)) * float(np.linalg.norm(v))
        if norm <= self.config.validation.noop_tol:
            self.stats.noop_skips += 1
            return True
        return False

    def admit_carrier(self, input_name: str, rows, block, v,
                      count: int = 1) -> Optional[Tuple[np.ndarray,
                                                        np.ndarray]]:
        """Admission for a row-local carrier in compact form: the no-op
        gate, then :func:`validate_carrier` — structure, NaN/Inf, and
        the rank/norm budgets, all computed on the ``(r, k)`` block so
        admission cost scales with the rows *touched*.  On reject the
        factors are quarantined widened (dense-shaped ``(P, Q)``) when
        the row structure permits, so :meth:`QuarantineQueue.replay`
        rides the ordinary update path; ``count`` is the logical update
        count a stacked carrier batch represents."""
        rows = np.asarray(rows)
        block = np.asarray(block)
        v = np.asarray(v)
        policy = self.config.validation
        if policy.noop_tol > 0.0 and self._noop_gate(block, v):
            return None
        reason = validate_carrier(input_name, rows, block, v,
                                  self._input_shapes[input_name], policy)
        if reason is not None:
            try:  # widen for replay; malformed rows keep the compact form
                n = self._input_shapes[input_name][0]
                P = np.zeros((n, block.shape[1]), np.float32)
                P[rows.astype(np.int64)] = block
                qu = P
            except Exception:  # noqa: BLE001
                qu = block
            self.quarantine.put(input_name, qu, v, reason)
            self.stats.quarantined += 1
            return None
        self.stats.admitted += count
        return block, v

    def admit_batch(self, input_name: str, updates) -> list:
        """Careful per-update batch admission: full
        :func:`validate_update` on each update, so one poisoned or
        malformed update quarantines alone and the healthy remainder
        still batches.  The engine lands here only when
        :meth:`admit_batch_stacked` refused the fast path — policy
        budgets set, or something in the batch is structurally off or
        non-finite."""
        admitted = [self.admit(input_name, u, v) for u, v in updates]
        return [a for a in admitted if a is not None]

    # -- transactional firing (layer 2) --------------------------------------
    def _fused_trigger(self, engine, input_name: str, bucket: int,
                       screened: bool = False):
        """The clean-path firing program: trigger sweep, NaN/Inf
        validation of every written view, AND the commit/rollback select
        fused into ONE jitted dispatch.  When any written view comes out
        non-finite the program returns the *pre-firing* arrays instead
        (``where(ok, new, old)``), so the store can never go non-finite
        — without any host-side sync on the clean path.  The ``ok``
        scalar stays on device; only the abort *accounting* reads it,
        lazily (:meth:`sync`)."""
        key = (input_name, bucket, screened)
        hit = self._fused.get(key)
        if hit is None:
            # host-screened factors (batch admission) skip the
            # in-program screen: one fewer full pass over (u, v)
            screen_inputs = (self.config.validation.check_finite
                             and not screened)
            # the fused program is pure w.r.t. the views passed in —
            # engine-local state never enters the closure — so it is
            # shared through the engine's trigger cache: same-program
            # tenants in a fleet pay its trace/compile once
            hit = engine._cached_build(
                ("fused", input_name, bucket, screened, screen_inputs),
                lambda: self._build_fused(engine, input_name, bucket,
                                          screen_inputs))
            self._fused[key] = hit
        return hit

    def _build_fused(self, engine, input_name: str, bucket: int,
                     screen_inputs: bool):
        import jax
        import jax.numpy as jnp
        from repro.core.codegen import (firing_name, named,
                                        trigger_touched_views)
        inner = engine._batched_trigger_fn(input_name, bucket)
        trigger = engine._bucket_trigger(input_name, bucket)
        written, read_only = trigger_touched_views(trigger)

        # flat tuples across the jit boundary (the dict-pytree
        # round-trip costs tens of µs per dispatch — same reason
        # build_trigger_fn stages its core this way).  No per-firing
        # flag output either: the threaded [input-rejects,
        # output-aborts] counter both reports aggregate health
        # (sync's single fetch) and, via its per-firing snapshots,
        # identifies WHICH firing failed in the rare abort walk.
        def core(wvals, rvals, u, v, nbad):
            views = dict(zip(written, wvals))
            views.update(zip(read_only, rvals))
            out = inner(views, u, v)
            ok_out = jnp.stack([jnp.isfinite(out[n]).all()
                                for n in written]).all()
            if screen_inputs:  # the admission screen, deferred here
                ok_in = jnp.isfinite(u).all() & jnp.isfinite(v).all()
            else:
                ok_in = jnp.bool_(True)
            ok = ok_in & ok_out
            # select-commit: elementwise where fuses into the
            # trigger's own update loops (lax.cond was measured
            # far slower here — its branch outputs are copied)
            new = tuple(jnp.where(ok, out[n], w)
                        for n, w in zip(written, wvals))
            bad = jnp.stack([~ok_in, ok_in & ~ok_out])
            return new, nbad + bad.astype(jnp.int32)

        core = jax.jit(named(core, firing_name(trigger, "guarded")))

        def fused(views, u, v, nbad):
            new, nbad = core(tuple(views[n] for n in written),
                             tuple(views[n] for n in read_only),
                             u, v, nbad)
            views.update(zip(written, new))
            return views, nbad

        fused.fallbacks = getattr(inner, "fallbacks", {})
        fused.fused_views = getattr(inner, "fused_views", ())
        return (fused, written)

    def fire(self, engine, input_name: str, bucket: int, P, Q,
             screened: bool = False) -> None:
        """Run one trigger firing transactionally: fire → validate
        outputs → commit, or roll back atomically and raise
        :class:`FiringAborted`.  Rollback restores the pre-firing
        arrays, so the store and
        :class:`~repro.core.runtime.EngineStats` come back
        bit-identically.

        Unplanned firings take the fused fast path
        (``engine._guard_fast_path``): the NaN/Inf screens (both the
        deferred admission screen on the factors and the output check)
        and the commit/rollback select all run inside the firing's own
        XLA program, so a bad firing never reaches the store at all and
        the clean path pays no device sync.  The accounting — reject
        and rollback counters, quarantined factors — resolves within a
        sync window (every 32 firings) or on an explicit
        :meth:`sync`."""
        if engine._guard_fast_path:
            if len(self._pending) >= 32:
                self.sync()
            return self._fire_fused(engine, input_name, bucket, P, Q,
                                    screened)
        if not self.config.transactional:
            if engine.chaos is not None:
                engine.chaos.maybe_raise_in_trigger()
            return engine._fire_inner(input_name, bucket, P, Q)
        snap = take_snapshot(engine)
        try:
            if engine.chaos is not None:
                engine.chaos.maybe_raise_in_trigger()
            engine._fire_inner(input_name, bucket, P, Q)
            reason = self.validate_outputs(snap, engine.views)
            if reason is not None:
                raise FiringAborted(reason, input_name, "validate")
        except FiringAborted:
            restore_snapshot(engine, snap)
            self.stats.rollbacks += 1
            raise
        except Exception as e:  # noqa: BLE001 — any kernel error rolls back
            restore_snapshot(engine, snap)
            self.stats.rollbacks += 1
            raise FiringAborted(repr(e), input_name, "execute") from e

    def fire_rowlocal(self, engine, input_name: str, fn, rows, block,
                      v) -> None:
        """Transactional row-slab firing.  Always the snapshot path —
        the fused select-commit program is keyed to dense ``(P, Q)``
        triggers and a row-local firing is already cheap enough that a
        snapshot's O(changed bytes) cost doesn't dominate it."""
        if not self.config.transactional:
            if engine.chaos is not None:
                engine.chaos.maybe_raise_in_trigger()
            engine.views = fn(engine.views, rows, block, v)
            return
        snap = take_snapshot(engine)
        try:
            if engine.chaos is not None:
                engine.chaos.maybe_raise_in_trigger()
            engine.views = fn(engine.views, rows, block, v)
            reason = self.validate_outputs(snap, engine.views)
            if reason is not None:
                raise FiringAborted(reason, input_name, "validate")
        except FiringAborted:
            restore_snapshot(engine, snap)
            self.stats.rollbacks += 1
            raise
        except Exception as e:  # noqa: BLE001 — any kernel error rolls back
            restore_snapshot(engine, snap)
            self.stats.rollbacks += 1
            raise FiringAborted(repr(e), input_name, "execute") from e

    def _fire_fused(self, engine, input_name: str, bucket: int,
                    P, Q, screened: bool = False) -> None:
        fn, written = self._fused_trigger(engine, input_name, bucket,
                                          screened)
        if self._nbad is None:
            import jax.numpy as jnp
            self._nbad = jnp.zeros((2,), jnp.int32)
        try:
            if engine.chaos is not None:
                engine.chaos.maybe_raise_in_trigger()
            out, self._nbad = fn(engine.views, P, Q, self._nbad)
        except FiringAborted:
            self.stats.rollbacks += 1
            raise
        except Exception as e:  # noqa: BLE001
            self.stats.rollbacks += 1
            raise FiringAborted(repr(e), input_name, "execute") from e
        engine.views = out  # safe either way: bad firings self-selected out
        engine._note_firing(fn)
        self._pending.append((self._nbad, input_name, P, Q))

    def sync(self) -> None:
        """Resolve deferred fused-firing outcomes.  The fused program
        threads a cumulative ``[input-rejects, output-aborts]`` count
        through every firing, so the clean case costs ONE fetch per
        sync window regardless of how many firings are pending; only
        when a count moved does the (rare) per-firing walk run — a
        poisoned update is reclassified as an admission reject (exactly
        as the host screen would have recorded it), a firing whose
        *outputs* went non-finite is counted as a rollback, and both
        quarantine the factors the in-program select rolled back."""
        if not self._pending:
            return
        pending, self._pending = self._pending, []
        tail = tuple(int(x) for x in np.asarray(pending[-1][0]))
        if tail == self._nbad_seen:  # every pending firing was clean
            return
        prev_in, prev_out = self._nbad_seen
        self._nbad_seen = tail
        for nbad_after, input_name, P, Q in pending:
            cur_in, cur_out = (int(x) for x in np.asarray(nbad_after))
            if cur_in > prev_in:
                # deferred admission screen fired: the factors were
                # non-finite, the select kept the store untouched
                self.stats.admitted -= 1
                self.stats.quarantined += 1
                self.quarantine.put(
                    input_name, P, Q,
                    f"{input_name}: non-finite entries in update factors")
            elif cur_out > prev_out:
                self.stats.rollbacks += 1
                self.stats.aborted_firings += 1
                self.quarantine.put(
                    input_name, P, Q,
                    f"{input_name}: firing aborted — non-finite output, "
                    f"rolled back in-program")
            prev_in, prev_out = cur_in, cur_out

    # -- post-firing validation (layer 2) ------------------------------------
    def validate_outputs(self, snap: FiringSnapshot, views) -> Optional[str]:
        if not self.config.validation.check_outputs:
            return None
        return check_finite(views, changed_views(snap, views))

    def on_abort(self, input_name: str, P, Q, reason: str) -> None:
        """A firing rolled back: keep its factors for inspection/replay.

        If the factors themselves turn out non-finite (possible only on
        the fused path, where the admission screen is deferred into the
        firing program and an unrelated fault — e.g. an injected trigger
        raise — can abort the firing first), the record is reclassified
        as the admission reject the host screen would have produced."""
        self.stats.aborted_firings += 1
        P = np.asarray(P)
        Q = np.asarray(Q)
        if (self.config.validation.check_finite
                and not (np.isfinite(P).all() and np.isfinite(Q).all())):
            self.stats.admitted -= 1
            self.stats.quarantined += 1
            self.quarantine.put(
                input_name, P, Q,
                f"{input_name}: non-finite entries in update factors")
            return
        self.quarantine.put(input_name, P, Q,
                            f"{input_name}: firing aborted — {reason}")

    # -- post-commit (layer 3) -----------------------------------------------
    def after_firing(self, engine) -> None:
        if self.sentinel is None:
            return
        drifts = self.sentinel.after_firing(engine)
        if drifts is not None:
            self.stats.probes = self.sentinel.probes
            self.stats.drift_recoveries = self.sentinel.recoveries
            self.stats.max_drift = self.sentinel.max_drift

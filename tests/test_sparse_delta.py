"""Sparsity-aware delta carrier tests (row-local containment).

The oracle chain is three engines fed the *same* logical updates:
  row-local carriers (row-slab triggers)  ≡  dense factor pairs
  (rank-k sweeps)  ≡  full re-evaluation — the dense path is the
bit-stable reference the carrier path must agree with to kernel
tolerance, and re-evaluation pins both to the paper's semantics.

Also here: carrier widening at closure boundaries (§4 product-rule
support analysis), the guard's no-op gate soundness bound, fleet replay
bit-identity with mixed-carrier tenants under chaos (REPRO_CHAOS_SEEDS,
comma-separated; default "0" locally, a matrix in CI), the one-time
CPU buffer-donation capability warning, and seeded determinism of the
carrier-native update streams.
"""

import os
import warnings

import pytest

pytest.importorskip("hypothesis",
                    reason="hypothesis is not installed in this container")

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import (IncrementalEngine, LowRankCarrier, NoOpCarrier,
                        Program, ReevalEngine, RowLocalCarrier, as_carrier,
                        compile_program, detect_row_local, dim, matmul,
                        max_abs_diff, stack_carriers, transpose)
from repro.data import RowLocalStream, row_local_stream, zipf_row_stream
from repro.fleet import ADMITTED, FleetConfig, FleetScheduler, TenantSpec
from repro.guard import ChaosConfig, GuardConfig
from repro.guard.validate import ValidationPolicy

from conftest import assert_close

CHAOS_SEEDS = [int(s) for s in
               os.environ.get("REPRO_CHAOS_SEEDS", "0").split(",")]

seeds = st.integers(min_value=0, max_value=2 ** 16)


def _chain_prog(n=64, m=32, k=16):
    """Left chain X·W1·W2 — row-locality of ΔX closes through both
    views (the carrier stays "row_local" end to end)."""
    p = Program(name="chain")
    X = p.input("X", (dim("N"), dim("M")))
    W1 = p.input("W1", (dim("M"), dim("K")))
    W2 = p.input("W2", (dim("K"), dim("K")))
    Y1 = p.let("Y1", matmul(X, W1))
    p.let("Y2", matmul(Y1, W2))
    p.outputs = ["Y1", "Y2"]
    return p.bind_dims(N=n, M=m, K=k)


def _gram_prog(n=48, m=16):
    """Gram matrix XᵀX — the transpose breaks row-support preservation,
    so a row-local ΔX must widen at this view."""
    p = Program(name="gram")
    X = p.input("X", (dim("N"), dim("M")))
    p.let("G", matmul(transpose(X), X))
    p.outputs = ["G"]
    return p.bind_dims(N=n, M=m)


def _chain_inputs(seed, n=64, m=32, k=16):
    rng = np.random.default_rng(seed)
    return {"X": rng.standard_normal((n, m)).astype(np.float32),
            "W1": rng.standard_normal((m, k)).astype(np.float32),
            "W2": rng.standard_normal((k, k)).astype(np.float32)}


# ---------------------------------------------------------------------------
# P1: row-local ≡ dense ≡ re-evaluation under ragged carrier streams
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(seed=seeds,
       steps=st.integers(min_value=1, max_value=4),
       rank=st.integers(min_value=1, max_value=3),
       rows_touched=st.integers(min_value=1, max_value=6))
def test_rowlocal_equals_dense_equals_reeval(seed, steps, rank,
                                             rows_touched):
    prog = _chain_prog()
    inputs = _chain_inputs(seed)
    carrier_eng = IncrementalEngine(prog, {"X": rank})
    dense_eng = IncrementalEngine(prog, {"X": rank})
    ree = ReevalEngine(prog)
    for e in (carrier_eng, dense_eng, ree):
        e.initialize(inputs)
    stream = row_local_stream(64, rows_touched, m=32, rank=rank, seed=seed)
    for _ in range(steps):
        c = stream.next_carrier()
        carrier_eng.apply_update("X", c)
        P, Q = c.factors()
        dense_eng.apply_update("X", P, Q)
        ree.apply_update("X", P, Q)
    for name in ("Y1", "Y2"):
        assert_close(carrier_eng.views[name], dense_eng.views[name],
                     msg=f"carrier vs dense on {name}")
        assert_close(carrier_eng.views[name], ree.views[name],
                     msg=f"carrier vs reeval on {name}")
    # the carrier path actually exercised the row-slab triggers
    assert carrier_eng.stats.rowlocal_firings == steps
    assert carrier_eng.stats.widened_carriers == 0


@settings(max_examples=10, deadline=None)
@given(seed=seeds, batches=st.integers(min_value=1, max_value=3))
def test_ragged_mixed_carrier_batches_match_dense(seed, batches):
    """Ragged batches mixing row-local / low-rank / no-op / raw pairs
    through apply_updates agree with the dense batch path."""
    prog = _chain_prog()
    inputs = _chain_inputs(seed)
    a = IncrementalEngine(prog, {"X": 2})
    b = IncrementalEngine(prog, {"X": 2})
    a.initialize(inputs)
    b.initialize(inputs)
    rng = np.random.default_rng(seed + 1)
    stream = row_local_stream(64, 3, m=32, rank=2, seed=seed)
    for _ in range(batches):
        rl = stream.next_carrier()
        P = (0.1 * rng.standard_normal((64, 2))).astype(np.float32)
        Q = (0.1 * rng.standard_normal((32, 2))).astype(np.float32)
        u = (0.1 * rng.standard_normal((64, 1))).astype(np.float32)
        v = (0.1 * rng.standard_normal((32, 1))).astype(np.float32)
        mixed = [rl, LowRankCarrier(P, Q), NoOpCarrier(64, 32), (u, v)]
        a.apply_updates("X", mixed)
        dense = [rl.factors(), (P, Q), (u, v)]   # noop contributes nothing
        b.apply_updates("X", dense)
    assert a.stats.noop_skips == batches
    for name in ("Y1", "Y2"):
        assert_close(a.views[name], b.views[name], msg=name)


def test_pure_rowlocal_batch_fires_row_slab_once():
    prog = _chain_prog()
    eng = IncrementalEngine(prog, {"X": 2})
    eng.initialize(_chain_inputs(3))
    ree = ReevalEngine(prog)
    ree.initialize(_chain_inputs(3))
    stream = row_local_stream(64, 2, m=32, rank=2, seed=5)
    cs = [stream.next_carrier() for _ in range(4)]
    eng.apply_updates("X", cs)
    for c in cs:
        ree.apply_update("X", *c.factors())
    assert eng.stats.rowlocal_firings == 1      # one stacked firing
    assert eng.stats.updates_applied == 4       # four logical updates
    for name in ("Y1", "Y2"):
        assert_close(eng.views[name], ree.views[name], msg=name)


# ---------------------------------------------------------------------------
# carrier widening at closure boundaries
# ---------------------------------------------------------------------------

def test_compiler_carrier_kinds_chain_vs_gram():
    chain = compile_program(_chain_prog())
    kinds = chain.triggers["X"].carriers
    assert kinds["Y1"] == "row_local" and kinds["Y2"] == "row_local"
    gram = compile_program(_gram_prog())
    assert gram.triggers["X"].carriers["G"] != "row_local"


def test_rowlocal_carrier_widens_at_gram_and_stays_exact():
    prog = _gram_prog()
    rng = np.random.default_rng(0)
    X0 = rng.standard_normal((48, 16)).astype(np.float32)
    eng = IncrementalEngine(prog, {"X": 2})
    eng.initialize({"X": X0})
    ree = ReevalEngine(prog)
    ree.initialize({"X": X0})
    c = row_local_stream(48, 3, m=16, rank=2, seed=1).next_carrier()
    eng.apply_update("X", c)
    ree.apply_update("X", *c.factors())
    assert eng.stats.widened_carriers == 1      # closure boundary hit
    assert eng.stats.rowlocal_firings == 0
    assert_close(eng.views["G"], ree.views["G"])


@settings(max_examples=15, deadline=None)
@given(seed=seeds, r=st.integers(min_value=1, max_value=8))
def test_detect_and_stack_preserve_dense_equivalence(seed, r):
    rng = np.random.default_rng(seed)
    n, m = 32, 12
    rows = np.sort(rng.choice(n, size=r, replace=False)).astype(np.int32)
    u = np.zeros((n, 2), dtype=np.float32)
    u[rows] = rng.standard_normal((r, 2)).astype(np.float32)
    v = rng.standard_normal((m, 2)).astype(np.float32)
    c = detect_row_local(u, v)
    assert c.kind == "row_local" and np.array_equal(np.sort(rows), c.rows)
    P, Q = c.factors()
    assert_close(P @ Q.T, u @ v.T)
    # stacking two contained carriers keeps the union support compact
    c2 = row_local_stream(n, 2, m=m, rank=1, seed=seed).next_carrier()
    s = stack_carriers([c, c2])
    assert s.kind == "row_local"
    P1, Q1 = c2.factors()
    Ps, Qs = s.factors()
    assert_close(Ps @ Qs.T, u @ v.T + P1 @ Q1.T)
    # a dense member forces the stack to widen — correctly
    d = as_carrier((0.1 * rng.standard_normal((n, 1))).astype(np.float32),
                   (0.1 * rng.standard_normal((m, 1))).astype(np.float32))
    w = stack_carriers([c, d])
    assert w.kind != "row_local"
    Pw, Qw = w.factors()
    Pd, Qd = d.factors()
    assert_close(Pw @ Qw.T, u @ v.T + Pd @ Qd.T)


# ---------------------------------------------------------------------------
# guard no-op gate: soundness (never skips a real delta)
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(seed=seeds, scale=st.floats(min_value=1e-9, max_value=1e-2))
def test_noop_gate_never_skips_above_tolerance(seed, scale):
    """The gate skips on the bound ‖u‖·‖v‖ ≥ ‖uvᵀ‖_F, so every skipped
    update's *true* delta is ≤ noop_tol — and every non-skipped update
    must land in the views."""
    tol = 1e-4
    prog = _chain_prog()
    eng = IncrementalEngine(
        prog, {"X": 1},
        guard=GuardConfig(validation=ValidationPolicy(noop_tol=tol)))
    eng.initialize(_chain_inputs(seed))
    rng = np.random.default_rng(seed)
    u = (scale * rng.standard_normal((64, 1))).astype(np.float32)
    v = (scale * rng.standard_normal((32, 1))).astype(np.float32)
    before = {k: np.asarray(val) for k, val in eng.views.items()}
    skips0 = eng.guard.stats.noop_skips
    eng.apply_update("X", u, v)
    if eng.guard.stats.noop_skips > skips0:
        # soundness: the skipped delta could not have moved any view
        # past tol (linear views contract through bounded factors here,
        # but the raw-input bound is the one the gate promises)
        assert float(np.linalg.norm(u @ v.T)) <= tol
        assert max_abs_diff(eng.views, before) == 0.0
    else:
        assert np.asarray(eng.views["X"]) is not before["X"]


def test_noop_gate_on_rowlocal_carrier_and_nan_falls_through():
    tol = 1e-6
    prog = _chain_prog()
    eng = IncrementalEngine(
        prog, {"X": 2},
        guard=GuardConfig(validation=ValidationPolicy(noop_tol=tol)))
    eng.initialize(_chain_inputs(0))
    tiny = row_local_stream(64, 2, m=32, rank=2, scale=1e-8,
                            seed=0).next_carrier()
    before = {k: np.asarray(v) for k, v in eng.views.items()}
    eng.apply_update("X", tiny)
    assert eng.guard.stats.noop_skips == 1
    assert eng.guard.stats.quarantined == 0      # a no-op is not a fault
    assert max_abs_diff(eng.views, before) == 0.0
    # NaN norms fail the ≤ comparison: a poisoned tiny update is
    # quarantined, never silently skipped
    bad = row_local_stream(64, 2, m=32, rank=2, scale=1e-8,
                           seed=1).next_carrier()
    bad.block[0, 0] = np.nan
    eng.apply_update("X", bad)
    assert eng.guard.stats.noop_skips == 1       # unchanged
    assert eng.guard.stats.quarantined == 1
    assert max_abs_diff(eng.views, before) == 0.0


def test_noop_stream_skips_every_declared_noop():
    """A stream that is 95% declared no-ops, one update per call: every
    NoOpCarrier is skipped and counted, only the live carriers fire, and
    the views equal a dense engine fed the no-ops as zero factor pairs
    (which it must fire)."""
    prog = _chain_prog()
    carrier_eng = IncrementalEngine(prog, {"X": 1})
    dense_eng = IncrementalEngine(prog, {"X": 1})
    for e in (carrier_eng, dense_eng):
        e.initialize(_chain_inputs(2))
    live = row_local_stream(64, 4, m=32, rank=1, seed=2)
    zero_u = np.zeros((64, 1), np.float32)
    zero_v = np.zeros((32, 1), np.float32)
    total, live_every = 40, 20
    for i in range(total):
        if i % live_every == 0:
            c = live.next_carrier()
            carrier_eng.apply_update("X", c)
            dense_eng.apply_update("X", *c.factors())
        else:
            carrier_eng.apply_update("X", NoOpCarrier(64, 32))
            dense_eng.apply_update("X", zero_u, zero_v)
    declared = total - total // live_every
    assert carrier_eng.stats.noop_skips == declared
    assert carrier_eng.stats.updates_applied == total
    assert carrier_eng.stats.triggers_fired == total // live_every
    assert dense_eng.stats.triggers_fired == total
    for name in ("Y1", "Y2"):
        assert_close(carrier_eng.views[name], dense_eng.views[name],
                     msg=name)


# ---------------------------------------------------------------------------
# fleet: mixed-carrier tenants, replay bit-identity under chaos
# ---------------------------------------------------------------------------

def _replay_reference(tenant, inputs, payload_by_lsn):
    ref = IncrementalEngine(tenant.spec.program, tenant.spec.update_ranks,
                            guard=tenant.spec.guarded or None)
    ref.initialize(inputs)
    for input_name, lsns in tenant.commit_log:
        assert input_name != "<reeval>", "property test must not degrade"
        ref.apply_updates(input_name, [payload_by_lsn[l] for l in lsns])
    return ref


@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_fleet_mixed_carrier_replay_bit_identical(seed):
    """Tenants fed an interleaved mix of row-local carriers, low-rank
    carriers, raw pairs, and no-ops, under worker crashes + lease
    expiry + poison: committed stores are bit-identical to isolated
    engines replaying each tenant's committed groups (the logged —
    post-poisoning — payloads, in the same representation)."""
    import time as _time

    class VClock:
        def __init__(self):
            self.t = 0.0

        def __call__(self):
            return self.t

        def sleep(self, dt):
            self.t += dt

    vc = VClock()
    fleet = FleetScheduler(
        FleetConfig(lease_ttl=1.0,
                    chaos=ChaosConfig(seed=seed, worker_crash_p=0.15,
                                      lease_expiry_p=0.15, poison_p=0.05)),
        clock=vc, sleep=vc.sleep)
    tenant_inputs = {}
    for i in range(2):
        tid = f"t{i}"
        tenant_inputs[tid] = _chain_inputs(seed + i)
        fleet.add_tenant(
            TenantSpec(tid, _chain_prog(), {"X": 2}, max_claim_rank=6),
            tenant_inputs[tid])
    rng = np.random.default_rng(seed + 9)
    streams = {tid: row_local_stream(64, 3, m=32, rank=2,
                                     seed=seed + 50 + i)
               for i, tid in enumerate(sorted(tenant_inputs))}
    by_lsn = {tid: {} for tid in tenant_inputs}
    admitted = {tid: 0 for tid in tenant_inputs}
    noops = 0
    for step in range(60):
        tid = f"t{rng.integers(2)}"
        kind = int(rng.integers(4))
        if kind == 0:
            sub = (streams[tid].next_carrier(),)
        elif kind == 1:
            sub = (LowRankCarrier(
                (0.1 * rng.standard_normal((64, 2))).astype(np.float32),
                (0.1 * rng.standard_normal((32, 2))).astype(np.float32)),)
        elif kind == 2:
            sub = ((0.1 * rng.standard_normal((64, 1))).astype(np.float32),
                   (0.1 * rng.standard_normal((32, 1))).astype(np.float32))
        else:
            sub = (NoOpCarrier(64, 32),)
            noops += 1
        assert fleet.submit(tid, "X", *sub) == ADMITTED
        tenant = fleet.registry.get(tid)
        if len(sub) == 1 and sub[0].kind == "noop":
            continue                    # acked, never logged
        admitted[tid] += 1
        entry = tenant.log.pending(0)[-1]
        by_lsn[tid][entry.lsn] = entry.payload()   # post-poisoning
        vc.sleep(0.01)
        if step % 15 == 14:
            fleet.run_until_idle(workers=2,
                                 on_stall=lambda: vc.sleep(1.1))
    fleet.run_until_idle(workers=2, on_stall=lambda: vc.sleep(1.1))
    total_noop_skips = 0
    for tid in sorted(tenant_inputs):
        tenant = fleet.registry.get(tid)
        assert not tenant.dirty()
        assert tenant.stats.committed_updates == admitted[tid]
        total_noop_skips += tenant.stats.noop_skips
        ref = _replay_reference(tenant, tenant_inputs[tid], by_lsn[tid])
        assert max_abs_diff(tenant.committed_views, ref.views) == 0.0, tid
        # every committed view stayed finite despite the poison stream
        for val in tenant.committed_views.values():
            assert np.isfinite(np.asarray(val)).all()
    assert total_noop_skips == noops
    assert fleet.chaos.worker_crashes + fleet.chaos.lease_expiries > 0


# ---------------------------------------------------------------------------
# codegen: one-time CPU donation capability warning
# ---------------------------------------------------------------------------

def test_donation_warning_fires_exactly_once():
    import jax

    from repro.core import codegen
    from repro.core.codegen import build_trigger_fn

    if jax.default_backend() != "cpu":
        pytest.skip("capability warning is CPU-only")
    compiled = compile_program(_chain_prog())
    trig = compiled.triggers["X"]
    old = codegen._donation_warned
    try:
        codegen._donation_warned = False
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            build_trigger_fn(trig, compiled.program, donate=True)
            build_trigger_fn(trig, compiled.program, donate=True)
        donation = [w for w in caught
                    if issubclass(w.category, RuntimeWarning)
                    and "donation" in str(w.message)]
        assert len(donation) == 1, "warning must fire exactly once"
        assert "CPU" in str(donation[0].message)
        # donate=False never warns
        codegen._donation_warned = False
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            build_trigger_fn(trig, compiled.program, donate=False)
        assert not [w for w in caught
                    if "donation" in str(w.message)]
    finally:
        codegen._donation_warned = old


# ---------------------------------------------------------------------------
# data: carrier-native streams are seeded-deterministic
# ---------------------------------------------------------------------------

def test_row_local_stream_seeded_determinism():
    mk = lambda: row_local_stream(128, 4, m=32, rank=2, seed=7)
    s1, s2 = mk(), mk()
    draws1 = [s1.next_carrier() for _ in range(6)]
    draws2 = [s2.next_carrier() for _ in range(6)]
    for a, b in zip(draws1, draws2):
        assert np.array_equal(a.rows, b.rows)
        assert np.array_equal(a.block, b.block)
        assert np.array_equal(a.V, b.V)
    # draws advance shared state (no silent per-call re-seeding) …
    assert not np.array_equal(draws1[0].block, draws1[1].block)
    # … and reset() replays from the seed
    s1.reset()
    c = s1.next_carrier()
    assert np.array_equal(c.rows, draws1[0].rows)
    assert np.array_equal(c.block, draws1[0].block)


def test_zipf_row_stream_carrier_native():
    z = zipf_row_stream(128, 32, 1.5, seed=3, rows_touched=6)
    assert isinstance(z, RowLocalStream)
    c = z.next_carrier()
    assert c.kind == "row_local"
    assert np.all(np.diff(c.rows) > 0)          # sorted, deduped
    assert 1 <= len(c.rows) <= 6                # skew may collapse rows
    assert c.n == 128 and c.V.shape[0] == 32
    # legacy form unchanged without rows_touched
    legacy = zipf_row_stream(128, 32, 1.5, seed=3)
    u, v = legacy.next_update()
    assert u.shape == (128, 1) and v.shape == (32, 1)


def test_stream_batch_is_dense_equivalent():
    s = row_local_stream(64, 3, m=16, rank=1, seed=11)
    probe = row_local_stream(64, 3, m=16, rank=1, seed=11)
    stacked = s.batch(5)
    dense = np.zeros((64, 16), dtype=np.float64)
    for _ in range(5):
        c = probe.next_carrier()
        P, Q = c.factors()
        dense += (P @ Q.T).astype(np.float64)
    Ps, Qs = stacked.factors()
    assert_close(Ps @ Qs.T, dense)


# ---------------------------------------------------------------------------
# P9: compact-chain analysis and the compact row-slab firing
# ---------------------------------------------------------------------------

def test_compact_chain_names_chain_vs_gram():
    from repro.core.codegen import compact_chain_names
    chain = compile_program(_chain_prog()).triggers["X"]
    names = compact_chain_names(chain)
    # every left factor in the chain stays compact (dU_X and the
    # per-view left blocks that alias it)
    assert names is not None and chain.u_var.name in names
    gram = compile_program(_gram_prog()).triggers["X"]
    # ΔG references ΔXᵀ — the chain cannot run compactly
    assert compact_chain_names(gram) is None


def test_inplace_apply_matches_staged_and_mutates_in_place():
    """Contained carriers on a compact chain fire the staged row-slab
    program and agree with the same deltas widened to dense factors,
    before and after a dense firing on the same engine."""
    n, m, k = 96, 24, 12
    inputs = _chain_inputs(5, n, m, k)
    carrier_eng = IncrementalEngine(_chain_prog(n, m, k), {"X": 2})
    dense_eng = IncrementalEngine(_chain_prog(n, m, k), {"X": 2})
    carrier_eng.initialize(inputs)
    dense_eng.initialize(inputs)
    s = row_local_stream(n, 3, m=m, rank=2, scale=0.1, seed=7)
    for _ in range(6):
        c = s.next_carrier()
        carrier_eng.apply_update("X", c)
        dense_eng.apply_update("X", *c.factors())
    assert carrier_eng.stats.rowlocal_firings == 6
    assert carrier_eng.stats.widened_carriers == 0
    assert dense_eng.stats.rowlocal_firings == 0
    for name in ("X", "Y1", "Y2"):
        assert_close(np.asarray(carrier_eng.views[name]),
                     np.asarray(dense_eng.views[name]), atol=1e-4)
    # a dense firing after the row-slab firings agrees too
    rng = np.random.default_rng(8)
    u = (0.1 * rng.standard_normal((n, 2))).astype(np.float32)
    v = (0.1 * rng.standard_normal((m, 2))).astype(np.float32)
    carrier_eng.apply_update("X", u, v)
    dense_eng.apply_update("X", u, v)
    assert carrier_eng.stats.rowlocal_firings == 6
    assert_close(np.asarray(carrier_eng.views["Y2"]),
                 np.asarray(dense_eng.views["Y2"]), atol=1e-4)


def test_guarded_engine_keeps_staged_rowlocal_path():
    n, m, k = 96, 24, 12
    inputs = _chain_inputs(6, n, m, k)
    eng = IncrementalEngine(_chain_prog(n, m, k), {"X": 1}, guard=True)
    eng.initialize(inputs)
    s = row_local_stream(n, 3, m=m, rank=1, scale=0.1, seed=9)
    for _ in range(3):
        eng.apply_update("X", s.next_carrier())
    assert eng.stats.rowlocal_firings == 3
    # the transactional guard needs copy-on-write firings: views must
    # never be switched to mutable in-place storage
    assert not isinstance(eng.views["Y2"], np.ndarray)


def test_contained_high_rank_batch_prices_at_scaled_rank():
    """A stacked contained batch whose rank crosses the §7 crossover
    must NOT be kicked to re-evaluation at the full-rank price: a
    row-slab sweep touches r·m elements, so the decision is priced at
    ceil(rank·frac) (the planner's K*/frac scaling, engine-side)."""
    n, m, k = 2048, 96, 64
    inputs = _chain_inputs(7, n, m, k)
    eng = IncrementalEngine(_chain_prog(n, m, k), {"X": 1},
                            flush_policy="cost")
    eng.initialize(inputs)
    ref = IncrementalEngine(_chain_prog(n, m, k), {"X": 1})
    ref.initialize(inputs)
    s = row_local_stream(n, 2, m=m, rank=1, scale=0.05, seed=3)
    batch = [s.next_carrier() for _ in range(96)]
    stacked = stack_carriers(batch)
    # the full-rank price would re-evaluate Y2 (rank 96 >= K* = 64)...
    assert eng._plan_decision("X", stacked.rank) != (frozenset(),
                                                     frozenset())
    # ...but the contained batch still fires the row-slab path
    assert eng._rowlocal_ok("X", stacked)
    eng.apply_updates("X", batch)
    assert eng.stats.rowlocal_firings == 1
    assert eng.stats.widened_carriers == 0
    ref.apply_updates("X", [c.factors() for c in batch])
    assert_close(np.asarray(eng.views["Y2"]), np.asarray(ref.views["Y2"]),
                 atol=5e-3)

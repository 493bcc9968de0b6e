"""Higher-order delta views (delta-of-delta, ISSUE 8): the property +
regression suite across every execution path.

Covers, per ISSUE 8's satellite checklist:
  * depth-1/2/3 engines stay exact against re-evaluation for every
    ``apps/`` program family under hypothesis-generated random update
    streams (ragged batches, mixed ranks), on the REPRO_CHAOS_SEEDS
    matrix;
  * the symbolic Δᵏ hierarchy: auxiliary-view registration, degree
    termination (Δ^{d+1} ≡ 0), the materialized Δ² trigger against the
    numeric second difference, and the inverse (Woodbury) unsupported
    path;
  * the TriggerCache order-collision fix (namespace + depth-keyed delta
    tails) with a concurrent regression test;
  * planner depth pricing (``WorkloadDescriptor.max_order``), the
    ``AdaptivePlanner`` reads-per-firing fit, plan-driven engine depth
    adoption, and the fleet scheduler's amortized pricing.

Tolerances: the ISSUE's "within 1e-6" target is met scale-normalized
(max |inc − ref| / max |ref|) for every polynomial family — the engines
run float32, so the absolute bound only holds relative to the views'
magnitude.  The OLS family goes through a float32 Woodbury inverse and
uses the repo-standard 2e-3 (same bound the first-order suites apply).
"""

import os
import threading
from dataclasses import replace

import numpy as np
import pytest

import jax.numpy as jnp

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.apps import (build_bgd_program, build_general_program,
                        build_ols_program, build_pagerank_program,
                        build_powers_program, build_sums_program)
from repro.core import (IncrementalEngine, IncrementalInverseError,
                        ReevalEngine, compile_delta_trigger, compile_program,
                        delta_view_name, max_abs_diff)
from repro.plan import (AdaptivePlanner, TriggerCache, ViewPlan,
                        WorkloadDescriptor, firing_cost_flops, plan_program)

CHAOS_SEEDS = [int(s) for s in
               os.environ.get("REPRO_CHAOS_SEEDS", "0").split(",")]

# family → (program builder, updatable inputs, per-input init scale,
#           scale-normalized tolerance)
FAMILIES = {
    "powers_exp": (lambda: build_powers_program(4, 12), ("A",),
                   {"A": 0.25}, 1e-6),
    "sums_powers": (lambda: build_sums_program(4, 10), ("A",),
                    {"A": 0.25}, 1e-6),
    "general_form": (lambda: build_general_program(4, 10, 6), ("A", "B"),
                     {"A": 0.25, "B": 0.3, "T0": 0.3}, 1e-6),
    "pagerank": (lambda: build_pagerank_program(10, k=4), ("M",),
                 {"M": 0.15}, 1e-6),
    "bgd": (lambda: build_bgd_program(16, 6, 1, k=4), ("X",),
            {"X": 0.5, "Y": 1.0, "Theta0": 0.1}, 1e-6),
    "ols": (lambda: build_ols_program(24, 6, 1), ("X",),
            {"X": 1.0, "Y": 1.0}, 2e-3),
}


def _gen_inputs(prog, rng, scales):
    from repro.core.cost import shape_of
    out = {}
    for name, v in prog.inputs.items():
        n, m = shape_of(v, dict(prog.dims))
        out[name] = (rng.standard_normal((n, m))
                     * scales.get(name, 0.3)).astype(np.float32)
    return out


def _ragged_stream(rng, shape, T):
    """T mixed-rank factored updates for one (n, m) input."""
    n, m = shape
    ups = []
    for _ in range(T):
        k = int(rng.integers(1, 3))
        ups.append(((rng.standard_normal((n, k)) * 0.02).astype(np.float32),
                    (rng.standard_normal((m, k)) * 0.02).astype(np.float32)))
    return ups


def _assert_views_match(eng, ref, tol, label=""):
    for stmt in eng.program.statements:
        name = stmt.target.name
        want = np.asarray(ref.views[name], np.float64)
        got = np.asarray(eng.views[name], np.float64)
        nrm = max(np.abs(want).max(), 1.0)
        diff = np.abs(got - want).max() / nrm
        assert diff <= tol, f"{label}{name}: {diff:.3e} > {tol}"


# ---------------------------------------------------------------------------
# the property suite: every app family × depth 1/2/3 × chaos-seed matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", CHAOS_SEEDS)
@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("family", sorted(FAMILIES))
@settings(max_examples=2, deadline=None)
@given(case=st.integers(min_value=0, max_value=2 ** 16),
       fold_window=st.sampled_from([2, 3]))
def test_depth_k_views_match_reevaluation(family, depth, seed, case,
                                          fold_window):
    build, upd_inputs, scales, tol = FAMILIES[family]
    prog = build()
    rng = np.random.default_rng((seed << 20) ^ case)
    inputs = _gen_inputs(prog, rng, scales)
    eng = IncrementalEngine(prog, order=depth, fold_window=fold_window)
    ref = ReevalEngine(prog)
    eng.initialize(inputs)
    ref.initialize(inputs)
    if depth >= 2:
        assert eng._deferred, "depth ≥ 2 must defer some view"
    shapes = {n: np.asarray(a).shape for n, a in inputs.items()}
    for _ in range(7):
        name = upd_inputs[int(rng.integers(len(upd_inputs)))]
        ups = _ragged_stream(rng, shapes[name], T=int(rng.integers(1, 4)))
        eng.apply_updates(name, ups)
        for u, v in ups:
            ref.apply_update(name, u, v)
    eng.flush()  # the read barrier: folds every pending window
    assert not eng._cascade_pending()
    _assert_views_match(eng, ref, tol, label=f"{family}@d{depth}: ")
    if depth >= 2:
        assert eng.stats.folds > 0


def test_reads_interleaved_with_stream_stay_exact():
    """output() mid-stream forces a fold of every tier and keeps serving
    exact values — the w_eff = min(w, 1/rho) story, numerically."""
    prog = build_sums_program(4, 10)
    rng = np.random.default_rng(3)
    inputs = _gen_inputs(prog, rng, {"A": 0.25})
    eng = IncrementalEngine(prog, order=3, fold_window=3)
    ref = ReevalEngine(prog)
    eng.initialize(inputs)
    ref.initialize(inputs)
    out = prog.output_names()[0]
    for i in range(10):
        ups = _ragged_stream(rng, (10, 10), T=1)
        eng.apply_updates("A", ups)
        ref.apply_update("A", *ups[0])
        if i % 4 == 1:  # read mid-window
            got = np.asarray(eng.output(out), np.float64)
            want = np.asarray(ref.views[out], np.float64)
            nrm = max(np.abs(want).max(), 1.0)
            assert np.abs(got - want).max() / nrm <= 1e-6
    assert eng.stats.reads >= 2


# ---------------------------------------------------------------------------
# the symbolic Δᵏ hierarchy (compiler layer)
# ---------------------------------------------------------------------------


def test_delta_view_registration_and_names():
    prog = build_general_program(4, 10, 6)
    c = compile_program(prog, order=2)
    assert c.order == 2
    assert delta_view_name("P2", 2) == "__d2__P2"
    reg = c.delta_views[("A", 2)]
    assert reg, "Δ² of the A-chain must register auxiliary views"
    for name, dv in reg.items():
        assert dv.view == name
        assert dv.name == delta_view_name(name, 2)
        assert dv.depth == 2 and dv.input_name == "A"
        assert dv.kind in ("lowrank", "dense")
        assert dv.flops >= 0.0
    # first-order compiles carry no hierarchy (regression pin)
    c1 = compile_program(prog)
    assert c1.order == 1 and not c1.delta_views


def test_delta_hierarchy_terminates_at_degree():
    """DBToaster termination: Δ^(d+1) ≡ 0 for a degree-d polynomial.
    matrix_powers k=4 is degree 4: depth 4 is the last non-zero level."""
    c = compile_program(build_powers_program(4, 8), order=5)
    assert c.delta_views[("A", 2)]
    assert c.delta_views[("A", 4)]
    assert not c.delta_views.get(("A", 5))


def test_inverse_unsupported_at_depth_two():
    c = compile_program(build_ols_program(20, 6, 1), order=2)
    # Z = XᵀX is quadratic: its Δ² exists; W = Z⁻¹ and beta do not
    assert "Z" in c.delta_views[("X", 2)]
    assert set(c.delta_unsupported[("X", 2)]) == {"W", "beta"}
    with pytest.raises(IncrementalInverseError):
        compile_delta_trigger(c, "X", 2)


def test_delta2_trigger_matches_second_difference(rng):
    """The materialized Δ² trigger against the numeric second
    difference: Δ²E(A; d, d) = E(A+2d) − 2E(A+d) + E(A)."""
    prog = build_powers_program(2, 8)  # single statement P2 = A·A
    A = (rng.standard_normal((8, 8)) * 0.3).astype(np.float32)
    eng = IncrementalEngine(prog, order=2)
    eng.initialize({"A": A})
    names = eng.materialize_delta_views("A", 2)
    assert names == ("__d2__P2",)
    fn = eng.delta_trigger_fn("A", 2)
    u = (rng.standard_normal((8, 1)) * 0.2).astype(np.float32)
    v = (rng.standard_normal((8, 1)) * 0.2).astype(np.float32)
    out = fn(dict(eng.views), u, v)
    d = u @ v.T

    def E(a):
        return a @ a

    expected = E(A + 2 * d) - 2 * E(A + d) + E(A)  # == 2·d·d
    np.testing.assert_allclose(np.asarray(out["__d2__P2"]), expected,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(expected, 2 * d @ d, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# the TriggerCache collision fix (satellite 4)
# ---------------------------------------------------------------------------


def test_trigger_cache_namespace_carries_order():
    """Regression: the shared-cache key used to omit the delta order, so
    an order-2 engine's deferred-lazy planned trigger could be served to
    a first-order engine of the same program (and vice versa)."""
    prog = build_powers_program(4, 12)
    cache = TriggerCache(capacity=64)
    e1 = IncrementalEngine(prog, trigger_cache=cache)
    e2 = IncrementalEngine(prog, order=2, fold_window=2,
                           trigger_cache=cache)
    tail = ("batched", "A", 1)
    assert e1._cache_key(tail) != e2._cache_key(tail)
    # depth-keyed delta tails are distinct per depth and memoized
    e3 = IncrementalEngine(prog, order=3, fold_window=2,
                           trigger_cache=cache)
    rng = np.random.default_rng(0)
    A = (rng.standard_normal((12, 12)) * 0.25).astype(np.float32)
    e3.initialize({"A": A})
    f2 = e3.delta_trigger_fn("A", 2)
    f3 = e3.delta_trigger_fn("A", 3)
    assert f2 is not f3
    assert e3.delta_trigger_fn("A", 2) is f2


def test_trigger_cache_concurrent_cross_order_engines():
    """Two same-program engines at different orders share one cache and
    are driven concurrently with identical streams; each must end
    bit-identical to an isolated engine of its own order — a colliding
    key would hand one engine the other's compiled trigger."""
    prog = build_sums_program(4, 10)
    rng = np.random.default_rng(7)
    inputs = _gen_inputs(prog, rng, {"A": 0.25})
    stream = [_ragged_stream(rng, (10, 10), T=2) for _ in range(6)]
    cache = TriggerCache(capacity=64)
    orders = [None, 2]
    shared = [IncrementalEngine(prog, order=o, fold_window=2,
                                trigger_cache=cache) for o in orders]
    isolated = [IncrementalEngine(prog, order=o, fold_window=2)
                for o in orders]
    for e in shared + isolated:
        e.initialize(inputs)
    errors = []

    def drive(eng):
        try:
            for ups in stream:
                eng.apply_updates("A", ups)
            eng.flush()
        except Exception as exc:  # pragma: no cover
            errors.append(exc)

    threads = [threading.Thread(target=drive, args=(e,)) for e in shared]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    for e in isolated:
        drive(e)
    for e_shared, e_iso in zip(shared, isolated):
        assert max_abs_diff(e_shared.views, e_iso.views) == 0.0
    assert cache.stats()["entries"] >= 2  # one namespace per order


# ---------------------------------------------------------------------------
# planner depth pricing + the adaptive reads-per-firing fit
# ---------------------------------------------------------------------------


def _past_crossover_setting():
    """A cell where first-order maintenance loses to re-evaluation:
    n=12 views with stacked update rank 16 > n (the §7 crossover)."""
    prog = build_powers_program(4, 12)
    wl = WorkloadDescriptor(update_rank=4, batch_size=4,
                            rank_lo=8, rank_hi=24)
    return prog, wl


def test_viewplan_order_validation():
    with pytest.raises(ValueError):
        ViewPlan(view="V", strategy="incremental", order=0)


def test_plan_program_prices_depth_only_when_it_pays():
    prog, wl = _past_crossover_setting()
    compiled = compile_program(prog)
    # dense reads (the default rho=1.0): a read folds every window, so
    # depth never amortizes and the plan must stay first-order
    base = plan_program(compiled, replace(wl, max_order=3))
    assert all(vp.order == 1 for vp in base.views.values())
    # sparse reads past the crossover: depth ≥ 2 wins ≥ 2×
    deep = plan_program(compiled, replace(wl, max_order=3, fold_window=8,
                                          reads_per_firing=0.02))
    orders = {n: vp.order for n, vp in deep.views.items()}
    assert any(o >= 2 for o in orders.values()), orders
    assert all(vp.materialize for vp in deep.views.values())
    # max_order=1 is inert regardless of read sparsity (regression pin)
    flat = plan_program(compiled, replace(wl, reads_per_firing=0.02))
    assert all(vp.order == 1 for vp in flat.views.values())


def test_plan_depth_respects_producer_consumer_monotonicity():
    prog, wl = _past_crossover_setting()
    deep = plan_program(compile_program(prog),
                        replace(wl, max_order=3, fold_window=8,
                                reads_per_firing=0.02))
    orders = {n: vp.order for n, vp in deep.views.items()}
    consumers = {}
    names = set(orders)
    for stmt in prog.statements:
        for dep in stmt.expr.free_vars():
            if dep in names and dep != stmt.target.name:
                consumers.setdefault(dep, []).append(stmt.target.name)
    for name, cs in consumers.items():
        for c in cs:
            assert orders[name] <= orders[c], \
                f"producer {name} (d{orders[name]}) staler than " \
                f"consumer {c} (d{orders[c]})"


def test_adaptive_planner_fits_reads_per_firing():
    prog, _ = _past_crossover_setting()
    compiled = compile_program(prog)
    wl = WorkloadDescriptor(update_rank=1, max_order=2, fold_window=8)
    ap = AdaptivePlanner(wl, replan_every=8, drift_tol=0.3)
    ap.bind(compiled)
    assert all(vp.order == 1 for vp in ap.plan.views.values())
    for _ in range(40):
        ap.observe("A", 16, 4)
    ap.observe_read()
    ap.observe_read()
    fitted = ap.observed_workload()
    assert fitted.reads_per_firing == pytest.approx(2 / 40)
    new = ap.maybe_replan()
    assert new is not None
    assert any(vp.order >= 2 for vp in new.views.values())
    # without the max_order opt-in the fit never touches the ratio
    ap1 = AdaptivePlanner(WorkloadDescriptor(update_rank=1), replan_every=8)
    ap1.bind(compiled)
    for _ in range(10):
        ap1.observe("A", 16, 4)
    ap1.observe_read()
    assert ap1.observed_workload().reads_per_firing == 1.0


def test_engine_adopts_plan_depth_and_stays_exact():
    prog = build_powers_program(4, 12)
    compiled = compile_program(prog)
    base = plan_program(compiled, WorkloadDescriptor(update_rank=1))
    deep = replace(base, views={n: replace(vp, strategy="incremental",
                                           threshold_rank=None,
                                           materialize=True, order=2)
                                for n, vp in base.views.items()})
    rng = np.random.default_rng(11)
    inputs = _gen_inputs(prog, rng, {"A": 0.25})
    eng = IncrementalEngine(prog, plan=deep, fold_window=3)
    ref = ReevalEngine(prog)
    eng.initialize(inputs)
    ref.initialize(inputs)
    assert set(eng._deferred) == set(base.views)
    for _ in range(8):
        ups = _ragged_stream(rng, (12, 12), T=2)
        eng.apply_updates("A", ups)
        for u, v in ups:
            ref.apply_update("A", u, v)
    eng.flush()
    _assert_views_match(eng, ref, 1e-6, label="planned-d2: ")
    assert eng.stats.folds > 0


def test_depth2_folds_once_per_window_without_reads():
    """With no reads, a depth-2 engine folds exactly once every
    ``fold_window`` firings; a partial window waits for the next read,
    which folds it and serves exact views."""
    prog = build_powers_program(4, 12)
    rng = np.random.default_rng(17)
    inputs = _gen_inputs(prog, rng, {"A": 0.25})
    w = 4
    eng = IncrementalEngine(prog, order=2, fold_window=w,
                            max_fold_rank=None)
    ref = ReevalEngine(prog)
    eng.initialize(inputs)
    ref.initialize(inputs)
    windows = 3
    for i in range(windows * w + 2):
        ups = _ragged_stream(rng, (12, 12), T=2)
        eng.apply_updates("A", ups)
        for u, v in ups:
            ref.apply_update("A", u, v)
        assert eng.stats.folds == (i + 1) // w
    assert eng.stats.folds == windows
    assert eng._cascade_pending()
    eng.flush()
    assert eng.stats.folds == windows + 1
    assert not eng._cascade_pending()
    _assert_views_match(eng, ref, 1e-6, label="d2 windows: ")


def test_engine_rejects_lazy_plus_deferred_plan():
    prog = build_powers_program(4, 12)
    compiled = compile_program(prog)
    base = plan_program(compiled, WorkloadDescriptor(update_rank=1))
    views = dict(base.views)
    names = sorted(views)
    views[names[0]] = replace(views[names[0]], materialize=False)
    views[names[-1]] = replace(views[names[-1]], order=2,
                               materialize=True)
    bad = replace(base, views=views)
    with pytest.raises(ValueError, match="materialize"):
        IncrementalEngine(prog, plan=bad)


def test_engine_adaptive_depth_hot_swap_stays_exact():
    """End to end: sparse reads observed online tip the adaptive planner
    into a depth ≥ 2 plan; the engine hot-swaps it mid-stream (folding
    the old windows first) and keeps serving exact reads."""
    prog = build_powers_program(4, 12)
    wl = WorkloadDescriptor(update_rank=1, max_order=2, fold_window=4)
    eng = IncrementalEngine(
        prog, {"A": 4},
        plan=AdaptivePlanner(wl, replan_every=6, drift_tol=0.2),
        fold_window=4)
    ref = ReevalEngine(prog)
    rng = np.random.default_rng(13)
    inputs = _gen_inputs(prog, rng, {"A": 0.25})
    eng.initialize(inputs)
    ref.initialize(inputs)
    for _ in range(20):
        ups = [_ragged_stream(rng, (12, 12), T=1)[0] for _ in range(4)]
        ups = [(np.hstack([u for u, _ in ups]),
                np.hstack([v for _, v in ups]))]
        eng.apply_updates("A", ups)
        for u, v in ups:
            ref.apply_update("A", u, v)
    assert any(o >= 2 for o in eng._view_orders.values()), \
        "sparse-read workload past the crossover must adopt depth"
    out = prog.output_names()[0]
    got = np.asarray(eng.output(out), np.float64)
    want = np.asarray(ref.views[out], np.float64)
    assert np.abs(got - want).max() / max(np.abs(want).max(), 1.0) <= 1e-6


# ---------------------------------------------------------------------------
# fleet-facing pricing
# ---------------------------------------------------------------------------


def test_firing_cost_amortized_for_deferred_views():
    prog = build_powers_program(4, 16)
    compiled = compile_program(prog)
    binding = dict(prog.dims)
    wl = WorkloadDescriptor(max_order=3, fold_window=8)
    full = firing_cost_flops(compiled, binding, "A", 8, workload=wl)
    orders2 = {stmt.target.name: 2 for stmt in prog.statements}
    amort2 = firing_cost_flops(compiled, binding, "A", 8, workload=wl,
                               view_orders=orders2)
    orders3 = {stmt.target.name: 3 for stmt in prog.statements}
    amort3 = firing_cost_flops(compiled, binding, "A", 8, workload=wl,
                               view_orders=orders3)
    assert amort2 < full
    assert amort3 <= amort2
    # first-order signature is the identity (regression pin)
    assert firing_cost_flops(compiled, binding, "A", 8, workload=wl,
                             view_orders={}) == full


# ---------------------------------------------------------------------------
# carrier × higher-order interplay (ISSUE 10 satellite: the gap left by
# PR 8 and PR 9 landing independently)
# ---------------------------------------------------------------------------
#
# Deferred (order>=2) engines bank firings in factored form and fold at
# reads; sparsity-aware carriers arrive as RowLocal/NoOp objects.  The
# contract where they meet: a no-op carrier is skipped without touching
# the window, a row-local carrier WIDENS into the banked window (the
# fold sweeps from a base snapshot, so there is no row-slab fast path
# at depth >= 2 — `_rowlocal_ok` refuses deferred engines) — and both
# must leave the folded views exact against re-evaluation.


def _carrier_chain_prog(n=48, m=24, k=12):
    from repro.core import Program, dim, matmul
    p = Program(name="ho_carrier_chain")
    X = p.input("X", (dim("N"), dim("M")))
    W1 = p.input("W1", (dim("M"), dim("K")))
    Y1 = p.let("Y1", matmul(X, W1))
    p.let("Y2", matmul(Y1, p.input("W2", (dim("K"), dim("K")))))
    p.outputs = ["Y1", "Y2"]
    return p.bind_dims(N=n, M=m, K=k)


def _carrier_chain_inputs(seed, n=48, m=24, k=12):
    rng = np.random.default_rng(seed)
    return {"X": rng.standard_normal((n, m)).astype(np.float32) * 0.3,
            "W1": rng.standard_normal((m, k)).astype(np.float32) * 0.3,
            "W2": rng.standard_normal((k, k)).astype(np.float32) * 0.3}


@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_rowlocal_carriers_through_order2_engine(seed):
    from repro.data import row_local_stream
    prog = _carrier_chain_prog()
    inputs = _carrier_chain_inputs(seed)
    lazy = IncrementalEngine(prog, {"X": 4}, order=2, fold_window=3)
    eager = IncrementalEngine(prog, {"X": 4})
    ref = ReevalEngine(prog)
    for e in (lazy, eager, ref):
        e.initialize(dict(inputs))
    stream = row_local_stream(48, 3, m=24, rank=2, seed=seed + 1)
    for c in [stream.next_carrier() for _ in range(10)]:
        lazy.apply_update("X", c)
        eager.apply_update("X", c)
        P, Q = c.factors()
        ref.apply_update("X", P, Q)
    lazy.output()
    # the eager engine fired row-slabs (Y1/Y2 are row-local); the lazy
    # one banked and folded — carriers widen at depth >= 2 by contract
    assert eager.stats.rowlocal_firings == 10
    assert lazy.stats.rowlocal_firings == 0
    assert lazy.stats.folds > 0
    for name in ("Y1", "Y2"):
        a = np.asarray(lazy.views[name], np.float64)
        b = np.asarray(ref.views[name], np.float64)
        assert np.abs(a - b).max() / max(np.abs(b).max(), 1.0) < 1e-5


@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_noop_carriers_through_order2_engine(seed):
    from repro.core import NoOpCarrier
    prog = _carrier_chain_prog()
    lazy = IncrementalEngine(prog, {"X": 4}, order=2, fold_window=3)
    lazy.initialize(_carrier_chain_inputs(seed))
    before = {k: np.asarray(v).copy() for k, v in lazy.views.items()}
    for _ in range(7):
        lazy.apply_update("X", NoOpCarrier(48, 24))
    lazy.output()
    assert lazy.stats.noop_skips == 7
    # no-ops never enter the window: nothing banked, nothing folded in
    for name in ("Y1", "Y2"):
        assert np.array_equal(np.asarray(lazy.views[name]), before[name])


@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_mixed_carriers_and_dense_through_order2(seed):
    """Interleaved RowLocal / LowRank / dense / NoOp updates through a
    depth-2 window must fold to the re-evaluation answer."""
    from repro.core import LowRankCarrier, NoOpCarrier
    from repro.data import row_local_stream
    rng = np.random.default_rng(seed + 5)
    prog = _carrier_chain_prog()
    inputs = _carrier_chain_inputs(seed)
    lazy = IncrementalEngine(prog, {"X": 4}, order=2, fold_window=2)
    ref = ReevalEngine(prog)
    lazy.initialize(dict(inputs))
    ref.initialize(dict(inputs))
    stream = row_local_stream(48, 2, m=24, rank=2, seed=seed)
    for step in range(12):
        kind = step % 4
        if kind == 0:
            c = stream.next_carrier()
            lazy.apply_update("X", c)
            P, Q = c.factors()
            ref.apply_update("X", P, Q)
        elif kind == 1:
            P = (rng.standard_normal((48, 2)) * 0.1).astype(np.float32)
            Q = (rng.standard_normal((24, 2)) * 0.1).astype(np.float32)
            lazy.apply_update("X", LowRankCarrier(P, Q))
            ref.apply_update("X", P, Q)
        elif kind == 2:
            u = (rng.standard_normal((48, 4)) * 0.1).astype(np.float32)
            v = (rng.standard_normal((24, 4)) * 0.1).astype(np.float32)
            lazy.apply_update("X", u, v)
            ref.apply_update("X", u, v)
        else:
            lazy.apply_update("X", NoOpCarrier(48, 24))
    lazy.output()
    for name in ("Y1", "Y2"):
        a = np.asarray(lazy.views[name], np.float64)
        b = np.asarray(ref.views[name], np.float64)
        assert np.abs(a - b).max() / max(np.abs(b).max(), 1.0) < 1e-5
    assert lazy.stats.folds > 0 and lazy.stats.noop_skips == 3

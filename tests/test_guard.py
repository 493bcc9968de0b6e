"""repro.guard: quarantine, transactional rollback, drift sentinel,
chaos harness, checkpoint checksums, and serve-path degradation.

The chaos suite runs under REPRO_CHAOS_SEEDS (comma-separated; default
"0" locally, a matrix in CI) so recovery paths are exercised under
several deterministic fault sequences.
"""

import dataclasses
import os

import numpy as np
import pytest

import jax.numpy as jnp

from repro.apps.matrix_powers import build_powers_program
from repro.apps.ols import build_ols_program
from repro.core.codegen import evaluate
from repro.core.runtime import EngineStats, IncrementalEngine
from repro.data.updates import UpdateStream
from repro.guard import (ChaosConfig, ChaosError, CircuitBreaker,
                         DegradePolicy, GuardConfig, GuardedView,
                         SentinelConfig, ValidationPolicy, validate_update)

CHAOS_SEEDS = [int(s) for s in
               os.environ.get("REPRO_CHAOS_SEEDS", "0").split(",")]


def _ols_inputs(m=96, n=12, p=2, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((m, n)).astype(np.float32)
    Y = rng.standard_normal((m, p)).astype(np.float32)
    return {"X": X, "Y": Y}


def _snapshot(engine):
    return {k: np.asarray(v) for k, v in engine.views.items()}


def _reference_views(engine):
    """Re-evaluate every statement from the engine's current inputs."""
    env = {k: engine.views[k] for k in engine.program.inputs}
    for st in engine.program.statements:
        env[st.target.name] = evaluate(st.expr, env, engine.binding)
    return env


# ---------------------------------------------------------------------------
# layer 1: validation + quarantine
# ---------------------------------------------------------------------------


def test_validate_update_reasons():
    pol = ValidationPolicy(max_update_rank=2, max_norm=10.0)
    ok_u = np.ones((4, 1), np.float32)
    ok_v = np.ones((3, 1), np.float32)
    assert validate_update("X", ok_u, ok_v, (4, 3), pol) is None
    assert "2-D" in validate_update("X", ok_u[:, 0], ok_v, (4, 3), pol)
    assert "rows" in validate_update("X", ok_u, ok_v, (5, 3), pol)
    assert "ranks disagree" in validate_update(
        "X", np.ones((4, 2), np.float32), ok_v, (4, 3), pol)
    assert "floating point" in validate_update(
        "X", ok_u.astype(np.int32), ok_v, (4, 3), pol)
    assert "exceeds budget" in validate_update(
        "X", np.ones((4, 3), np.float32), np.ones((3, 3), np.float32),
        (4, 3), pol)
    bad = ok_u.copy()
    bad[0] = np.nan
    assert "non-finite" in validate_update("X", bad, ok_v, (4, 3), pol)
    assert "norm bound" in validate_update(
        "X", 100 * ok_u, 100 * ok_v, (4, 3), pol)


def test_quarantine_never_corrupts_views():
    prog = build_ols_program(m=96, n=12, p=2)
    eng = IncrementalEngine(prog, guard=GuardConfig())
    eng.initialize(_ols_inputs())
    before = _snapshot(eng)
    rng = np.random.default_rng(1)
    for kind in (np.nan, np.inf, -np.inf):
        u = rng.standard_normal((96, 1)).astype(np.float32)
        u[5] = kind
        v = rng.standard_normal((12, 1)).astype(np.float32)
        eng.apply_update("X", u, v)
        assert eng.enqueue_update("X", u, v) is None
    eng.guard.sync()  # resolve the deferred (in-program) screens
    assert len(eng.guard.quarantine) == 6
    assert eng.guard.stats.quarantined == 6
    after = _snapshot(eng)
    for k in before:
        np.testing.assert_array_equal(before[k], after[k])
    # quarantine is inspectable per input
    assert len(eng.guard.quarantine.by_input("X")) == 6
    assert eng.guard.quarantine.reasons() == {
        "non-finite entries in update factors": 6}


def test_quarantine_replay_after_repair():
    prog = build_ols_program(m=96, n=12, p=2)
    eng = IncrementalEngine(prog, guard=GuardConfig())
    eng.initialize(_ols_inputs())
    u = np.full((96, 1), np.nan, np.float32)
    v = np.ones((12, 1), np.float32) * 0.01
    eng.apply_update("X", u, v)
    eng.guard.sync()
    assert len(eng.guard.quarantine) == 1

    def repair(rec):
        return np.nan_to_num(rec.u), rec.v

    applied, requarantined = eng.guard.quarantine.replay(eng, repair=repair)
    assert (applied, requarantined) == (1, 0)
    assert len(eng.guard.quarantine) == 0
    # replay without repair goes straight back to quarantine, not a loop
    eng.apply_update("X", u, v)
    applied, requarantined = eng.guard.quarantine.replay(eng)
    assert (applied, requarantined) == (0, 1)
    assert len(eng.guard.quarantine) == 1


def test_quarantine_capacity_evicts_oldest():
    prog = build_ols_program(m=96, n=12, p=2)
    eng = IncrementalEngine(prog, guard=GuardConfig(quarantine_capacity=3))
    eng.initialize(_ols_inputs())
    u = np.full((96, 1), np.nan, np.float32)
    v = np.ones((12, 1), np.float32)
    for _ in range(5):
        eng.apply_update("X", u, v)
    eng.guard.sync()
    assert len(eng.guard.quarantine) == 3
    assert eng.guard.quarantine.evicted == 2


# ---------------------------------------------------------------------------
# layer 2: transactional firings
# ---------------------------------------------------------------------------


def test_injected_trigger_fault_rolls_back_bit_identically():
    prog = build_ols_program(m=96, n=12, p=2)
    eng = IncrementalEngine(prog, guard=GuardConfig(),
                            chaos=ChaosConfig(seed=0, trigger_raise_p=1.0))
    eng.initialize(_ols_inputs())
    before_views = dict(eng.views)  # references: must be THE same arrays
    before_stats = dataclasses.replace(eng.stats)
    rng = np.random.default_rng(2)
    u = rng.standard_normal((96, 1)).astype(np.float32) * 0.01
    v = rng.standard_normal((12, 1)).astype(np.float32) * 0.01
    out = eng.apply_update("X", u, v)
    for k, arr in before_views.items():
        assert out[k] is arr, f"{k}: rollback must restore the same buffer"
    # every counter as it was, but the trigger built for this firing,
    # which stays in the trigger cache
    assert eng.stats == dataclasses.replace(
        before_stats, trigger_builds=before_stats.trigger_builds + 1)
    assert eng.guard.stats.rollbacks == 1
    assert eng.guard.stats.aborted_firings == 1
    assert eng.chaos.raises == 1
    # the aborted factors are quarantined for inspection
    assert len(eng.guard.quarantine) == 1


def test_nonfinite_output_rolls_back():
    """A finite-but-huge update passes admission, overflows f32 in the
    firing, and is caught by output validation + rolled back."""
    prog = build_ols_program(m=96, n=12, p=2)
    eng = IncrementalEngine(prog, guard=GuardConfig())
    eng.initialize(_ols_inputs())
    before = _snapshot(eng)
    u = np.full((96, 1), 1e38, np.float32)
    v = np.full((12, 1), 1.0, np.float32)
    assert validate_update("X", u, v, (96, 12),
                           ValidationPolicy()) is None  # admissible
    eng.apply_update("X", u, v)
    eng.guard.sync()  # settle the deferred in-program rollback accounting
    after = _snapshot(eng)
    for k in before:
        np.testing.assert_array_equal(before[k], after[k])
    assert eng.guard.stats.rollbacks == 1
    assert all(np.isfinite(a).all() for a in after.values())
    reasons = list(eng.guard.quarantine)[0].reason
    assert "non-finite output" in reasons


def test_norm_budget_blocks_huge_updates_at_admission():
    pol = ValidationPolicy(max_norm=1e6)
    prog = build_ols_program(m=96, n=12, p=2)
    eng = IncrementalEngine(prog, guard=GuardConfig(validation=pol))
    eng.initialize(_ols_inputs())
    eng.apply_update("X", np.full((96, 1), 1e38, np.float32),
                     np.ones((12, 1), np.float32))
    assert eng.guard.stats.quarantined == 1
    assert eng.guard.stats.rollbacks == 0  # never reached the trigger


def test_guard_refuses_donate():
    prog = build_ols_program(m=96, n=12, p=2)
    with pytest.raises(ValueError, match="donate"):
        IncrementalEngine(prog, guard=GuardConfig(), donate=True)


def test_batched_firing_quarantines_only_poisoned():
    prog = build_ols_program(m=96, n=12, p=2)
    eng = IncrementalEngine(prog, guard=GuardConfig())
    eng.initialize(_ols_inputs())
    rng = np.random.default_rng(3)
    ups = [(rng.standard_normal((96, 1)).astype(np.float32) * 0.01,
            rng.standard_normal((12, 1)).astype(np.float32) * 0.01)
           for _ in range(6)]
    ups[2] = (np.full((96, 1), np.nan, np.float32), ups[2][1])
    eng.apply_updates("X", ups)
    assert eng.guard.stats.quarantined == 1
    assert eng.guard.stats.admitted == 5
    assert all(np.isfinite(np.asarray(a)).all() for a in eng.views.values())
    ref = _reference_views(eng)
    np.testing.assert_allclose(np.asarray(eng.views["beta"]),
                               np.asarray(ref["beta"]), rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# layer 3: drift sentinel
# ---------------------------------------------------------------------------


def test_sentinel_detects_and_recovers_drift():
    prog = build_ols_program(m=96, n=12, p=2)
    eng = IncrementalEngine(
        prog, guard=GuardConfig(sentinel=SentinelConfig(probe_every=1,
                                                        tol=1e-3)))
    eng.initialize(_ols_inputs())
    # inject artificial drift: perturb a maintained view directly
    eng.views["Z"] = eng.views["Z"] + 0.5
    rng = np.random.default_rng(4)
    u = rng.standard_normal((96, 1)).astype(np.float32) * 0.01
    v = rng.standard_normal((12, 1)).astype(np.float32) * 0.01
    eng.apply_update("X", u, v)  # probe fires, sees the drift, recovers
    sen = eng.guard.sentinel
    assert sen.probes >= 1
    assert sen.recoveries >= 1
    assert eng.guard.stats.drift_recoveries >= 1
    ref = _reference_views(eng)
    for name in ("Z", "W", "beta"):
        np.testing.assert_allclose(np.asarray(eng.views[name]),
                                   np.asarray(ref[name]),
                                   rtol=5e-3, atol=5e-3)
    # drift probes after recovery are back under tolerance
    drifts = sen.probe(eng)
    assert all(d <= sen.config.tol for d in drifts.values()), drifts


def test_sentinel_feeds_planner_note_drift():
    from repro.plan import AdaptivePlanner
    prog = build_ols_program(m=96, n=12, p=2)
    eng = IncrementalEngine(
        prog, plan=AdaptivePlanner(),
        guard=GuardConfig(sentinel=SentinelConfig(probe_every=1, tol=1e-3)))
    eng.initialize(_ols_inputs())
    eng.views["Z"] = eng.views["Z"] + 0.5
    rng = np.random.default_rng(5)
    eng.apply_update("X",
                     rng.standard_normal((96, 1)).astype(np.float32) * 0.01,
                     rng.standard_normal((12, 1)).astype(np.float32) * 0.01)
    assert eng.planner.drift_counts.get("Z", 0) >= 1


# ---------------------------------------------------------------------------
# the acceptance chaos run: 500 firings with poison + trigger faults
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", CHAOS_SEEDS)
@pytest.mark.parametrize("family", ["ols", "powers"])
def test_chaos_500_firings_stays_finite_and_converges(family, seed):
    if family == "ols":
        prog = build_ols_program(m=64, n=8, p=2)
        inputs = _ols_inputs(m=64, n=8, p=2, seed=seed)
        input_name, (n_rows, n_cols) = "X", (64, 8)
    else:
        prog = build_powers_program(k=4, n=24, model="exp")
        rng = np.random.default_rng(seed)
        a = rng.standard_normal((24, 24)).astype(np.float32)
        a *= 0.9 / max(abs(np.linalg.eigvals(a)))
        inputs = {"A": a}
        input_name, (n_rows, n_cols) = "A", (24, 24)

    chaos = ChaosConfig(seed=seed, poison_p=0.01, poison_kind="nan",
                        trigger_raise_p=0.005)
    eng = IncrementalEngine(
        prog, guard=GuardConfig(sentinel=SentinelConfig(probe_every=100)),
        chaos=chaos)
    eng.initialize(inputs)
    stream = UpdateStream(n=n_rows, m=n_cols, scale=0.005,
                          seed=seed, zipf=1.5)
    it = iter(stream)
    for i in range(500):
        u, v = next(it)
        eng.apply_update(input_name, u, v)
        if i % 100 == 99:  # the engine never serves a non-finite view
            assert all(bool(jnp.isfinite(a).all())
                       for a in eng.views.values()), f"firing {i}"

    eng.guard.sync()
    g = eng.guard.stats
    assert eng.chaos.poisoned > 0, "chaos never fired — test is vacuous"
    assert g.quarantined == eng.chaos.poisoned
    assert g.rollbacks == eng.chaos.raises
    assert g.admitted + g.quarantined == 500
    assert all(bool(jnp.isfinite(a).all()) for a in eng.views.values())
    # final views match re-evaluation from the maintained inputs within
    # the sentinel tolerance (relative Frobenius residual)
    ref = _reference_views(eng)
    tol = eng.guard.sentinel.config.tol
    for st in prog.statements:
        name = st.target.name
        r = np.asarray(ref[name], np.float64)
        c = np.asarray(eng.views[name], np.float64)
        drift = np.linalg.norm(r - c) / max(np.linalg.norm(r), 1e-30)
        assert drift <= tol, f"{name}: drift {drift:.2e} > {tol}"


# ---------------------------------------------------------------------------
# checkpoint checksums + chain fallback
# ---------------------------------------------------------------------------


def _ckpt_tree(step, rng):
    return {"w": (rng.standard_normal((32, 16)) * 0.1 + step
                  ).astype(np.float32),
            "b": np.full((16,), float(step), np.float32)}


def test_checkpoint_checksum_fallback(tmp_path):
    from repro.dist.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path), async_save=False,
                            incremental_rank=4, full_every=10)
    rng = np.random.default_rng(0)
    trees = {s: _ckpt_tree(s, rng) for s in range(4)}
    for s in range(4):
        mgr.save(s, trees[s])
    # corrupt the newest payload's array bytes (zip still opens)
    path = os.path.join(str(tmp_path), "ckpt_00000003.npz")
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.seek(size - 64)
        f.write(b"\xff" * 32)
    restored = mgr.restore(trees[3])
    assert mgr.last_restored_step == 2
    np.testing.assert_allclose(restored["w"], trees[2]["w"], atol=2e-3)


def test_checkpoint_all_corrupt_raises(tmp_path):
    from repro.dist.checkpoint import (CheckpointCorruptError,
                                       CheckpointManager)
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    rng = np.random.default_rng(0)
    tree = _ckpt_tree(0, rng)
    mgr.save(0, tree)
    path = os.path.join(str(tmp_path), "ckpt_00000000.npz")
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) - 64)
        f.write(b"\xff" * 32)
    with pytest.raises(CheckpointCorruptError):
        mgr.restore(tree)


def test_chaos_corrupts_and_manager_falls_back(tmp_path):
    """The chaos corrupt-checkpoint hook + checksum fallback, end to
    end through the manager's own write path."""
    from repro.dist.checkpoint import CheckpointManager
    chaos = ChaosConfig(seed=3, corrupt_checkpoint_p=1.0).monkey()
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    rng = np.random.default_rng(0)
    trees = {s: _ckpt_tree(s, rng) for s in range(2)}
    mgr.save(0, trees[0])          # intact
    mgr._chaos = chaos
    mgr.save(1, trees[1])          # corrupted on write
    assert chaos.corruptions == 1
    restored = mgr.restore(trees[1])
    assert mgr.last_restored_step == 0
    np.testing.assert_array_equal(restored["b"], trees[0]["b"])


# ---------------------------------------------------------------------------
# supervisor survives chaos: host kill + corrupt-checkpoint restore
# ---------------------------------------------------------------------------


def test_supervisor_survives_host_kill_and_corrupt_checkpoint(tmp_path):
    from repro.dist.checkpoint import CheckpointManager
    from repro.dist.fault_tolerance import (FaultToleranceConfig,
                                            FaultTolerantController,
                                            TrainingSupervisor)

    class FakeClock:
        t = 0.0

        def __call__(self):
            return self.t

    clock = FakeClock()
    chaos = ChaosConfig(seed=7, corrupt_checkpoint_p=0.5,
                        kill_host_p=0.0).monkey()
    mgr = CheckpointManager(str(tmp_path), async_save=False, chaos=chaos)
    ctl = FaultTolerantController(
        4, FaultToleranceConfig(heartbeat_timeout=5.0, min_hosts=1),
        clock=clock, chaos=chaos)
    sup = TrainingSupervisor(ctl, save_every=4)
    state = {"step": -1, "restores": 0}

    def step_fn(t):
        clock.t += 1.0
        state["step"] = t
        if t == 9:
            chaos._killed.add(2)  # deterministic mid-step host kill
        return 0.1

    def reporting_fn(t):
        return range(4)  # every host reports; chaos swallows the dead one

    def save_fn(t):
        mgr.save(t, {"step": np.asarray([t], np.int64)})

    def restore_fn():
        from repro.dist.checkpoint import CheckpointCorruptError
        state["restores"] += 1
        if mgr.latest_step() is None:
            return 0
        try:
            mgr.restore({"step": np.asarray([0], np.int64)})
        except CheckpointCorruptError:
            return 0  # every checkpoint corrupt: restart from scratch
        return mgr.last_restored_step

    restarts = sup.run(30, step_fn, save_fn, restore_fn,
                       reporting_fn=reporting_fn)
    assert restarts >= 1            # the kill forced a restart
    assert state["restores"] >= 1
    assert 2 not in ctl.alive_hosts()
    assert state["step"] == 29      # and the run still finished
    assert chaos.corruptions >= 1   # restore path really saw corruption


# ---------------------------------------------------------------------------
# layer 5: serve-path degradation
# ---------------------------------------------------------------------------


class _FlakyView:
    """Duck-typed logit view whose flush fails until told otherwise."""

    def __init__(self):
        self.logits = np.zeros((4, 4), np.float32)
        self.failing = False
        self.flushes = 0
        self.pending_updates = 0

    def submit_head_update(self, u, v):
        self.flush()
        return True

    def flush(self):
        if self.failing:
            raise RuntimeError("backend down")
        self.flushes += 1
        self.logits = self.logits + 1.0
        return self.logits


def test_circuit_breaker_state_machine():
    clock = {"t": 0.0}
    br = CircuitBreaker(threshold=2, reset_timeout=10.0,
                        clock=lambda: clock["t"])
    assert br.state == "closed" and br.allow()
    br.record_failure()
    assert br.state == "closed"
    br.record_failure()
    assert br.state == "open" and not br.allow()
    clock["t"] += 10.0
    assert br.state == "half_open" and br.allow()
    br.record_failure()             # failed probe re-opens from now
    assert br.state == "open"
    clock["t"] += 10.0
    br.record_success()
    assert br.state == "closed" and br.consecutive_failures == 0


def test_guarded_view_degrades_to_snapshot_and_recovers():
    clock = {"t": 0.0}
    view = _FlakyView()
    gv = GuardedView(view,
                     DegradePolicy(max_retries=1, backoff_base=0.0,
                                   breaker_threshold=2, breaker_reset=30.0),
                     clock=lambda: clock["t"], sleep=lambda s: None)
    assert gv.flush()               # healthy: fresh serving
    good = np.asarray(view.logits).copy()
    view.failing = True
    assert not gv.flush()
    assert not gv.flush()           # second exhausted refresh trips it
    assert gv.breaker.state == "open"
    clock["t"] += 3.0
    out = gv.read()                 # degraded read: last-good snapshot
    np.testing.assert_array_equal(out, good)
    h = gv.health()
    assert h["serving"] == "snapshot"
    assert h["staleness_s"] == pytest.approx(3.0)
    assert h["degraded_reads"] == 1
    assert h["refresh_failures"] == 2
    clock["t"] += 30.0              # breaker half-opens, probe succeeds
    view.failing = False
    assert gv.flush()
    assert gv.breaker.state == "closed"
    assert gv.health()["serving"] == "fresh"
    assert gv.staleness() == 0.0


def test_serve_engine_view_health(tmp_path):
    pytest.importorskip("repro.serve")
    from repro.serve.incremental_views import IncrementalLogitView

    rng = np.random.default_rng(0)
    hidden = rng.standard_normal((8, 6)).astype(np.float32)
    head = rng.standard_normal((5, 6)).astype(np.float32)
    view = IncrementalLogitView(hidden, head, flush_size=2)
    gv = GuardedView(view, DegradePolicy(max_retries=0))
    u = rng.standard_normal((5, 1)).astype(np.float32) * 0.01
    v = rng.standard_normal((6, 1)).astype(np.float32) * 0.01
    gv.submit(u, v)
    assert gv.flush()
    h = gv.health()
    assert h["breaker"] == "closed" and h["serving"] == "fresh"
    ref = (np.asarray(hidden) @ (np.asarray(head) + u @ v.T).T)
    np.testing.assert_allclose(np.asarray(gv.read()), ref, rtol=2e-4,
                               atol=2e-4)


# ---------------------------------------------------------------------------
# satellite regressions: UpdateStream, planner op scales, refit
# ---------------------------------------------------------------------------


def test_update_stream_batch_advances():
    """Regression: batch() used to re-seed per call, replaying the same
    updates forever (and ignoring prior iteration draws)."""
    s = UpdateStream(n=16, m=4, seed=5)
    u1, v1 = s.batch(3)
    u2, v2 = s.batch(3)
    assert not (np.array_equal(u1, u2) and np.array_equal(v1, v2))
    s.reset()
    u3, v3 = s.batch(3)
    np.testing.assert_array_equal(u1, u3)
    np.testing.assert_array_equal(v1, v3)
    # iteration and batch() share one advancing stream
    s2 = UpdateStream(n=16, m=4, seed=5)
    next(iter(s2))
    u4, _ = s2.batch(3)
    assert not np.array_equal(u1, u4)
    # two same-seed streams replay identically (the benchmark contract)
    a = UpdateStream(n=16, m=4, seed=9)
    b = UpdateStream(n=16, m=4, seed=9)
    ua, va = a.batch(4)
    ub, vb = b.batch(4)
    np.testing.assert_array_equal(ua, ub)
    np.testing.assert_array_equal(va, vb)


def test_planner_op_cost_scales_move_inverse_crossover():
    from repro.plan import MaintenancePlan, WorkloadDescriptor, plan_program
    prog = build_ols_program(m=256, n=32, p=4)
    wl = WorkloadDescriptor(update_rank=1, rank_lo=1, rank_hi=40)
    plain = plan_program(prog, wl)
    scaled = plan_program(
        prog, dataclasses.replace(wl, op_cost_scales={"inverse": 8.0}))
    # W := (XᵀX)⁻¹ is inverse-dominated: its effective crossover rises
    assert scaled.views["W"].crossover_rank > plain.views["W"].crossover_rank
    # matmul-dominated views are unaffected
    assert scaled.views["Z"].crossover_rank == plain.views["Z"].crossover_rank
    # and the straddling cell flips strategy: hybrid → incremental
    assert plain.views["W"].strategy == "hybrid"
    assert scaled.views["W"].strategy == "incremental"
    # op scales survive plan serialization
    rt = MaintenancePlan.from_json(scaled.to_json())
    assert rt.workload.op_cost_scales == {"inverse": 8.0}


def test_calibrate_op_cost_scales_shape():
    from repro.plan import calibrate_op_cost_scales
    scales = calibrate_op_cost_scales(n=64, samples=1)
    assert set(scales) == {"matmul", "inverse", "other"}
    assert scales["matmul"] == 1.0
    assert all(s >= 1e-3 for s in scales.values())


def test_adaptive_planner_refits_cost_scale_from_stats():
    from repro.core.compiler import compile_program
    from repro.plan import AdaptivePlanner
    prog = build_ols_program(m=256, n=32, p=4)
    ap = AdaptivePlanner(drift_tol=0.5)
    ap.bind(compile_program(prog))
    stats = EngineStats()
    assert ap.refit_from_stats(stats) is None  # unmeasurable: no-op
    stats.trigger_seconds, stats.sweep_flops_timed = 0.1, 1e6
    stats.reeval_seconds, stats.reeval_flops_timed = 0.1, 1e8
    scale = ap.refit_from_stats(stats)
    assert scale == pytest.approx(100.0)
    assert ap.workload.cost_scale == pytest.approx(100.0)
    # the material change forces a replan regardless of cadence
    new = ap.maybe_replan()
    assert new is not None
    assert any(vp.strategy != "incremental" for vp in new.views.values())


def test_refit_through_engine_firing_path():
    """EngineStats timed-FLOP counters feed the planner's online refit
    via _observe_firing without any manual wiring."""
    from repro.plan import AdaptivePlanner
    prog = build_ols_program(m=96, n=12, p=2)
    eng = IncrementalEngine(prog, plan=AdaptivePlanner(replan_every=2))
    eng.initialize(_ols_inputs())
    rng = np.random.default_rng(6)
    for _ in range(3):
        eng.apply_update("X",
                         rng.standard_normal((96, 1)).astype(np.float32)
                         * 0.01,
                         rng.standard_normal((12, 1)).astype(np.float32)
                         * 0.01, block=True)
    eng.reevaluate(block=True)
    assert eng.stats.sweep_flops_timed > 0
    assert eng.stats.reeval_flops_timed > 0
    scale = eng.planner.refit_from_stats(eng.stats)
    assert scale is not None and scale > 0

# ---------------------------------------------------------------------------
# higher-order (deferred-cascade) engines under guard (ISSUE 8)
# ---------------------------------------------------------------------------


def test_deferred_engine_never_takes_guard_fast_path():
    """The fused-transaction fast path skips host-side snapshots; a
    deferred cascade carries host window state, so it must stay off."""
    prog = build_powers_program(k=4, n=12, model="exp")
    eng = IncrementalEngine(prog, order=2, fold_window=2,
                            guard=GuardConfig())
    assert not eng._guard_fast_path
    assert IncrementalEngine(prog, guard=GuardConfig())._guard_fast_path


def test_higher_order_fault_rolls_back_cascade_bit_identically():
    """An aborted firing on an order-2 engine must restore the views AND
    the cascade window (factors, bases, counters) — a half-accumulated
    window would silently double-apply at the next fold."""
    prog = build_powers_program(k=4, n=12, model="exp")
    rng = np.random.default_rng(2)
    a = rng.standard_normal((12, 12)).astype(np.float32) * 0.2
    eng = IncrementalEngine(prog, order=2, fold_window=4,
                            guard=GuardConfig(),
                            chaos=ChaosConfig(seed=0, trigger_raise_p=1.0))
    eng.initialize({"A": a})
    # seed the window with one admitted update (chaos counts firings
    # before raising; probability 1.0 raises on every guarded attempt)
    before_cascade = eng._cascade_snapshot()
    before_views = dict(eng.views)
    u = rng.standard_normal((12, 1)).astype(np.float32) * 0.01
    v = rng.standard_normal((12, 1)).astype(np.float32) * 0.01
    out = eng.apply_update("A", u, v)
    for k, arr in before_views.items():
        assert out[k] is arr, f"{k}: rollback must restore the same buffer"
    factors, base, firings = eng._cascade_snapshot()
    bf_factors, bf_base, bf_firings = before_cascade
    assert firings == bf_firings
    assert {o: {k: len(v) for k, v in fs.items()}
            for o, fs in factors.items()} == \
        {o: {k: len(v) for k, v in fs.items()}
         for o, fs in bf_factors.items()}
    assert eng.guard.stats.rollbacks == 1
    assert eng.stats.folds == 0


@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_fold_abort_refolds_exactly(seed):
    """Chaos raised inside a fold rolls the fold back and re-folds via
    the chaos-free exact path; the stream must end exact regardless."""
    prog = build_powers_program(k=4, n=12, model="exp")
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((12, 12)).astype(np.float32)
    a *= 0.5 / max(abs(np.linalg.eigvals(a)))
    chaos = ChaosConfig(seed=seed, trigger_raise_p=0.35)
    eng = IncrementalEngine(prog, order=2, fold_window=2,
                            guard=GuardConfig(), chaos=chaos)
    eng.initialize({"A": a})
    stream = UpdateStream(n=12, m=12, scale=0.01, seed=seed)
    it = iter(stream)
    for _ in range(30):
        u, v = next(it)
        eng.apply_update("A", u, v)
    eng.flush()
    assert eng.chaos.raises > 0, "chaos never fired — test is vacuous"
    assert eng.stats.folds > 0
    assert all(bool(jnp.isfinite(x).all()) for x in eng.views.values())
    # the maintained inputs hold exactly the admitted updates, so
    # re-evaluating from them is the exactness oracle
    ref = _reference_views(eng)
    for st in prog.statements:
        name = st.target.name
        r = np.asarray(ref[name], np.float64)
        c = np.asarray(eng.views[name], np.float64)
        err = np.abs(r - c).max() / max(np.abs(r).max(), 1.0)
        assert err <= 1e-5, f"{name}: {err:.2e}"


@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_higher_order_chaos_matches_first_order_replay(seed):
    """Differential: an order-2 guarded engine under poison + trigger
    chaos stays exactly-once — its final state matches an isolated
    clean FIRST-order engine replaying only the admitted updates."""
    prog = build_powers_program(k=4, n=16, model="exp")
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((16, 16)).astype(np.float32)
    a *= 0.5 / max(abs(np.linalg.eigvals(a)))
    chaos = ChaosConfig(seed=seed, poison_p=0.05, poison_kind="nan",
                        trigger_raise_p=0.05)
    eng = IncrementalEngine(prog, order=2, fold_window=3,
                            guard=GuardConfig(), chaos=chaos)
    eng.initialize({"A": a})
    stream = UpdateStream(n=16, m=16, scale=0.005, seed=seed)
    it = iter(stream)
    applied = []
    n_updates = 60
    for _ in range(n_updates):
        u, v = next(it)
        before = eng.guard.stats.admitted
        aborted = eng.guard.stats.aborted_firings
        eng.apply_update("A", u, v)
        # "admitted" is admission control (validation passed); a chaos
        # abort rolls an admitted firing back and drops it — committed
        # means admitted AND not aborted
        if (eng.guard.stats.admitted > before
                and eng.guard.stats.aborted_firings == aborted):
            applied.append((u, v))
    eng.flush()
    eng.guard.sync()
    g = eng.guard.stats
    assert eng.chaos.poisoned > 0, "chaos never fired — test is vacuous"
    assert g.admitted + g.quarantined == n_updates  # exactly-once
    assert len(applied) == g.admitted - g.aborted_firings
    replay = IncrementalEngine(prog)  # clean, first-order
    replay.initialize({"A": a})
    for u, v in applied:
        replay.apply_update("A", u, v)
    for st in prog.statements:
        name = st.target.name
        r = np.asarray(replay.views[name], np.float64)
        c = np.asarray(eng.views[name], np.float64)
        err = np.abs(r - c).max() / max(np.abs(r).max(), 1.0)
        assert err <= 1e-5, f"{name}: {err:.2e}"
    np.testing.assert_array_equal(np.asarray(eng.views["A"]),
                                  np.asarray(replay.views["A"]))

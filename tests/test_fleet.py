"""repro.fleet tests: leases/fencing, admission, exactly-once commit
under worker crashes + lease expiry, the N-tenants-bit-identical-to-N-
isolated-engines property, overload tiers, noisy-neighbor quarantine,
and the 500-firing fleet chaos acceptance run — plus the satellite
regressions (thread-safe TriggerCache, chain-aware planner pricing,
deterministic degrade clocks).

The chaos tests run under REPRO_CHAOS_SEEDS (comma-separated; default
"0" locally, a matrix in CI).
"""

import os
import threading

import numpy as np
import pytest

from repro.apps.ols import build_ols_program
from repro.core.compiler import compile_program
from repro.core.runtime import IncrementalEngine, max_abs_diff
from repro.fleet import (ADMITTED, QUEUE_FULL, SHED, THROTTLED, FleetConfig,
                         FleetScheduler, LeaseStore, OverloadPolicy,
                         TenantSpec, TokenBucket, WorkerCrashed)
from repro.guard import ChaosConfig, CircuitBreaker, DegradePolicy, \
    retry_with_backoff
from repro.plan import (TriggerCache, WorkloadDescriptor, firing_cost_flops,
                        plan_program, trigger_chain_costs)
from repro.serve.incremental_views import build_logit_view_program

CHAOS_SEEDS = [int(s) for s in
               os.environ.get("REPRO_CHAOS_SEEDS", "0").split(",")]


class VClock:
    """Deterministic virtual time for lease/breaker/backoff tests."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt

    def sleep(self, dt: float) -> None:
        self.t += dt


def _ols_tenant(m=24, n=6, p=1, seed=0):
    rng = np.random.default_rng(seed)
    prog = build_ols_program(m, n, p)
    inputs = {"X": rng.standard_normal((m, n)).astype(np.float32),
              "Y": rng.standard_normal((m, p)).astype(np.float32)}
    return prog, inputs


def _logit_tenant(m=8, d=4, p=5, seed=0):
    rng = np.random.default_rng(seed)
    prog = build_logit_view_program(m, d, p)
    inputs = {"H": rng.standard_normal((m, d)).astype(np.float32),
              "W": (rng.standard_normal((p, d)) * 0.1).astype(np.float32)}
    return prog, inputs


def _rank1(rng, n, m, scale=0.1):
    return ((rng.standard_normal((n, 1)) * scale).astype(np.float32),
            (rng.standard_normal((m, 1)) * scale).astype(np.float32))


def _replay_reference(tenant, inputs, updates_by_lsn):
    """An isolated engine fed the tenant's committed firing groups in
    commit order — the fleet's committed store must match it
    bit-identically (same guard config, same grouping, same values)."""
    ref = IncrementalEngine(tenant.spec.program, tenant.spec.update_ranks,
                            guard=tenant.spec.guarded or None)
    ref.initialize(inputs)
    for input_name, lsns in tenant.commit_log:
        assert input_name != "<reeval>", "property test must not degrade"
        ref.apply_updates(input_name,
                          [updates_by_lsn[l] for l in lsns])
    return ref


# ---------------------------------------------------------------------------
# leases
# ---------------------------------------------------------------------------

def test_lease_claim_renew_release():
    vc = VClock()
    store = LeaseStore(ttl=1.0, clock=vc)
    lease = store.claim("t1", "w1")
    assert lease is not None and lease.token == 1
    # live lease blocks everyone, including the holder (not reentrant)
    assert store.claim("t1", "w2") is None
    assert store.claim("t1", "w1") is None
    vc.advance(0.6)
    assert store.renew(lease)          # extended to t=1.6
    vc.advance(0.8)
    assert store.is_current(lease)     # t=1.4 < 1.6
    assert store.release(lease)
    assert not store.is_current(lease)
    lease2 = store.claim("t1", "w2")   # freed: next claim wins token 2
    assert lease2 is not None and lease2.token == 2
    assert store.stats()["reclaims"] == 0


def test_lease_expiry_reclaim_and_fencing():
    vc = VClock()
    store = LeaseStore(ttl=1.0, clock=vc)
    stale = store.claim("t1", "w1")
    vc.advance(1.5)                    # w1 dies; TTL runs out
    assert store.expired() and store.expired()[0] is stale
    fresh = store.claim("t1", "w2")    # reclaim
    assert fresh is not None and fresh.token == 2
    assert store.stats()["reclaims"] == 1
    # the zombie is fenced out of every path
    assert not store.is_current(stale)
    assert not store.renew(stale)
    assert not store.release(stale)
    assert store.stats()["fence_rejections"] == 2
    assert store.is_current(fresh)     # the reclaimer is unaffected


def test_lease_break_is_indistinguishable_from_expiry():
    vc = VClock()
    store = LeaseStore(ttl=10.0, clock=vc)
    lease = store.claim("t1", "w1")
    assert store.break_lease("t1")     # chaos lease_expiry_p path
    assert not store.is_current(lease)
    assert store.holder("t1") is None
    assert store.claim("t1", "w2") is not None
    assert store.stats()["broken"] == 1


# ---------------------------------------------------------------------------
# admission
# ---------------------------------------------------------------------------

def test_token_bucket_refill():
    vc = VClock()
    b = TokenBucket(rate=2.0, burst=4, clock=vc)
    assert all(b.allow() for _ in range(4))   # full burst
    assert not b.allow()                      # empty
    vc.advance(1.0)                           # +2 tokens
    assert b.allow() and b.allow() and not b.allow()
    vc.advance(100.0)
    assert b.available() == 4                 # capped at burst


def test_admission_throttle_queue_full_and_shed():
    vc = VClock()
    fleet = FleetScheduler(FleetConfig(lease_ttl=1.0), clock=vc,
                           sleep=vc.sleep)
    prog, inputs = _logit_tenant()
    # sheddable=False so the full queue exposes QUEUE_FULL back-pressure
    # instead of tripping the shedding tier first (covered elsewhere)
    fleet.add_tenant(TenantSpec("t1", prog, {"W": 1}, quota_rate=1.0,
                                quota_burst=2, queue_capacity=3,
                                sheddable=False), inputs)
    rng = np.random.default_rng(0)
    ups = [_rank1(rng, 5, 4) for _ in range(4)]
    assert fleet.submit("t1", "W", *ups[0]) == ADMITTED
    assert fleet.submit("t1", "W", *ups[1]) == ADMITTED
    assert fleet.submit("t1", "W", *ups[2]) == THROTTLED   # bucket empty
    vc.advance(2.0)                                        # refill 2
    assert fleet.submit("t1", "W", *ups[2]) == ADMITTED
    assert fleet.submit("t1", "W", *ups[3]) == QUEUE_FULL  # log at cap 3
    t = fleet.registry.get("t1")
    assert t.stats.decisions == {ADMITTED: 3, THROTTLED: 1, QUEUE_FULL: 1}
    with pytest.raises(KeyError):
        fleet.submit("t1", "nope", *ups[0])


# ---------------------------------------------------------------------------
# the claim/commit protocol
# ---------------------------------------------------------------------------

def test_commit_is_bit_identical_to_isolated_engine():
    vc = VClock()
    fleet = FleetScheduler(FleetConfig(lease_ttl=1.0), clock=vc,
                           sleep=vc.sleep)
    prog, inputs = _ols_tenant()
    tenant = fleet.add_tenant(TenantSpec("acme", prog, {"X": 1}), inputs)
    rng = np.random.default_rng(1)
    by_lsn = {}
    for i in range(7):
        u, v = _rank1(rng, 24, 6)
        assert fleet.submit("acme", "X", u, v) == ADMITTED
        by_lsn[i + 1] = (u, v)
    fleet.run_until_idle(workers=2, on_stall=lambda: vc.advance(1.1))
    assert not tenant.dirty()
    assert tenant.stats.committed_updates == 7
    ref = _replay_reference(tenant, inputs, by_lsn)
    assert max_abs_diff(tenant.committed_views, ref.views) == 0.0


def test_worker_crash_replay_exactly_once():
    vc = VClock()
    # crash every claim until we disarm the monkey
    fleet = FleetScheduler(
        FleetConfig(lease_ttl=1.0,
                    chaos=ChaosConfig(seed=0, worker_crash_p=1.0)),
        clock=vc, sleep=vc.sleep)
    prog, inputs = _logit_tenant()
    tenant = fleet.add_tenant(TenantSpec("t1", prog, {"W": 1}), inputs)
    rng = np.random.default_rng(2)
    by_lsn = {}
    for i in range(5):
        u, v = _rank1(rng, 5, 4)
        fleet.submit("t1", "W", u, v)
        by_lsn[i + 1] = (u, v)
    committed_before = dict(tenant.committed_views)
    with pytest.raises(WorkerCrashed):
        fleet.run_claim("w1")
    # the dead claim left its lease and uncommitted engine state behind
    assert tenant.inflight is not None
    assert fleet.leases.holder("t1") is not None
    assert tenant.applied_lsn == 0
    # committed reads never saw any of it
    assert max_abs_diff(tenant.committed_views, committed_before) == 0.0
    # TTL not yet expired: nobody can reclaim
    assert fleet.run_claim("w2") == "idle"
    vc.advance(1.5)
    fleet.chaos = None                 # second incarnation is healthy
    assert fleet.run_claim("w2") == "committed"
    assert tenant.stats.replays == 1   # rolled the dead claim back
    assert fleet.leases.stats()["reclaims"] == 1
    assert tenant.stats.committed_updates == 5   # exactly once
    assert not tenant.dirty()
    ref = _replay_reference(tenant, inputs, by_lsn)
    assert max_abs_diff(tenant.committed_views, ref.views) == 0.0


def test_lease_expiry_fences_commit_and_rolls_back():
    vc = VClock()
    fleet = FleetScheduler(
        FleetConfig(lease_ttl=1.0,
                    chaos=ChaosConfig(seed=0, lease_expiry_p=1.0)),
        clock=vc, sleep=vc.sleep)
    prog, inputs = _logit_tenant()
    tenant = fleet.add_tenant(TenantSpec("t1", prog, {"W": 1}), inputs)
    rng = np.random.default_rng(3)
    u, v = _rank1(rng, 5, 4)
    fleet.submit("t1", "W", u, v)
    assert fleet.run_claim("w1") == "fenced"
    # fenced claims roll their own work back: nothing applied,
    # nothing committed, log intact for the next worker
    assert tenant.stats.fenced_aborts == 1
    assert tenant.applied_lsn == 0 and tenant.dirty()
    assert tenant.inflight is None
    fleet.chaos = None
    assert fleet.run_claim("w2") == "committed"
    assert tenant.stats.committed_updates == 1   # exactly once
    ref = _replay_reference(tenant, inputs, {1: (u, v)})
    assert max_abs_diff(tenant.committed_views, ref.views) == 0.0


def test_max_claim_rank_bounds_one_claim():
    vc = VClock()
    fleet = FleetScheduler(FleetConfig(lease_ttl=1.0), clock=vc,
                           sleep=vc.sleep)
    prog, inputs = _logit_tenant()
    tenant = fleet.add_tenant(
        TenantSpec("t1", prog, {"W": 1}, max_claim_rank=3), inputs)
    rng = np.random.default_rng(4)
    for _ in range(8):
        fleet.submit("t1", "W", *_rank1(rng, 5, 4))
    assert fleet.run_claim("w1") == "committed"
    assert tenant.applied_lsn == 3          # capped claim
    assert tenant.stats.committed_updates == 3
    fleet.run_until_idle(on_stall=lambda: vc.advance(1.1))
    assert tenant.applied_lsn == 8 and not tenant.dirty()


# ---------------------------------------------------------------------------
# the bit-identical N-tenant property + chaos acceptance
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_fleet_property_bit_identical_to_isolated_engines(seed):
    """N tenants under interleaved updates, worker crashes, and lease
    expiries produce committed stores bit-identical to N isolated
    single-tenant engines replaying each tenant's committed groups —
    which is simultaneously the exactly-once proof and the
    no-cross-tenant-contamination proof."""
    vc = VClock()
    fleet = FleetScheduler(
        FleetConfig(lease_ttl=1.0,
                    chaos=ChaosConfig(seed=seed, worker_crash_p=0.2,
                                      lease_expiry_p=0.2)),
        clock=vc, sleep=vc.sleep)
    specs = {}
    tenant_inputs = {}
    # two same-program tenants (they share compiled triggers) + one
    # distinct-shape tenant
    for i, (m, d, p) in enumerate([(8, 4, 5), (8, 4, 5), (6, 3, 4)]):
        tid = f"t{i}"
        prog, inputs = _logit_tenant(m, d, p, seed=i)
        specs[tid] = (prog, (p, d))
        tenant_inputs[tid] = inputs
        # small claims → many claims → many chaos draws per run
        fleet.add_tenant(TenantSpec(tid, prog, {"W": 1},
                                    max_claim_rank=4), inputs)
    rng = np.random.default_rng(seed + 100)
    by_lsn = {tid: {} for tid in specs}
    lsn = {tid: 0 for tid in specs}
    outcomes = {}
    for step in range(60):
        tid = f"t{rng.integers(3)}"
        p, d = specs[tid][1]
        u, v = _rank1(rng, p, d)
        assert fleet.submit(tid, "W", u, v) == ADMITTED
        lsn[tid] += 1
        by_lsn[tid][lsn[tid]] = (u, v)
        if step % 10 == 9:             # interleave refresh with ingest
            for k, n in fleet.run_until_idle(
                    workers=3,
                    on_stall=lambda: vc.advance(1.1)).items():
                outcomes[k] = outcomes.get(k, 0) + n
    for k, n in fleet.run_until_idle(workers=3,
                                     on_stall=lambda: vc.advance(1.1)
                                     ).items():
        outcomes[k] = outcomes.get(k, 0) + n
    total_committed = 0
    for tid, (prog, _) in specs.items():
        tenant = fleet.registry.get(tid)
        assert not tenant.dirty()
        assert tenant.stats.committed_updates == lsn[tid]  # exactly once
        ref = _replay_reference(tenant, tenant_inputs[tid], by_lsn[tid])
        assert max_abs_diff(tenant.committed_views, ref.views) == 0.0
        total_committed += tenant.stats.committed_updates
    assert total_committed == 60
    # chaos actually happened on every seed at these probabilities
    assert fleet.chaos.worker_crashes + fleet.chaos.lease_expiries > 0
    assert outcomes.get("committed", 0) > 0
    # same-program tenants shared compiled triggers
    assert fleet.registry.trigger_cache.stats()["hits"] > 0


@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_fleet_chaos_acceptance_500_firings(seed):
    """The ISSUE acceptance run: ~500 submissions across a mixed fleet
    under worker crashes, lease expiry, slow workers, poisoned updates,
    and queue-pressure overload.  Invariants: exactly-once commit
    accounting per tenant, no cross-tenant contamination (bit-identical
    per-tenant replay), and final committed views consistent with full
    re-evaluation from the tenant's own inputs."""
    vc = VClock()
    fleet = FleetScheduler(
        FleetConfig(lease_ttl=1.0,
                    overload=OverloadPolicy(degraded_at=0.7,
                                            shedding_at=0.9,
                                            cold_after_s=1e9),
                    chaos=ChaosConfig(seed=seed, worker_crash_p=0.1,
                                      lease_expiry_p=0.1,
                                      slow_worker_p=0.05,
                                      slow_worker_s=1.5,   # > lease TTL
                                      poison_p=0.02)),
        clock=vc, sleep=vc.sleep)
    shapes = {}
    tenant_inputs = {}
    # 3 linear logit-view tenants (two share a program) + 2 OLS tenants
    for i, (m, d, p) in enumerate([(8, 4, 5), (8, 4, 5), (6, 3, 4)]):
        tid = f"logit{i}"
        prog, inputs = _logit_tenant(m, d, p, seed=i)
        fleet.add_tenant(TenantSpec(tid, prog, {"W": 1}, slo_s=0.5,
                                    queue_capacity=64), inputs)
        shapes[tid] = ("W", (p, d))
        tenant_inputs[tid] = inputs
    for i, (m, n) in enumerate([(24, 6), (16, 4)]):
        tid = f"ols{i}"
        prog, inputs = _ols_tenant(m, n, 1, seed=10 + i)
        fleet.add_tenant(TenantSpec(tid, prog, {"X": 1}, slo_s=0.5,
                                    queue_capacity=64), inputs)
        shapes[tid] = ("X", (m, n))
        tenant_inputs[tid] = inputs
    tids = sorted(shapes)
    rng = np.random.default_rng(seed + 7)
    by_lsn = {tid: {} for tid in tids}
    admitted = {tid: 0 for tid in tids}
    submitted = 0
    for step in range(500):
        tid = tids[int(rng.integers(len(tids)))]
        input_name, (n, m) = shapes[tid]
        u, v = _rank1(rng, n, m, scale=0.05)
        decision = fleet.submit(tid, input_name, u, v)
        submitted += 1
        if decision == ADMITTED:
            admitted[tid] += 1
            # the LOG's values are what count (post-poisoning), so
            # read the entry back for the replay reference
            entry = fleet.registry.get(tid).log.pending(0)[-1]
            by_lsn[tid][entry.lsn] = (entry.u, entry.v)
        vc.advance(0.01)
        if step % 25 == 24:            # interleave refresh with ingest
            fleet.run_until_idle(workers=3,
                                 on_stall=lambda: vc.advance(1.1))
    fleet.run_until_idle(workers=3, on_stall=lambda: vc.advance(1.1))
    assert sum(admitted.values()) > 400   # queue pressure, not collapse
    for tid in tids:
        tenant = fleet.registry.get(tid)
        assert not tenant.dirty()
        # exactly-once: every admitted update is committed exactly once
        assert tenant.stats.committed_updates == admitted[tid], tid
        assert tenant.applied_lsn == admitted[tid]
        # no contamination: bit-identical to this tenant's own replay
        ref = _replay_reference(tenant, tenant_inputs[tid], by_lsn[tid])
        assert max_abs_diff(tenant.committed_views, ref.views) == 0.0, tid
        # consistency: committed views match re-evaluation from the
        # tenant's own (updated) inputs.  Linear views are tight;
        # OLS goes through an f32 inverse (repo-standard tolerance).
        fresh = IncrementalEngine(tenant.spec.program)
        fresh.initialize({k: np.asarray(tenant.committed_views[k])
                          for k in tenant.spec.program.inputs})
        for name in fresh.program.outputs:
            got = np.asarray(tenant.committed_views[name])
            want = np.asarray(fresh.views[name])
            tol = 1e-6 if tid.startswith("logit") else 2e-3
            np.testing.assert_allclose(got, want, rtol=tol,
                                       atol=tol * np.abs(want).max())
    # the fault mix actually fired
    assert fleet.chaos.worker_crashes > 0
    assert fleet.chaos.lease_expiries + fleet.leases.stats()["broken"] >= 0
    assert fleet.chaos.poisoned > 0
    stats = fleet.fleet_stats()
    assert stats["replays"] + stats["fenced_aborts"] > 0
    assert stats["trigger_cache"]["hits"] > 0


# ---------------------------------------------------------------------------
# overload tiers + degradation
# ---------------------------------------------------------------------------

def test_overload_tiers_shed_and_reeval_on_read():
    vc = VClock()
    fleet = FleetScheduler(
        FleetConfig(lease_ttl=1.0,
                    overload=OverloadPolicy(degraded_at=0.5,
                                            shedding_at=0.75,
                                            cold_after_s=2.0)),
        clock=vc, sleep=vc.sleep)
    prog0, inputs0 = _logit_tenant(seed=0)
    prog1, inputs1 = _logit_tenant(seed=1)
    fleet.add_tenant(TenantSpec("cold", prog0, {"W": 1}, queue_capacity=4),
                     inputs0)
    fleet.add_tenant(TenantSpec("vip", prog1, {"W": 1}, queue_capacity=4,
                                sheddable=False), inputs1)
    rng = np.random.default_rng(5)
    ups = [_rank1(rng, 5, 4) for _ in range(8)]
    assert fleet.tier() == "normal"
    vc.advance(3.0)                     # both tenants go cold
    for i in range(3):                  # load 3/8 → normal; 4/8 → degraded
        fleet.submit("cold", "W", *ups[i])
    assert fleet.tier() == "normal"
    fleet.submit("cold", "W", *ups[3])
    assert fleet.tier() == "degraded"
    cold = fleet.registry.get("cold")
    vip = fleet.registry.get("vip")
    assert cold.mode == "reeval_on_read"   # cold + sheddable → degraded
    assert vip.mode == "incremental"       # reserved capacity is spared
    for i in range(2):
        fleet.submit("vip", "W", *ups[4 + i])
    assert fleet.tier() == "shedding"      # 6/8
    assert fleet.submit("cold", "W", *ups[6]) == SHED
    assert fleet.submit("vip", "W", *ups[7]) == ADMITTED  # not sheddable
    # a degraded tenant is not scheduled; its pending deltas fold in on
    # the READ, via the same lease/commit protocol
    assert all(t.spec.tenant_id != "cold" for t in fleet._claimable())
    y = np.asarray(fleet.read("cold", "Y"))
    assert cold.stats.reeval_on_read == 1
    assert not cold.dirty()
    W = np.asarray(inputs0["W"])
    for i in range(4):
        u, v = ups[i]
        W = W + u @ v.T
    np.testing.assert_allclose(y, inputs0["H"] @ W.T, rtol=1e-5, atol=1e-5)
    # drain the vip tenant; fleet cools down and modes recover
    fleet.run_until_idle(on_stall=lambda: vc.advance(1.1))
    fleet.submit("cold", "W", *ups[7])     # any submit re-applies tiers
    assert fleet.tier() == "normal"
    assert cold.mode == "incremental"


def test_noisy_neighbor_quarantine_and_probe():
    vc = VClock()
    fleet = FleetScheduler(FleetConfig(lease_ttl=1.0), clock=vc,
                           sleep=vc.sleep)
    prog_bad, inputs_bad = _logit_tenant(seed=0)
    prog_ok, inputs_ok = _logit_tenant(seed=1)
    # every firing of the bad tenant's engine raises (injected fault);
    # the guard aborts + quarantines, the fleet's breaker opens
    fleet.add_tenant(
        TenantSpec("bad", prog_bad, {"W": 1},
                   chaos=ChaosConfig(seed=0, trigger_raise_p=1.0),
                   breaker_threshold=2, breaker_reset_s=10.0),
        inputs_bad)
    tenant_ok = fleet.add_tenant(TenantSpec("ok", prog_ok, {"W": 1}),
                                 inputs_ok)
    bad = fleet.registry.get("bad")
    last_good = dict(bad.committed_views)
    rng = np.random.default_rng(6)
    for _ in range(2):
        fleet.submit("bad", "W", *_rank1(rng, 5, 4))
        fleet.submit("ok", "W", *_rank1(rng, 5, 4))
        out = fleet.run_until_idle(on_stall=lambda: vc.advance(1.1))
        assert out.get("quarantined", 0) >= 1
    # two all-aborted claims → breaker open → tenant unschedulable
    assert bad.breaker.state == "open"
    assert bad.stats.aborted_claims == 2
    assert len(bad.engine.guard.quarantine) > 0
    fleet.submit("bad", "W", *_rank1(rng, 5, 4))
    assert fleet.run_claim("w1") == "idle"     # quarantined, skipped
    # reads still serve the last-good committed snapshot
    assert max_abs_diff({"Y": fleet.read("bad", "Y")},
                        {"Y": last_good["Y"]}) == 0.0
    # the healthy tenant was never affected
    assert tenant_ok.stats.commits == 2 and not tenant_ok.dirty()
    # after the reset window, ONE probe claim is admitted (half-open)
    vc.advance(11.0)
    assert bad.breaker.state == "half_open"
    assert fleet.run_claim("w1") == "quarantined"   # probe fails again
    assert bad.breaker.state == "open"


def test_thread_mode_smoke():
    """Live worker threads (real clock): submit, drain, verify."""
    # generous TTL: the first claim pays jit compile on a cold cache,
    # and a fenced retry (while harmless) would make the test slower
    fleet = FleetScheduler(FleetConfig(lease_ttl=10.0, workers=2))
    prog, inputs = _logit_tenant()
    tenant = fleet.add_tenant(TenantSpec("t1", prog, {"W": 1}), inputs)
    rng = np.random.default_rng(7)
    by_lsn = {}
    fleet.start()
    try:
        for i in range(12):
            u, v = _rank1(rng, 5, 4)
            assert fleet.submit("t1", "W", u, v) == ADMITTED
            by_lsn[i + 1] = (u, v)
        fleet.drain(["t1"], timeout_s=60.0)
    finally:
        fleet.stop()
    assert not tenant.dirty()
    assert tenant.stats.committed_updates == 12
    ref = _replay_reference(tenant, inputs, by_lsn)
    assert max_abs_diff(tenant.committed_views, ref.views) == 0.0


def test_thread_worker_error_is_counted():
    """A tenant whose firing raises does not kill the worker pool, and
    the error is counted and shown in fleet_stats()."""
    import time
    fleet = FleetScheduler(FleetConfig(lease_ttl=0.05, workers=1))
    prog, inputs = _logit_tenant()
    tenant = fleet.add_tenant(TenantSpec("t1", prog, {"W": 1}), inputs)

    def explode(*a, **k):
        raise RuntimeError("firing exploded")

    tenant.engine.apply_updates = explode
    rng = np.random.default_rng(3)
    fleet.start()
    try:
        assert fleet.submit("t1", "W", *_rank1(rng, 5, 4)) == ADMITTED
        deadline = time.monotonic() + 30.0
        while fleet.worker_errors == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        fleet.stop()
    stats = fleet.fleet_stats()
    assert stats["worker_errors"] >= 1
    assert "firing exploded" in stats["last_worker_error"]
    assert tenant.dirty() and tenant.stats.commits == 0


def test_serve_engine_attach_fleet():
    """ServeEngine routes hot-swap deltas / reads / health through a
    fleet-backed logit view."""
    pytest.importorskip("jax")
    import jax
    from repro.launch.train import custom_10m
    from repro.models import build_model
    from repro.serve import ServeEngine

    cfg = custom_10m()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, batch_size=1, max_seq=32)
    rng = np.random.default_rng(8)
    m, d, p = 6, cfg.d_model, 16
    prog = build_logit_view_program(m, d, p)
    inputs = {"H": rng.standard_normal((m, d)).astype(np.float32),
              "W": (rng.standard_normal((p, d)) * 0.1).astype(np.float32)}
    fleet = FleetScheduler(FleetConfig(lease_ttl=2.0))
    fleet.add_tenant(TenantSpec("acme", prog, {"W": 1}), inputs)
    eng.attach_fleet(fleet, {"lm_head": "acme"})
    u, v = _rank1(rng, p, d, scale=0.01)
    assert eng.hot_swap("lm_head", u, v)       # admitted into the log
    eng.flush_views()                          # drains the fleet inline
    y = np.asarray(eng.view_logits("lm_head"))
    W = np.asarray(inputs["W"]) + u @ v.T
    np.testing.assert_allclose(y, inputs["H"] @ W.T, rtol=1e-5, atol=1e-5)
    health = eng.view_health()["lm_head"]
    assert health["tenant"] == "acme" and not health["dirty"]
    with pytest.raises(ValueError):
        eng.attach_fleet(fleet, {"layers.0.mlp": "acme"})


# ---------------------------------------------------------------------------
# satellite: thread-safe TriggerCache
# ---------------------------------------------------------------------------

def test_same_program_tenants_reuse_the_first_tenants_triggers():
    """Tenants registered after the first, on the same program, get
    every trigger from the fleet's shared cache: they build none, the
    cache misses stay at the first tenant's, and each still commits
    views bit-identical to its own replay."""
    fleet = FleetScheduler(FleetConfig(lease_ttl=60.0))
    cache = fleet.registry.trigger_cache
    rng = np.random.default_rng(5)
    tenant_inputs, by_lsn = {}, {}

    def bring_up(tid, seed):
        prog, inputs = _logit_tenant(seed=seed)
        tenant_inputs[tid] = inputs
        fleet.add_tenant(TenantSpec(tid, prog, {"W": 1}, max_claim_rank=4),
                         inputs)
        by_lsn[tid] = {}
        for lsn in range(1, 5):
            u, v = _rank1(rng, 5, 4)
            assert fleet.submit(tid, "W", u, v) == ADMITTED
            by_lsn[tid][lsn] = (u, v)
        fleet.run_until_idle(workers=1)

    bring_up("t0", 0)
    first = fleet.registry.get("t0")
    assert first.engine.stats.trigger_builds > 0
    misses = cache.stats()["misses"]
    assert misses == first.engine.stats.trigger_builds
    for i in (1, 2):
        bring_up(f"t{i}", i)
    assert cache.stats()["misses"] == misses
    assert cache.stats()["hits"] > 0
    for tid in ("t1", "t2"):
        tenant = fleet.registry.get(tid)
        assert tenant.engine.stats.trigger_builds == 0
        assert tenant.stats.committed_updates == 4
        assert all(tenant.engine._trigger_fns[name] is fn for name, fn
                   in first.engine._trigger_fns.items())
        ref = _replay_reference(tenant, tenant_inputs[tid], by_lsn[tid])
        assert max_abs_diff(tenant.committed_views, ref.views) == 0.0


def test_trigger_cache_concurrent_access():
    cache = TriggerCache(capacity=8)
    built = []
    build_lock = threading.Lock()

    def builder(key):
        def make():
            with build_lock:
                built.append(key)
            return ("fn", key)
        return make

    errors = []
    results = {}

    def worker(wid):
        rng = np.random.default_rng(wid)
        try:
            for _ in range(200):
                key = ("k", int(rng.integers(16)))
                fn = cache.get_or_build(key, builder(key))
                assert fn[1] == key            # never someone else's fn
                _ = len(cache), key in cache, cache.stats()
                results[(wid, key)] = fn
        except Exception as e:                 # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    stats = cache.stats()
    assert stats["hits"] + stats["misses"] == 8 * 200
    assert stats["entries"] <= 8               # capacity respected
    assert stats["evictions"] >= stats["misses"] - 8


def test_trigger_cache_lru_eviction_and_evict():
    cache = TriggerCache(capacity=2)
    a = cache.get_or_build(("a",), lambda: "A")
    b = cache.get_or_build(("b",), lambda: "B")
    assert cache.get_or_build(("a",), lambda: "A2") == "A"   # hit, MRU
    cache.get_or_build(("c",), lambda: "C")    # evicts LRU = ("b",)
    assert ("b",) not in cache and ("a",) in cache
    assert cache.stats()["evictions"] == 1
    assert cache.evict(("a",)) and not cache.evict(("a",))
    assert len(cache) == 1
    with pytest.raises(ValueError):
        TriggerCache(capacity=0)


# ---------------------------------------------------------------------------
# satellite: chain-aware planner pricing
# ---------------------------------------------------------------------------

def test_chain_aware_pricing_demotes_lone_survivors():
    """When siblings re-evaluate, a lone incremental view bears the
    whole shared delta chain — chain-aware pricing must lower its
    effective crossover (never raise it)."""
    prog = build_ols_program(96, 12, 2)
    compiled = compile_program(prog, {"X": 1})
    base = plan_program(compiled, WorkloadDescriptor(update_rank=1,
                                                     batch_size=8))
    aware = plan_program(compiled, WorkloadDescriptor(update_rank=1,
                                                      batch_size=8,
                                                      chain_aware=True))
    order = {"reeval": 0, "hybrid": 1, "incremental": 2}
    demoted = 0
    for name, vp in aware.views.items():
        bp = base.views[name]
        assert order[vp.strategy] <= order[bp.strategy], name
        if vp.strategy != bp.strategy:
            demoted += 1
        if vp.strategy == "hybrid" and bp.strategy == "hybrid":
            assert vp.threshold_rank <= bp.threshold_rank
    assert demoted >= 1        # the chain price moved at least one view

    # a chain-aware plan still executes correctly
    rng = np.random.default_rng(9)
    inputs = {"X": rng.standard_normal((96, 12)).astype(np.float32),
              "Y": rng.standard_normal((96, 2)).astype(np.float32)}
    eng = IncrementalEngine(prog, {"X": 1}, plan=aware,
                            trigger_cache=TriggerCache())
    ref = IncrementalEngine(prog, {"X": 1})
    eng.initialize(inputs)
    ref.initialize(inputs)
    ups = [_rank1(rng, 96, 12, scale=0.05) for _ in range(4)]
    eng.apply_updates("X", ups)
    ref.apply_updates("X", ups)
    eng.refresh()
    for name in prog.outputs:
        np.testing.assert_allclose(np.asarray(eng.views[name]),
                                   np.asarray(ref.views[name]),
                                   rtol=2e-3, atol=2e-3)


def test_firing_cost_flops_prices_the_chain():
    prog = build_ols_program(96, 12, 2)
    compiled = compile_program(prog, {"X": 1})
    binding = dict(prog.dims)
    assign_flops, view_deps = trigger_chain_costs(
        compiled.triggers["X"], binding)
    assert all(c > 0 for c in assign_flops.values())
    c1 = firing_cost_flops(compiled, binding, "X", 1)
    c8 = firing_cost_flops(compiled, binding, "X", 8)
    assert c8 > c1 > 0                       # monotone in stacked rank
    # re-evaluating a view swaps its sweep for its reeval cost and can
    # only drop chain assigns, never add them
    views = [up.view for up in compiled.triggers["X"].updates
             if up.view in {s.target.name for s in prog.statements}]
    c_re = firing_cost_flops(compiled, binding, "X", 8,
                             reeval_views=frozenset(views[:1]))
    assert c_re != c8 and c_re > 0


# ---------------------------------------------------------------------------
# satellite: deterministic degrade (clock + jitter + single probe)
# ---------------------------------------------------------------------------

def test_retry_with_backoff_injectable_clock_and_deadline():
    vc = VClock()
    sleeps = []

    def sleep(dt):
        sleeps.append(dt)
        vc.advance(dt)

    calls = []

    def always_fails():
        calls.append(vc())
        raise RuntimeError("down")

    policy = DegradePolicy(max_retries=50, backoff_base=0.5,
                           backoff_max=8.0, retry_deadline=3.0,
                           full_jitter=False, jitter=0.0)
    rng = np.random.default_rng(0)
    with pytest.raises(RuntimeError):
        retry_with_backoff(always_fails, policy, rng, sleep=sleep,
                           clock=vc)
    # deadline bounded the loop long before 50 retries
    assert len(calls) < 10
    assert vc() <= 3.0 + 8.0               # never sleeps past the budget


def test_retry_full_jitter_decorrelates():
    vc = VClock()
    sleeps = []

    def sleep(dt):
        sleeps.append(dt)
        vc.advance(dt)

    def fails():
        raise RuntimeError("down")

    policy = DegradePolicy(max_retries=6, backoff_base=1.0,
                           backoff_max=4.0, full_jitter=True)
    with pytest.raises(RuntimeError):
        retry_with_backoff(fails, policy, np.random.default_rng(1),
                           sleep=sleep, clock=vc)
    assert len(sleeps) == 6                # one pause per retry
    # full jitter: uniform in [0, min(base·2^i, cap)] — all draws in
    # range, and (statistically certain for this seed) not lock-step
    caps = [min(1.0 * 2 ** i, 4.0) for i in range(len(sleeps))]
    assert all(0.0 <= s <= c for s, c in zip(sleeps, caps))
    assert len({round(s / c, 6) for s, c in zip(sleeps, caps)}) > 1


def test_breaker_half_open_single_probe():
    vc = VClock()
    br = CircuitBreaker(threshold=2, reset_timeout=5.0, clock=vc)
    assert br.allow()
    br.record_failure()
    br.record_failure()
    assert br.state == "open" and not br.allow()
    vc.advance(5.0)
    assert br.state == "half_open"
    assert br.allow()                      # the single probe
    assert not br.allow()                  # concurrent caller: wait
    br.record_failure()                    # probe failed → open again
    assert br.state == "open"
    vc.advance(5.0)
    assert br.allow()
    br.record_success()                    # probe succeeded → closed
    assert br.state == "closed" and br.allow()


def test_breaker_abandoned_probe_rearms():
    vc = VClock()
    br = CircuitBreaker(threshold=1, reset_timeout=2.0, clock=vc)
    br.record_failure()
    vc.advance(2.0)
    assert br.allow()                      # probe claimed …
    assert not br.allow()                  # … and in flight
    vc.advance(2.0)                        # prober crashed; window re-arms
    assert br.allow()

# ---------------------------------------------------------------------------
# higher-order (deferred-cascade) tenants under fleet chaos (ISSUE 8)
# ---------------------------------------------------------------------------

def _replay_with_opts(tenant, inputs, updates_by_lsn):
    """Isolated replay honoring the tenant's engine_opts (order,
    fold_window, …) — a deferred tenant must be replayed by a deferred
    engine for bit-identity to be achievable."""
    ref = IncrementalEngine(tenant.spec.program, tenant.spec.update_ranks,
                            guard=tenant.spec.guarded or None,
                            **tenant.spec.engine_opts)
    ref.initialize(inputs)
    for input_name, lsns in tenant.commit_log:
        assert input_name != "<reeval>", "differential test must not degrade"
        ref.apply_updates(input_name,
                          [updates_by_lsn[l] for l in lsns])
    return ref


@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_fleet_higher_order_chaos_bit_identical_and_exact(seed):
    """ISSUE 8 differential: a 5-tenant fleet in which two tenants run
    order-2 deferred engines (``TenantSpec.engine_opts``) under worker
    crashes, lease expiry, and poison.  Invariants: exactly-once
    commit accounting; committed stores bit-identical to same-order
    isolated replays (aborted/replayed firings never tick a cascade
    window twice); and, after a fold barrier, numeric agreement with a
    clean FIRST-order replay of the same committed groups."""
    vc = VClock()
    fleet = FleetScheduler(
        FleetConfig(lease_ttl=1.0,
                    chaos=ChaosConfig(seed=seed, worker_crash_p=0.15,
                                      lease_expiry_p=0.1, poison_p=0.02)),
        clock=vc, sleep=vc.sleep)
    from repro.apps.matrix_powers import build_powers_program
    shapes, tenant_inputs = {}, {}
    rng0 = np.random.default_rng(99)
    for i in range(3):   # two deferred tenants + one first-order control
        tid = f"pow{i}"
        prog = build_powers_program(k=4, n=10, model="exp")
        a = rng0.standard_normal((10, 10)).astype(np.float32)
        a *= 0.5 / max(abs(np.linalg.eigvals(a)))
        opts = {"order": 2, "fold_window": 2} if i < 2 else {}
        fleet.add_tenant(TenantSpec(tid, prog, {"A": 1}, max_claim_rank=4,
                                    engine_opts=opts), {"A": a})
        shapes[tid] = ("A", (10, 10))
        tenant_inputs[tid] = {"A": a}
    for i, (m, d, p) in enumerate([(8, 4, 5), (6, 3, 4)]):
        tid = f"logit{i}"
        prog, inputs = _logit_tenant(m, d, p, seed=i)
        fleet.add_tenant(TenantSpec(tid, prog, {"W": 1},
                                    max_claim_rank=4), inputs)
        shapes[tid] = ("W", (p, d))
        tenant_inputs[tid] = inputs
    assert fleet.registry.get("pow0").engine._deferred
    assert not fleet.registry.get("pow2").engine._deferred

    tids = sorted(shapes)
    rng = np.random.default_rng(seed + 5)
    by_lsn = {tid: {} for tid in tids}
    admitted = {tid: 0 for tid in tids}
    for step in range(150):
        tid = tids[int(rng.integers(len(tids)))]
        input_name, (n, m) = shapes[tid]
        u, v = _rank1(rng, n, m, scale=0.02)
        if fleet.submit(tid, input_name, u, v) == ADMITTED:
            admitted[tid] += 1
            entry = fleet.registry.get(tid).log.pending(0)[-1]
            by_lsn[tid][entry.lsn] = (entry.u, entry.v)
        vc.advance(0.01)
        if step % 25 == 24:
            fleet.run_until_idle(workers=3,
                                 on_stall=lambda: vc.advance(1.1))
    fleet.run_until_idle(workers=3, on_stall=lambda: vc.advance(1.1))
    assert fleet.chaos.worker_crashes + fleet.chaos.lease_expiries > 0

    for tid in tids:
        tenant = fleet.registry.get(tid)
        assert not tenant.dirty()
        assert tenant.stats.committed_updates == admitted[tid], tid
        ref = _replay_with_opts(tenant, tenant_inputs[tid], by_lsn[tid])
        assert max_abs_diff(tenant.committed_views, ref.views) == 0.0, tid
        # fold barrier, then the first-order differential.  5e-6
        # scale-normalized: two float32 maintenance paths (per-firing
        # sweeps vs window folds) drift apart by a few ulps per firing.
        views = dict(tenant.engine.flush())
        first = IncrementalEngine(tenant.spec.program,
                                  tenant.spec.update_ranks,
                                  guard=tenant.spec.guarded or None)
        first.initialize(tenant_inputs[tid])
        for input_name, lsns in tenant.commit_log:
            first.apply_updates(input_name,
                                [by_lsn[tid][l] for l in lsns])
        for st in tenant.spec.program.statements:
            name = st.target.name
            want = np.asarray(first.views[name], np.float64)
            got = np.asarray(views[name], np.float64)
            err = np.abs(got - want).max() / max(np.abs(want).max(), 1.0)
            assert err <= 5e-6, f"{tid}/{name}: {err:.2e}"
    # deferred tenants actually exercised the cascade under chaos
    assert fleet.registry.get("pow0").engine.stats.folds > 0 or \
        fleet.registry.get("pow1").engine.stats.folds > 0

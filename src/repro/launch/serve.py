"""Serving driver: batched generation with the ServeEngine.

  PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-7b \
      --reduced --batch 4 --max-new 16
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.models import build_model
from repro.serve import ServeEngine
from .compile_cache import enable_compile_cache
from .train import custom_10m, custom_100m


def serve_fivm(args) -> None:
    """Models-as-views serving (docs/fivm.md): data arrival and model
    refresh are decoupled — ingest banks factored deltas into the
    ring's deferred windows, each read folds and re-solves — and the
    same ring shape runs as a fleet tenant so staleness is accounted
    against the tenant SLO."""
    from repro.apps import get_app
    from repro.data import labeled_stream
    from repro.fivm.registry import RingRegistry, submit_event
    from repro.fleet import FleetConfig, FleetScheduler

    app = get_app("fivm_learning")(
        features=args.fivm_features, capacity=args.fivm_capacity,
        order=2, churn=0.3)
    app.ingest(8)
    app.refresh()          # compile + first solve outside the ledger
    out = app.serve_demo(bursts=args.fivm_bursts,
                         burst_size=args.fivm_burst_size)
    print(f"[serve] fivm decoupled ring: {out['events']} events "
          f"({out['live']:.0f} live), "
          f"ingest {out['ingest_us_per_event']:.0f} us/event, "
          f"reads {[f'{t:.1f}ms' for t in out['read_ms']]}, "
          f"folds={out['folds']} strategies={out['strategies']}")

    # fleet-hosted ring tenant: same carriers, lease-claimed refresh,
    # SLO staleness accounting
    spec = app.spec
    fleet = FleetScheduler(FleetConfig(lease_ttl=0.5,
                                       workers=args.fleet_workers))
    reg = RingRegistry()
    reg.add_fleet_tenant(fleet, spec, "fivm-ring", slo_s=0.5)
    stream = labeled_stream(spec.features, targets=spec.targets,
                            capacity=spec.capacity, churn=0.3, seed=1)
    fleet.start()
    try:
        t0 = time.perf_counter()
        n = args.fivm_bursts * args.fivm_burst_size
        for ev in stream.events(n):
            submit_event(fleet, "fivm-ring", spec.capacity, ev)
        fleet.drain(["fivm-ring"])
        dt = time.perf_counter() - t0
        G = fleet.read_views("fivm-ring")["G"]
        health = fleet.tenant_health()[0]
        print(f"[serve] fivm fleet tenant: {n} events in {dt:.2f}s "
              f"({3 * n / dt:.0f} firings/s), G={tuple(G.shape)}, "
              f"staleness={health['staleness_s']:.3f}s "
              f"(slo={health['slo_s']}s) health={health}")
    finally:
        fleet.stop()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="custom-10m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=512)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--logit-view", action="store_true",
                    help="attach a guarded incremental lm_head logit "
                         "view, drive hot-swap deltas through it, and "
                         "print per-view serving health")
    ap.add_argument("--corpus", type=int, default=64,
                    help="--logit-view corpus size (cached hidden rows)")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="serve N fleet tenants (one logit view each) "
                         "through repro.fleet: lease-claimed refresh "
                         "workers, admission control, shared trigger "
                         "cache; prints fleet health + stats")
    ap.add_argument("--fleet-workers", type=int, default=2)
    ap.add_argument("--fivm", action="store_true",
                    help="serve the repro.fivm learning views instead "
                         "of token generation: a maintained gram ring "
                         "in decoupled (order=2, bank-on-ingest, "
                         "fold-on-read) mode, plus a fleet-hosted ring "
                         "tenant with SLO staleness accounting")
    ap.add_argument("--fivm-features", type=int, default=24)
    ap.add_argument("--fivm-capacity", type=int, default=256)
    ap.add_argument("--fivm-bursts", type=int, default=8)
    ap.add_argument("--fivm-burst-size", type=int, default=48)
    args = ap.parse_args()
    enable_compile_cache()

    if args.fivm:
        serve_fivm(args)
        return

    if args.arch == "custom-10m":
        cfg = custom_10m()
    elif args.arch == "custom-100m":
        cfg = custom_100m()
    else:
        cfg = get_config(args.arch)
        cfg = cfg.reduced() if args.reduced else cfg
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    degrade = None
    if args.logit_view:
        from repro.guard import DegradePolicy
        degrade = DegradePolicy()
    eng = ServeEngine(model, params, batch_size=args.batch,
                      max_seq=args.max_seq, temperature=args.temperature,
                      degrade=degrade)
    rng = np.random.default_rng(0)
    if args.logit_view:
        # guarded corpus logit view over a synthetic cached-hidden corpus:
        # hot-swap a burst of lm_head deltas, then report serving health
        from repro.serve.incremental_views import IncrementalLogitView
        d = cfg.d_model
        hidden = rng.standard_normal((args.corpus, d)).astype(np.float32)
        head = rng.standard_normal((cfg.vocab, d)).astype(np.float32) * 0.02
        eng.attach_logit_view("lm_head",
                              IncrementalLogitView(hidden, head))
        for _ in range(8):
            u = rng.standard_normal((cfg.vocab, 1)).astype(np.float32) * .01
            v = rng.standard_normal((d, 1)).astype(np.float32) * .01
            eng.hot_swap("lm_head", u, v)
        eng.flush_views()
        logits = eng.view_logits("lm_head")
        print(f"[serve] logit view: {logits.shape} "
              f"health={eng.view_health()['lm_head']}")
    if args.fleet > 0:
        # multi-tenant serving: N tenants, each its own corpus logit
        # view, refreshed by a shared lease-coordinated worker pool.
        # Same-shape tenants share compiled triggers (fleet cache).
        from repro.fleet import FleetConfig, FleetScheduler, TenantSpec
        from repro.serve.incremental_views import build_logit_view_program
        d, p = cfg.d_model, cfg.vocab
        fleet = FleetScheduler(FleetConfig(lease_ttl=0.5,
                                           workers=args.fleet_workers))
        tenant_of = {}
        for i in range(args.fleet):
            tid = f"tenant-{i}"
            prog = build_logit_view_program(args.corpus, d, p)
            inputs = {
                "H": rng.standard_normal((args.corpus, d)
                                         ).astype(np.float32),
                "W": rng.standard_normal((p, d)).astype(np.float32) * .02,
            }
            fleet.add_tenant(TenantSpec(tid, prog, {"W": 1}, slo_s=0.25,
                                        quota_rate=200.0, quota_burst=32),
                             inputs)
            tenant_of[f"lm_head.{i}"] = tid
        eng.attach_fleet(fleet, tenant_of)
        fleet.start()
        try:
            for _ in range(8):
                for path in tenant_of:
                    u = rng.standard_normal((p, 1)).astype(np.float32) * .01
                    v = rng.standard_normal((d, 1)).astype(np.float32) * .01
                    eng.hot_swap(path, u, v)
            eng.flush_views()
            for path in tenant_of:
                logits = eng.view_logits(path)
                print(f"[serve] fleet view {path}: {logits.shape} "
                      f"health={eng.view_health()[path]}")
            print(f"[serve] fleet stats: {fleet.fleet_stats()}")
        finally:
            fleet.stop()
    prompts = rng.integers(1, cfg.vocab, size=(args.batch, args.prompt_len)
                           ).astype(np.int32)
    t0 = time.perf_counter()
    out = eng.generate(prompts, max_new=args.max_new)
    dt = time.perf_counter() - t0
    print(f"[serve] {cfg.name}: generated {out.shape} in {dt:.2f}s "
          f"({out.size/dt:.1f} tok/s)")
    print(out[:, :12])


if __name__ == "__main__":
    main()

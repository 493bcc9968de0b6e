#!/usr/bin/env python3
"""Smoke test: the view engine and the fleet, on one TPU chip.

Drives the system's main path once through its normal entry points, at a
size its users would call real, and checks every result against an
independent plain ``jax.numpy`` re-evaluation of the final inputs
(float32, ``precision=HIGHEST``):

  engine  the paper's §7 programs through ``IncrementalEngine``, once with
          ``apply_backend="xla"`` and once with ``"pallas"``: matrix
          powers (k=16, n=16384) and OLS (m=32768, n=4096, p=1).  Each is
          initialised, then takes 8 rank-1 row updates (``apply_update``),
          16 updates through ``enqueue_update`` + ``flush`` (stacked and
          re-compressed), and one batch of ``RowLocalCarrier`` updates
          touching under 1% of the rows.  The same three update paths
          then run on a feature chain ``Y1 = X·W1; Y2 = Y1·W2``
          (X 131072×1024), whose views are row-local, so the carrier
          batch takes the row-slab trigger (the ``rank_update_rows``
          kernel under ``"pallas"``) instead of widening.
  fleet   a ``FleetScheduler`` with 4 guarded matrix-powers tenants
          (n=4096, k=8) and live worker threads: 32 rank-1 updates per
          tenant through ``submit``, then ``drain``, ``read_views``, ``stop``.

Usage (from the checkout root; one process, it starts no other)::

    python chip_smoke.py                    # one TPU chip
    python chip_smoke.py --four-chips       # only: row-sharded views on 4 chips
    python chip_smoke.py --cpu-rehearsal    # tiny sizes, JAX_PLATFORMS=cpu
    python chip_smoke.py --cpu-rehearsal --four-chips   # 4 virtual CPU devices

The last line of standard output is ``{"ok": true, "device": {...}}``.  Any
mismatch, exception, guard rollback/abort/quarantine, fleet worker error,
Pallas fallback or fleet tier other than ``normal`` exits non-zero without
that line, as does a run that finds no TPU (outside ``--cpu-rehearsal``).
Lines starting with ``info:`` are information, not metrics.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

# Engine and reference both round in float32: ~1e-6 of the largest entry
# each at these widths (n ≤ 4096 measured on the CPU, flat in n), so 1e-5
# relative to the largest reference entry leaves a 3x margin over the sum.
TOL = 1e-5

FULL = dict(powers_n=16384, powers_k=16, ols_m=32768, ols_n=4096,
            chain_n=131072, chain_m=1024, chain_k=512,
            fleet_n=4096, fleet_k=8)
REHEARSAL = dict(powers_n=256, powers_k=16, ols_m=512, ols_n=64,
                 chain_n=1024, chain_m=128, chain_k=128,
                 fleet_n=128, fleet_k=8)
FLEET_TENANTS = 4
FLEET_UPDATES = 32            # per tenant


def info(msg: str) -> None:
    print(f"info: {msg}", flush=True)


class Smoke:
    """Runs the phases and collects every failed check."""

    def __init__(self, sizes, seed: int):
        import jax
        import numpy as np
        self.jax, self.np = jax, np
        self.sizes = sizes
        self.seed = seed
        self.failures = []
        # one fused program: no view-sized temporaries on the device
        self._rel_err = jax.jit(lambda got, want: (
            jax.numpy.max(jax.numpy.abs(got - want))
            / jax.numpy.max(jax.numpy.abs(want))))

    # -- checks ----------------------------------------------------------------
    def expect(self, cond: bool, what: str) -> None:
        if not cond:
            self.failures.append(what)
            print(f"FAIL: {what}", flush=True)

    def compare(self, label: str, got, want) -> float:
        """Max |got − want| over max |want|, checked against TOL."""
        err = float(self._rel_err(got, want))
        ok = err <= TOL
        print(f"{label}: max rel err {err:.3e} (tol {TOL:.0e}) "
              f"{'ok' if ok else 'MISMATCH'}", flush=True)
        self.expect(ok, f"{label} max rel err {err:.3e} > {TOL:.0e}")
        return err

    def memory(self, phase: str) -> None:
        stats = self.jax.devices()[0].memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            info(f"{phase}: peak_bytes_in_use "
                 f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB")

    def mesh_peaks(self, devices) -> None:
        """Each chip's peak after the row-sharded engine: the input goes
        to its row blocks and the views are computed there, so no chip
        (chip 0 least of all) holds a whole view."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devices]
        if None in peaks:
            return
        info("four-chips peak_bytes_in_use per chip: " + ", ".join(
            f"{p / 2**30:.2f}" for p in peaks) + " GiB")
        spread = (max(peaks) - min(peaks)) / 2**30
        self.expect(spread <= 0.5, f"four-chips peaks differ by "
                                   f"{spread:.2f} GiB across chips")

    # -- update streams (host float64 mirror of the input) -----------------------
    def row_updates(self, rng, mirror, count, scale, rows=None):
        """Rank-1 row updates ``X[r] += d``, applied to the mirror too."""
        np = self.np
        m, n = mirror.shape
        out = []
        for i in range(count):
            r = int(rng.integers(m)) if rows is None else int(rows[i])
            d = (rng.standard_normal(n) * scale).astype(np.float32)
            u = np.zeros((m, 1), np.float32)
            u[r, 0] = 1.0
            mirror[r] += d
            out.append((u, d[:, None]))
        return out

    def carriers(self, rng, mirror, scale):
        """4 rank-1 ``RowLocalCarrier`` updates covering one aligned
        window of under 1% of the rows."""
        from repro.core.factored import RowLocalCarrier
        np = self.np
        m, n = mirror.shape
        window = max(4, m // 128)
        start = int(rng.integers(m // window)) * window
        out = []
        for part in np.split(np.arange(start, start + window), 4):
            block = rng.standard_normal((part.size, 1)).astype(np.float32)
            V = (rng.standard_normal((n, 1)) * scale).astype(np.float32)
            mirror[part] += block @ V.T
            out.append(RowLocalCarrier(part.astype(np.int32), block, V, m))
        return out, window

    def drive_engine(self, eng, name, mirror, rng, scale):
        """The engine's update paths, in order; returns the carrier window."""
        jax = self.jax
        t0 = time.perf_counter()
        for u, v in self.row_updates(rng, mirror, 8, scale):
            # blocked: without donation, back-to-back async firings hold
            # three generations of the views (15 GiB at n=16384)
            eng.apply_update(name, u, v, block=True)
        t1 = time.perf_counter()
        # 16 updates on 4 rows (Zipf-like skew): stacked rank 16,
        # numerical rank 4, so the re-compression at max_batch_rank is exact
        hot = rng.choice(mirror.shape[0], 4, replace=False)
        for u, v in self.row_updates(rng, mirror, 16, scale,
                                     rows=self.np.tile(hot, 4)):
            eng.enqueue_update(name, u, v)
        eng.flush()
        jax.block_until_ready(eng.views)
        t2 = time.perf_counter()
        carriers, window = self.carriers(rng, mirror, scale)
        eng.apply_updates(name, carriers, block=True)
        t3 = time.perf_counter()
        info(f"apply_update x8 {t1 - t0:.3f} s, enqueue x16 + flush "
             f"{t2 - t1:.3f} s, carrier batch {t3 - t2:.3f} s "
             f"(first use of each shape includes its compile)")
        return window

    # -- engine phase --------------------------------------------------------------
    def engine_powers(self, backend: str) -> None:
        from repro.apps import MatrixPowers
        from repro.core import IncrementalEngine
        from repro.core.iterative import matrix_powers
        n, k = self.sizes["powers_n"], self.sizes["powers_k"]
        label = f"engine powers[{backend}]"
        A = self.np.asarray(MatrixPowers.synthesize(n, seed=self.seed)["A"])
        eng = IncrementalEngine(matrix_powers(k=k, n=n), {"A": 1},
                                apply_backend=backend, max_batch_rank=8,
                                flush_size=16, flush_age=3600.0)
        t0 = time.perf_counter()
        eng.initialize({"A": A})
        self.jax.block_until_ready(eng.views)
        info(f"{label} initialize {time.perf_counter() - t0:.3f} s "
             f"(includes compile)")
        mirror = A.astype(self.np.float64)
        del A
        rng = self.np.random.default_rng(self.seed + 1)
        window = self.drive_engine(eng, "A", mirror, rng,
                                   scale=0.3 / n ** 0.5)
        self.engine_checks(label, eng, window)
        names = [f"P{2 ** i}" for i in range(1, k.bit_length())]
        self.check_powers(label, eng.views, mirror, names)

    def engine_ols(self, backend: str) -> None:
        from repro.apps.ols import OLS, build_ols_program
        from repro.core import IncrementalEngine
        m, n = self.sizes["ols_m"], self.sizes["ols_n"]
        label = f"engine ols[{backend}]"
        inputs, _ = OLS.synthesize(m, n, 1, seed=self.seed)
        X = self.np.asarray(inputs["X"])
        Y = self.np.asarray(inputs["Y"])
        del inputs
        eng = IncrementalEngine(build_ols_program(m, n, 1), {"X": 1},
                                apply_backend=backend, max_batch_rank=8,
                                flush_size=16, flush_age=3600.0)
        t0 = time.perf_counter()
        eng.initialize({"X": X, "Y": Y})
        self.jax.block_until_ready(eng.views)
        info(f"{label} initialize {time.perf_counter() - t0:.3f} s "
             f"(includes compile)")
        mirror = X.astype(self.np.float64)
        del X
        rng = self.np.random.default_rng(self.seed + 2)
        window = self.drive_engine(eng, "X", mirror, rng, scale=0.5)
        self.engine_checks(label, eng, window)
        self.check_ols(label, eng.views, mirror, Y)

    def engine_chain(self, backend: str) -> None:
        from repro.core import IncrementalEngine, Program, dim, matmul
        np = self.np
        n, m, k = (self.sizes[f"chain_{d}"] for d in "nmk")
        label = f"engine chain[{backend}]"
        prog = Program(name="feature_chain")
        X = prog.input("X", (dim("N"), dim("M")))
        W1 = prog.input("W1", (dim("M"), dim("K")))
        W2 = prog.input("W2", (dim("K"), dim("K")))
        Y1 = prog.let("Y1", matmul(X, W1))
        prog.let("Y2", matmul(Y1, W2))
        prog.outputs = ["Y1", "Y2"]
        prog = prog.bind_dims(N=n, M=m, K=k)
        rng = np.random.default_rng(self.seed + 4)
        X = rng.standard_normal((n, m), np.float32)
        W1 = rng.standard_normal((m, k), np.float32) / np.float32(m ** 0.5)
        W2 = rng.standard_normal((k, k), np.float32) / np.float32(k ** 0.5)
        eng = IncrementalEngine(prog, {"X": 1}, apply_backend=backend,
                                max_batch_rank=8, flush_size=16,
                                flush_age=3600.0)
        t0 = time.perf_counter()
        eng.initialize({"X": X, "W1": W1, "W2": W2})
        self.jax.block_until_ready(eng.views)
        info(f"{label} initialize {time.perf_counter() - t0:.3f} s "
             f"(includes compile)")
        mirror = X.astype(np.float64)
        del X
        window = self.drive_engine(eng, "X", mirror, rng, scale=1.0)
        self.engine_checks(label, eng, window, rowlocal=True)
        jnp = self.jax.numpy
        Xf = jnp.asarray(mirror.astype(np.float32))
        del mirror
        self.compare(f"{label} X", eng.views["X"], Xf)
        Y1 = self.mm(Xf, jnp.asarray(W1))
        del Xf
        self.compare(f"{label} Y1", eng.views["Y1"], Y1)
        self.compare(f"{label} Y2", eng.views["Y2"],
                     self.mm(Y1, jnp.asarray(W2)))

    def engine_checks(self, label, eng, window, rowlocal=False) -> None:
        s = eng.stats
        info(f"{label} stats: updates_applied={s.updates_applied} "
             f"triggers_fired={s.triggers_fired} "
             f"recompressions={s.recompressions} "
             f"rowlocal_firings={s.rowlocal_firings} "
             f"widened_carriers={s.widened_carriers} "
             f"carrier rows={window} pallas_fallbacks={s.pallas_fallbacks}")
        self.expect(s.updates_applied == 8 + 16 + 4,
                    f"{label} applied {s.updates_applied} updates, not 28")
        self.expect(s.recompressions >= 1,
                    f"{label} flush did not re-compress the stacked batch")
        self.expect(s.pallas_fallbacks == 0,
                    f"{label} {s.pallas_fallbacks} Pallas fallbacks")
        if rowlocal:
            self.expect(s.rowlocal_firings >= 1 and s.widened_carriers == 0,
                        f"{label} carrier batch did not take the row-slab "
                        f"trigger ({s.rowlocal_firings} row-local firings, "
                        f"{s.widened_carriers} widened)")

    # -- references: plain jax.numpy, float32, HIGHEST -------------------------------
    def mm(self, a, b):
        return self.jax.numpy.matmul(
            a, b, precision=self.jax.lax.Precision.HIGHEST)

    def check_powers(self, label, views, mirror, names, device=None):
        """Repeated squaring of the final input; each power is compared
        (and dropped) before the next is formed."""
        jax = self.jax
        dev = device or jax.devices()[0]
        P = jax.device_put(mirror.astype(self.np.float32), dev)
        self.compare(f"{label} A", jax.device_put(views["A"], dev), P)
        for name in names:
            P = self.mm(P, P)
            self.compare(f"{label} {name}", jax.device_put(views[name], dev),
                         P)

    def check_ols(self, label, views, mirror, Y) -> None:
        jax, jnp = self.jax, self.jax.numpy
        X = jnp.asarray(mirror.astype(self.np.float32))
        Y = jnp.asarray(Y)
        G = self.mm(X.T, X)
        beta = jnp.linalg.solve(G, self.mm(X.T, Y))
        self.compare(f"{label} X", views["X"], X)
        self.compare(f"{label} Z", views["Z"], G)
        self.compare(f"{label} beta", views["beta"], beta)

    # -- fleet phase -----------------------------------------------------------------
    def fleet(self) -> None:
        from repro.apps import MatrixPowers
        from repro.core.iterative import matrix_powers
        from repro.fleet import (ADMITTED, FleetConfig, FleetScheduler,
                                 TenantSpec)
        np = self.np
        n, k = self.sizes["fleet_n"], self.sizes["fleet_k"]
        fleet = FleetScheduler(FleetConfig(workers=4, lease_ttl=300.0))
        mirrors = {}
        for t in range(FLEET_TENANTS):
            tid = f"tenant{t}"
            A = np.asarray(MatrixPowers.synthesize(
                n, seed=self.seed + 10 + t)["A"])
            fleet.add_tenant(TenantSpec(tid, matrix_powers(k=k, n=n),
                                        {"A": 1}, guarded=True), {"A": A})
            mirrors[tid] = A.astype(np.float64)
        rng = np.random.default_rng(self.seed + 3)
        tiers = set()
        t0 = time.perf_counter()
        fleet.start()
        try:
            for _ in range(FLEET_UPDATES):
                for tid, mirror in mirrors.items():
                    (u, v), = self.row_updates(rng, mirror, 1,
                                               0.3 / n ** 0.5)
                    decision = fleet.submit(tid, "A", u, v)
                    self.expect(decision == ADMITTED,
                                f"fleet {tid} submit {decision}")
                    tiers.add(fleet.tier())
            fleet.drain(timeout_s=900.0)
            tiers.add(fleet.tier())
            views = {tid: fleet.read_views(tid) for tid in mirrors}
        finally:
            fleet.stop()
        info(f"fleet {FLEET_TENANTS} tenants x {FLEET_UPDATES} updates, "
             f"submit to drained {time.perf_counter() - t0:.3f} s "
             f"(includes compile)")
        stats = fleet.fleet_stats()
        info(f"fleet stats: commits={stats['commits']} "
             f"committed_updates={stats['committed_updates']} "
             f"tier={stats['tier']} worker_errors={stats['worker_errors']} "
             f"worker_crashes={stats['worker_crashes']} "
             f"fenced_aborts={stats['fenced_aborts']}")
        self.expect(tiers == {"normal"}, f"fleet tiers seen {sorted(tiers)}")
        self.expect(stats["worker_errors"] == 0,
                    f"fleet worker errors: {stats['worker_errors']} "
                    f"(last {stats['last_worker_error']})")
        self.expect(stats["worker_crashes"] == 0, "fleet worker crashes")
        want = FLEET_TENANTS * FLEET_UPDATES
        self.expect(stats["committed_updates"] == want,
                    f"fleet committed {stats['committed_updates']} "
                    f"updates, not {want}")
        for tenant in fleet.registry:
            g = tenant.engine.guard.stats
            self.expect(g.rollbacks == 0 and g.aborted_firings == 0
                        and g.quarantined == 0,
                        f"fleet {tenant.spec.tenant_id} guard: rollbacks="
                        f"{g.rollbacks} aborted={g.aborted_firings} "
                        f"quarantined={g.quarantined} "
                        f"{tenant.engine.guard.quarantine.reasons()}")
        names = [f"P{2 ** i}" for i in range(1, k.bit_length())]
        for tid, mirror in mirrors.items():
            self.check_powers(f"fleet {tid}", views[tid], mirror, names)

    # -- four chips ------------------------------------------------------------------
    def four_chips(self) -> None:
        """Row-sharded matrix powers on a 4-device ``rows`` mesh, against
        the same program on one device and against the reference."""
        from repro.apps import MatrixPowers
        from repro.core import IncrementalEngine
        from repro.core.iterative import matrix_powers
        jax, np = self.jax, self.np
        n, k = self.sizes["powers_n"], self.sizes["powers_k"]
        names = [f"P{2 ** i}" for i in range(1, k.bit_length())]
        A = np.asarray(MatrixPowers.synthesize(n, seed=self.seed)["A"])
        mesh = jax.make_mesh((4,), ("rows",), devices=jax.devices()[:4])
        runs = {}
        # the mesh engine first, so that each chip's peak is its own
        for where, kw in (("4 chips", {"mesh": mesh}), ("1 chip", {})):
            eng = IncrementalEngine(matrix_powers(k=k, n=n), {"A": 1},
                                    max_batch_rank=8, flush_size=16,
                                    flush_age=3600.0, **kw)
            t0 = time.perf_counter()
            eng.initialize({"A": A})
            mirror = A.astype(np.float64)
            rng = np.random.default_rng(self.seed + 1)  # same stream
            self.drive_engine(eng, "A", mirror, rng, scale=0.3 / n ** 0.5)
            info(f"four-chips {where}: {time.perf_counter() - t0:.3f} s "
                 f"(includes compile)")
            if kw:
                self.mesh_peaks(jax.devices()[:4])
            runs[where] = eng.views
            del eng
        one, four = runs["1 chip"], runs["4 chips"]
        for name in ["A"] + names:
            devs = {s.device.id for s in four[name].addressable_shards}
            shapes = {s.data.shape for s in four[name].addressable_shards}
            ok = len(devs) == 4 and shapes == {(n // 4, n)}
            print(f"four-chips {name}: shards on devices {sorted(devs)}, "
                  f"shard shapes {sorted(shapes)} "
                  f"{'ok' if ok else 'NOT ROW-SHARDED'}", flush=True)
            self.expect(ok, f"four-chips {name} shards {devs} {shapes}")
        dev = jax.devices()[0]
        for name in ["A"] + names:
            self.compare(f"four-chips {name} vs 1 chip",
                         jax.device_put(four[name], dev), one[name])
        self.check_powers("four-chips", four, mirror, names, device=dev)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the row-sharded 4-chip phase")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU backend (never a TPU result)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.four_chips:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4")
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    platform = "cpu" if args.cpu_rehearsal else "tpu"
    devices = jax.devices()
    want = 4 if args.four_chips else 1
    if devices[0].platform != platform or len(devices) < want:
        print(f"error: need {want} {platform} device(s), JAX found "
              f"{len(devices)} {devices[0].platform}", file=sys.stderr)
        return 2
    info(f"compile cache {enable_compile_cache()}")
    sizes = REHEARSAL if args.cpu_rehearsal else FULL
    smoke = Smoke(sizes, args.seed)
    if args.four_chips:
        info(f"four-chips: matrix powers n={sizes['powers_n']} "
             f"k={sizes['powers_k']}")
        phases = [("four-chips", smoke.four_chips)]
    else:
        info(f"sizes: powers n={sizes['powers_n']} k={sizes['powers_k']}; "
             f"ols m={sizes['ols_m']} n={sizes['ols_n']} p=1; chain "
             f"n={sizes['chain_n']} m={sizes['chain_m']} "
             f"k={sizes['chain_k']}; fleet "
             f"{FLEET_TENANTS} tenants powers n={sizes['fleet_n']} "
             f"k={sizes['fleet_k']}")
        phases = [(f"engine {prog}[{backend}]",
                   lambda p=prog, b=backend: getattr(smoke, f"engine_{p}")(b))
                  for prog in ("powers", "ols", "chain") for backend in ("xla", "pallas")]
        phases.append(("fleet", smoke.fleet))
    for name, run in phases:
        t0 = time.perf_counter()
        run()
        gc.collect()   # the phase's engines and views go before the next
        info(f"phase {name}: {time.perf_counter() - t0:.3f} s wall")
        smoke.memory(name)
    if smoke.failures:
        print(f"{len(smoke.failures)} check(s) failed:", file=sys.stderr)
        for f in smoke.failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}))
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except Exception:  # noqa: BLE001 — any failure is a failed smoke
        traceback.print_exc()
        code = 1
    sys.exit(code)

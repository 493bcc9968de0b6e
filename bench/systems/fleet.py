"""A ``FleetScheduler`` with one tenant per program instance, served live.

Updates enter through ``submit``; the fleet's worker threads claim,
fire, guard and commit them; a reader sees them through ``read_views``.
Each tenant's input is made on the device from the seed, and every
admitted update goes into the benchmark's record for the reference.
"""

from __future__ import annotations

import jax

from bench import record
from bench.harness import load_program


class System:
    def __init__(self, cfg: dict, seed: int, spans):
        from repro.fleet import FleetConfig, FleetScheduler, TenantSpec
        self.cfg = cfg
        self.spans = spans
        self.prog = load_program(cfg["program"])
        self.n = int(cfg["n"])
        self.fleet = FleetScheduler(FleetConfig(**cfg.get("fleet", {})))
        self.ids = [f"t{t:02d}" for t in range(int(cfg["tenants"]))]
        self._keys = {}
        with spans.span("initialize"):
            for t, tid in enumerate(self.ids):
                self._keys[tid] = record.jax_key(seed, 1, t)
                A = self.prog.synthesize(cfg, self._keys[tid])
                self.fleet.add_tenant(
                    TenantSpec(tid, self.prog.build_program(cfg),
                               {self.prog.INPUT: 1},
                               **cfg.get("tenant", {})),
                    {self.prog.INPUT: A})
            jax.block_until_ready([self.fleet.read_views(t) for t in self.ids])
        self.records = {tid: record.RowRecord(self.n) for tid in self.ids}
        self.max_claim_rank = self.fleet.registry.get(
            self.ids[0]).spec.max_claim_rank

    def deltas(self, rng, count: int, scale: float):
        return self.prog.deltas(self.cfg, rng, count, scale)

    def submit(self, tid: str, row: int, delta) -> tuple[str, int | None]:
        """``(decision, lsn)``: the log position of an admitted update.
        Only the caller appends to the logs, so the last LSN is its own."""
        from repro.fleet import ADMITTED
        with self.spans.span("submit"):
            decision = self.fleet.submit(tid, self.prog.INPUT,
                                         record.one_hot(self.n, row),
                                         delta[:, None])
        if decision != ADMITTED:
            return decision, None
        self.records[tid].add([row], [delta])
        return decision, self.fleet.registry.get(tid).log.last_lsn()

    def warm(self, rng, scale: float) -> None:
        """Fire one claim at every stacked-rank bucket a claim can reach
        (1, 2, 4 … ``max_claim_rank``), on the first tenant, without the
        worker threads; the tenants share the compiled programs."""
        tid = self.ids[0]
        b = 1
        while b <= self.max_claim_rank:
            rows = rng.integers(self.n, size=b)
            for row, delta in zip(rows, self.deltas(rng, b, scale)):
                self.submit(tid, int(row), delta)
            self.fleet.run_until_idle(workers=1)
            jax.block_until_ready(self.fleet.read_views(tid))
            b *= 2

    def start(self) -> None:
        self.fleet.start()

    def stop(self) -> None:
        self.fleet.stop()

    def applied_lsn(self, tid: str) -> int:
        return self.fleet.registry.get(tid).applied_lsn

    def read_visible(self, tid: str) -> int:
        """Read the tenant's views through the served path and wait until
        they are ready on the device; returns the last LSN they hold.
        The tenant's mutex makes the LSN and the views one commit's."""
        tenant = self.fleet.registry.get(tid)
        with tenant.mutex:
            lsn = tenant.applied_lsn
            with self.spans.span("read_views"):
                views = self.fleet.read_views(tid)
        with self.spans.span("collector_wait"):
            jax.block_until_ready(views)
        return lsn

    def counters(self) -> dict:
        s = self.fleet.fleet_stats()
        return {k: s[k] for k in ("commits", "committed_updates",
                                  "worker_errors", "worker_crashes",
                                  "fenced_aborts", "replays", "tier")} | {
            "decisions": dict(s["decisions"])}

    def readings(self, control: bool = False
                 ) -> tuple[dict[str, float], dict[str, float] | None]:
        """Worst reading over the tenants, view by view, of the committed
        views against the reference (and of the control, where asked)."""
        from bench.reference import apply_row_updates
        views = {tid: self.fleet.read_views(tid) for tid in self.ids}
        self.fleet = None
        got: dict[str, float] = {}
        ctl: dict[str, float] | None = {} if control else None
        for tid in self.ids:
            rows, deltas = self.records[tid].arrays()
            A = apply_row_updates(
                self.prog.synthesize(self.cfg, self._keys[tid]), rows, deltas)
            for name, v in self.prog.readings(self.cfg, A,
                                              views.pop(tid)).items():
                got[name] = max(got.get(name, 0.0), v)
            if control:
                for name, v in self.prog.control_readings(self.cfg,
                                                          A).items():
                    ctl[name] = max(ctl.get(name, 0.0), v)
        return got, ctl

"""The program's own spans and counters, seen through the benchmark as it
is: a trace recorded with the engine's spans installed reduces to the
same busy time as without them, and a traced run reads the engine's rank
counters.  CPU traces check the arithmetic only: their numbers are no
device time."""

from __future__ import annotations

import jax
import numpy as np

from benchutil import harness, run_tiny, tiny_cell
from bench import trace_reduce as tr
from bench.spans import Spans


def test_program_spans_leave_busy_unchanged(tmp_path):
    """One CPU trace, recorded with the engine's spans written beside the
    benchmark's, reduces to the same window, busy time, idle share and
    busy time in the firing spans whether the engine's spans are among
    the host spans or not."""
    from repro import obs
    from repro.core import IncrementalEngine

    prog = harness.load_program("matrix_powers")
    cfg = {"n": 64, "k": 4, "spectral_scale": 0.9}
    eng = IncrementalEngine(prog.build_program(cfg), {"A": 1},
                            max_batch_rank=4)
    eng.initialize({"A": prog.synthesize(cfg, jax.random.key(0))})
    rng = np.random.default_rng(0)
    batch = [(rng.standard_normal((64, 1)).astype(np.float32),
              rng.standard_normal((64, 1)).astype(np.float32))
             for _ in range(6)]
    eng.apply_updates("A", batch, block=True)  # builds bucket 4
    spans, program = Spans(annotate=True), obs.Spans(annotate=True)
    before = obs.install(program)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with spans.span(tr.WINDOW_SPAN):
            for _ in range(3):
                with spans.span("apply_updates"):
                    eng.apply_updates("A", batch)
                with spans.span("wait_views"):
                    jax.block_until_ready(eng.views)
    finally:
        jax.profiler.stop_trace()
        obs.install(before)
    engine_names = {s[0] for s in program.records}
    assert {"engine.apply_updates", "engine.stack", "engine.recompress",
            "engine.dispatch"} <= engine_names
    bench_names = {s[0] for s in spans.records}
    ops, host = tr.extract(str(tmp_path), "cpu", bench_names | engine_names)
    bench_only = [s for s in host if s[0] in bench_names]
    assert len(bench_only) < len(host)
    assert sum(1 for s in host if s[0] == "engine.dispatch") == 3
    full = tr.reduce(ops, host, busy_spans=harness.FIRING_SPANS)
    plain = tr.reduce(ops, bench_only, busy_spans=harness.FIRING_SPANS)
    for key in ("window_s", "busy_s", "idle_share", "busy_in_spans_s",
                "device_ops"):
        assert full[key] == plain[key], key


def test_traced_run_reads_rank_counters():
    """A traced run of the batch cell reads ``bucket_fill_pct``,
    ``stacked_rank_per_firing`` and ``trigger_builds_in_window`` from the
    engine's counters through the harness as it is (the roofline reader,
    which needs a TPU's peaks, left out)."""
    cell = tiny_cell("powers16k.zipf_batch")
    cell.per_layer = [m for m in cell.per_layer
                      if m["name"] != "firing_roofline_pct"]
    result = run_tiny(cell, trace=True)
    assert result["correct"], result["checks"]
    metrics = result["metrics"]
    fill = metrics["bucket_fill_pct"]
    assert fill["unit"] == "%" and 0 < fill["value"] <= 100
    assert metrics["stacked_rank_per_firing"]["value"] > 0
    assert metrics["trigger_builds_in_window"]["value"] >= 0

"""The trace reduction's arithmetic, on hand-made intervals and on a small
trace recorded on the CPU.  A CPU trace checks the arithmetic only: its
numbers are no device time."""

from __future__ import annotations

import time

import pytest

from benchutil import ROOT  # noqa: F401  (puts the checkout on the path)
from bench import trace_reduce as tr


def test_union_intersect_clip():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5),
                                                              (3, 4)]
    assert tr.intersect([(0, 2), (3, 5)], [(1, 4)]) == [(1, 2), (3, 4)]
    assert tr.clip([(0, 2), (3, 5), (6, 7)], 1, 4) == [(1, 2), (3, 4)]
    assert tr.length([(0, 2), (3, 4)]) == 3


def test_reduce_by_hand():
    ops = {"dev0": [("fusion.1", 1.0, 2.0), ("fusion.2", 1.5, 3.0),
                    ("copy", 5.0, 6.0), ("fusion.1", 8.0, 12.0)]}
    spans = [(tr.WINDOW_SPAN, 0.0, 10.0), ("apply_update", 0.5, 3.5),
             ("read_views", 3.5, 5.0), ("apply_update", 6.0, 9.0)]
    r = tr.reduce(ops, spans, busy_spans=("apply_update",))
    assert r["window_s"] == 10.0
    # busy: [1,3] + [5,6] + [8,10] (clipped) = 2 + 1 + 2
    assert r["busy_s"] == pytest.approx(5.0)
    assert r["idle_share"] == pytest.approx(0.5)
    # inside apply_update spans: [1,3] and [8,9]
    assert r["busy_in_spans_s"] == pytest.approx(3.0)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(3.0)]
    assert {n for n, _ in r["device_ops"]} == {"fusion.1", "fusion.2",
                                               "copy"}
    # gaps [0,1], [3,5], [6,8], each named by the span overlapping most
    assert r["idle_gaps"] == [["read_views", pytest.approx(2.0)],
                              ["apply_update", pytest.approx(2.0)],
                              ["apply_update", pytest.approx(1.0)]]
    assert sum(t for _, t in r["idle_gaps"]) + r["busy_s"] == \
        pytest.approx(r["window_s"])


def test_reduce_averages_devices_and_needs_window():
    ops = {"d0": [("a", 0.0, 4.0)], "d1": [("a", 0.0, 2.0)]}
    r = tr.reduce(ops, [(tr.WINDOW_SPAN, 0.0, 4.0)])
    assert r["busy_s"] == pytest.approx(3.0)
    with pytest.raises(ValueError):
        tr.reduce(ops, [])


def test_cpu_trace_recorded_and_reduced(tmp_path):
    import jax
    import jax.numpy as jnp

    from bench.spans import Spans

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    spans = Spans(annotate=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with spans.span(tr.WINDOW_SPAN):
            for _ in range(3):
                with spans.span("apply_update"):
                    f(x).block_until_ready()
            with spans.span("host_sleep"):
                time.sleep(0.05)
    finally:
        jax.profiler.stop_trace()
    ops, host = tr.extract(str(tmp_path), "cpu",
                           {tr.WINDOW_SPAN, "apply_update", "host_sleep"})
    names = [n for n, _, _ in host]
    assert names.count(tr.WINDOW_SPAN) == 1
    assert names.count("apply_update") == 3
    r = tr.reduce(ops, host, busy_spans=("apply_update",))
    w = [s for s in spans.records if s[0] == tr.WINDOW_SPAN][0]
    # the trace's clock and the host's agree on the window's length
    assert r["window_s"] == pytest.approx(w[2] - w[1], rel=0.2, abs=5e-3)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_in_spans_s"] <= r["busy_s"] + 1e-9
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / r["window_s"])
    assert r["idle_gaps"][0][0] == "host_sleep"
    assert r["idle_gaps"][0][1] >= 0.04

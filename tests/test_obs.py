"""Spans, counters and program names of the engine's update path.

``repro.obs`` keeps spans in memory with their parent and request;
the engine opens its spans through it and counts the ranks it stacks,
fires and pads; every jitted firing program carries a ``fire_`` name.
"""

import threading

import jax
import numpy as np
import pytest

from repro import obs
from repro.core.iterative import matrix_powers
from repro.core.runtime import IncrementalEngine


@pytest.fixture
def recorder():
    spans = obs.Spans()
    before = obs.install(spans)
    try:
        yield spans
    finally:
        obs.install(before)


def test_null_path_records_nothing_and_shares_one_context():
    spans = obs.Spans()
    before = obs.install(spans)
    try:
        assert obs.install(None) is spans
        first, second = obs.span("a"), obs.span("b", request=True)
        assert first is second
        with first:
            with second:
                pass
    finally:
        obs.install(before)
    assert spans.records == []


def test_nesting_gives_parent(recorder):
    with recorder.span("outer"):
        with obs.span("middle"):
            with obs.span("inner"):
                pass
        with obs.span("sibling"):
            pass
    by_name = {r.name: r for r in recorder.nodes(0, float("inf"))}
    assert by_name["outer"].parent is None
    assert by_name["middle"].parent == by_name["outer"].id
    assert by_name["inner"].parent == by_name["middle"].id
    assert by_name["sibling"].parent == by_name["outer"].id
    # the triples the benchmark reads keep their shape
    assert [s[0] for s in recorder.between(0, float("inf"))] == [
        "inner", "middle", "sibling", "outer"]
    assert all(len(s) == 3 for s in recorder.records)


def test_spans_of_one_call_share_a_request(recorder):
    with recorder.span("window"):
        for _ in range(2):
            with obs.span("call", request=True):
                with obs.span("step"):
                    with obs.span("leaf"):
                        pass
    nodes = recorder.nodes(0, float("inf"))
    calls = [r for r in nodes if r.name == "call"]
    assert len({c.request for c in calls}) == 2
    for c in calls:
        assert c.request == c.id
        members = {r.name for r in nodes if r.request == c.request}
        assert members == {"call", "step", "leaf"}
    window = next(r for r in nodes if r.name == "window")
    assert window.request is None


def test_threads_keep_separate_parent_stacks(recorder):
    entered, release = threading.Event(), threading.Event()

    def worker():
        with obs.span("thread_outer"):
            entered.set()
            release.wait(timeout=10)
            with obs.span("thread_inner"):
                pass

    t = threading.Thread(target=worker)
    with recorder.span("main_outer"):
        t.start()
        assert entered.wait(timeout=10)
        with obs.span("main_inner"):
            release.set()
            t.join(timeout=10)
    assert not t.is_alive()
    by_name = {r.name: r for r in recorder.nodes(0, float("inf"))}
    assert by_name["thread_outer"].parent is None
    assert by_name["thread_inner"].parent == by_name["thread_outer"].id
    assert by_name["main_inner"].parent == by_name["main_outer"].id


def _powers_engine(n=32, **kw):
    eng = IncrementalEngine(matrix_powers(k=4, n=n), {"A": 1}, **kw)
    A = np.random.default_rng(0).standard_normal((n, n)) * (0.9 / n ** 0.5)
    eng.initialize({"A": A.astype(np.float32)})
    return eng


def _batch(n, count, seed=1):
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal((n, 1)).astype(np.float32) * 0.1,
             rng.standard_normal((n, 1)).astype(np.float32) * 0.1)
            for _ in range(count)]


def test_apply_updates_spans_and_rank_counters(recorder):
    """A batch of rank 12 over a cap of 5 is stacked, re-compressed to 5
    and fired at bucket 8, in that order under ``engine.apply_updates``."""
    eng = _powers_engine(max_batch_rank=5)
    builds = eng.stats.trigger_builds
    eng.apply_updates("A", _batch(32, 12), block=True)
    nodes = recorder.nodes(0, float("inf"))
    root = next(r for r in nodes if r.name == "engine.apply_updates")
    assert root.request == root.id
    children = sorted((r for r in nodes if r.parent == root.id),
                      key=lambda r: r.start)
    # bucket 8 was not built before this batch: its trigger is built
    # after re-compression, before the dispatch
    assert [r.name for r in children] == [
        "engine.stack", "engine.recompress", "engine.build",
        "engine.dispatch", "engine.block"]
    assert all(r.request == root.id for r in children)
    assert eng.stats.trigger_builds == builds + 1
    s = eng.stats
    assert s.stacked_rank == 12
    assert s.fired_rank == 5
    assert s.padded_rank == 8
    assert s.recompressions == 1


def test_apply_update_spans(recorder):
    eng = _powers_engine()
    u, v = _batch(32, 1)[0]
    eng.apply_update("A", u, v, block=True)
    nodes = recorder.nodes(0, float("inf"))
    root = next(r for r in nodes if r.name == "engine.apply_update")
    assert [r.name for r in nodes if r.parent == root.id] == [
        "engine.dispatch", "engine.block"]
    assert (eng.stats.stacked_rank, eng.stats.fired_rank,
            eng.stats.padded_rank) == (1, 1, 1)


@pytest.mark.parametrize("guarded,expected", [
    (False, {"jit(evaluate)", "jit(fire_A_r1)", "jit(fire_A_r4)"}),
    (True, {"jit(fire_guarded_A_r1)", "jit(fire_guarded_A_r4)"}),
])
def test_firing_programs_lowered_under_fire_names(guarded, expected):
    """Every firing program is lowered as ``jit_fire_...`` (JAX prefixes
    the function's name with ``jit``), the evaluator as ``jit_evaluate``;
    these are the module names a device trace shows."""
    lowered = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        eng = _powers_engine(n=24, guard=True if guarded else None)
        u, v = _batch(24, 1)[0]
        eng.apply_update("A", u, v, block=True)
        eng.apply_updates("A", _batch(24, 3), block=True)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert expected <= set(lowered)
    fired = [n for n in lowered if "fire_" in n]
    assert fired and all(n.startswith("jit(fire_") for n in fired)


def test_rolled_back_firing_keeps_its_trigger_build(recorder):
    """A firing that is rolled back leaves the rank counters as they
    were, but the trigger it built stays in the cache and in the count:
    the next firing builds nothing."""
    import dataclasses

    from repro.guard import ChaosConfig, GuardConfig
    eng = _powers_engine(guard=GuardConfig(),
                         chaos=ChaosConfig(seed=0, trigger_raise_p=1.0))
    before = dataclasses.replace(eng.stats)
    u, v = _batch(32, 1)[0]
    eng.apply_update("A", u, v)
    assert eng.guard.stats.rollbacks == 1
    assert eng.stats == dataclasses.replace(
        before, trigger_builds=before.trigger_builds + 1)
    assert (eng.stats.stacked_rank, eng.stats.padded_rank) == (0, 0)
    builds = [r for r in recorder.records if r[0] == "engine.build"]
    eng.apply_update("A", u, v)
    assert eng.guard.stats.rollbacks == 2
    assert [r for r in recorder.records if r[0] == "engine.build"] == builds


def test_rowlocal_batch_spans_and_rank_counters(recorder):
    """A row-local carrier batch of rank 8 over a cap of 4 is
    re-compressed on its compact rows and fires once at rank 4."""
    from repro.core import Program, dim, matmul
    from repro.data import row_local_stream

    p = Program(name="chain")
    X = p.input("X", (dim("N"), dim("M")))
    W = p.input("W", (dim("M"), dim("K")))
    p.let("Y", matmul(X, W))
    p.outputs = ["Y"]
    p.bind_dims(N=64, M=32, K=16)
    rng = np.random.default_rng(0)
    eng = IncrementalEngine(p, {"X": 2}, max_batch_rank=4)
    eng.initialize({"X": rng.standard_normal((64, 32)).astype(np.float32),
                    "W": rng.standard_normal((32, 16)).astype(np.float32)})
    stream = row_local_stream(64, 2, m=32, rank=2, seed=5)
    eng.apply_updates("X", [stream.next_carrier() for _ in range(4)])
    assert eng.stats.rowlocal_firings == 1
    assert (eng.stats.stacked_rank, eng.stats.fired_rank,
            eng.stats.padded_rank) == (8, 4, 4)
    nodes = recorder.nodes(0, float("inf"))
    root = next(r for r in nodes if r.name == "engine.apply_updates")
    assert [r.name for r in nodes if r.parent == root.id
            and r.name != "engine.build"] == ["engine.recompress",
                                              "engine.dispatch"]

"""Engine-level distributed integration + cost-model auto-flush.

The mesh plumbing (does the engine route firings through the row-sharded
apply, does the output stay exact) is checked here on a 1-device mesh so
it runs in-process; multi-device numerics of the same code path are
covered by tests/test_distributed.py in subprocesses.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.ols import build_ols_program
from repro.core import IncrementalEngine, ReevalEngine, max_abs_diff
from repro.core.cost import batched_strategy
from repro.core.iterative import matrix_powers
from repro.data.updates import UpdateStream

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _updates(n, m, count, seed=3, rank=1):
    it = iter(UpdateStream(n=n, m=m, rank=rank, scale=0.02, seed=seed))
    return [next(it) for _ in range(count)]


def _powers_inputs(n, seed=0):
    rng = np.random.default_rng(seed)
    a = (0.5 / np.sqrt(n)) * rng.normal(size=(n, n))
    return {"A": jnp.asarray(a, jnp.float32)}


def _ols_inputs(m, n, seed=0):
    rng = np.random.default_rng(seed)
    return {"X": jnp.asarray(rng.normal(size=(m, n)), jnp.float32),
            "Y": jnp.asarray(rng.normal(size=(m, 1)), jnp.float32)}


# -- engine mesh= path --------------------------------------------------------


def test_engine_mesh_path_matches_single_device():
    """IncrementalEngine(mesh=...) fires every trigger through the
    row-sharded apply and stays exact (1-device mesh in-process)."""
    mesh = jax.make_mesh((1,), ("rows",))
    prog = matrix_powers(k=8, n=48, model="exp")
    dist = IncrementalEngine(prog, mesh=mesh)
    ref = IncrementalEngine(matrix_powers(k=8, n=48, model="exp"))
    dist.initialize(_powers_inputs(48))
    ref.initialize(_powers_inputs(48))

    ups = _updates(48, 48, 6, seed=13)
    for u, v in ups[:3]:
        dist.apply_update("A", jnp.asarray(u), jnp.asarray(v))
        ref.apply_update("A", jnp.asarray(u), jnp.asarray(v))
    dist.apply_updates("A", ups[3:], block=True)
    ref.apply_updates("A", ups[3:], block=True)
    assert max_abs_diff(dist.views, ref.views) < 1e-4
    assert dist.stats.triggers_fired == ref.stats.triggers_fired == 4


def test_engine_mesh_path_multi_device_subprocess():
    """Same engine path on a real 8-way mesh: sharded views, exact
    results vs the paper's re-evaluation baseline."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import IncrementalEngine, ReevalEngine, max_abs_diff
        from repro.core.iterative import matrix_powers
        from repro.data.updates import UpdateStream

        n = 64
        rng = np.random.default_rng(0)
        A = jnp.asarray(rng.normal(size=(n, n)) / 9, jnp.float32)
        mesh = jax.make_mesh((8,), ("rows",))
        eng = IncrementalEngine(matrix_powers(k=8, n=n, model="exp"),
                                mesh=mesh)
        ree = ReevalEngine(matrix_powers(k=8, n=n, model="exp"))
        eng.initialize({"A": A})
        ree.initialize({"A": A})
        # views actually live row-sharded on the mesh
        sh = eng.views["P8"].sharding
        assert getattr(sh, "mesh", None) is not None and \\
            len(sh.device_set) == 8, sh
        it = iter(UpdateStream(n=n, m=n, scale=0.02, seed=1))
        ups = [next(it) for _ in range(8)]
        eng.apply_updates("A", ups, block=True)
        for u, v in ups:
            ree.apply_update("A", jnp.asarray(u), jnp.asarray(v))
        err = max_abs_diff(eng.views, ree.views,
                           tuple(eng.program.output_names()))
        assert err < 1e-3, err
        print("engine mesh OK", err)
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"


def test_planned_engine_multi_device_subprocess():
    """A maintenance plan executing on a real 8-way mesh: the planned
    firing (incremental + in-firing reeval partition) stays exact vs the
    re-evaluation baseline, and plans carry the mesh into the trigger
    cache key so a second engine re-jits nothing."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import IncrementalEngine, ReevalEngine, max_abs_diff
        from repro.core.iterative import matrix_powers
        from repro.data.updates import UpdateStream
        from repro.plan import TriggerCache, WorkloadDescriptor

        n = 64
        rng = np.random.default_rng(0)
        A = jnp.asarray(rng.normal(size=(n, n)) / 9, jnp.float32)
        mesh = jax.make_mesh((8,), ("rows",))
        cache = TriggerCache()
        wl = WorkloadDescriptor(batch_size=100000)  # all views reeval
        eng = IncrementalEngine(matrix_powers(k=8, n=n, model="exp"),
                                mesh=mesh, plan=wl, trigger_cache=cache)
        ree = ReevalEngine(matrix_powers(k=8, n=n, model="exp"))
        eng.initialize({"A": A})
        ree.initialize({"A": A})
        it = iter(UpdateStream(n=n, m=n, scale=0.02, seed=1))
        ups = [next(it) for _ in range(8)]
        eng.apply_updates("A", ups, block=True)
        assert eng.stats.plan_reevals > 0
        for u, v in ups:
            ree.apply_update("A", jnp.asarray(u), jnp.asarray(v))
        err = max_abs_diff(eng.views, ree.views,
                           tuple(eng.program.output_names()))
        assert err < 1e-3, err
        misses = cache.misses
        eng2 = IncrementalEngine(matrix_powers(k=8, n=n, model="exp"),
                                 mesh=mesh, plan=wl, trigger_cache=cache)
        eng2.initialize({"A": A})
        eng2.apply_updates("A", ups, block=True)
        assert cache.misses == misses, (cache.stats(), misses)
        err2 = max_abs_diff(eng2.views, eng.views)
        assert err2 < 1e-5, err2
        print("planned mesh OK", err, cache.stats())
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"


# -- sharded initialize on a 4-way mesh ----------------------------------------

# One child process with four virtual devices drives every check of the
# mesh engine's placement, its agreement with one device and with a plain
# reference, its collective counter and its initialize spans, and prints
# what it saw; the tests below each read their part of it.
_MESH4_N = 256
_MESH4_SCRIPT = textwrap.dedent("""
    import json, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec
    from repro import obs
    from repro.core import IncrementalEngine
    from repro.core.iterative import matrix_powers
    from repro.roofline.analysis import parse_collectives

    n = {n}
    rng = np.random.default_rng(0)
    A = (rng.normal(size=(n, n)) * 0.9 / np.sqrt(n)).astype(np.float32)
    mesh = jax.make_mesh((4,), ("rows",))
    HIGHEST = jax.lax.Precision.HIGHEST

    def placement(views):
        return {{k: [str(v.sharding.spec),
                    sorted({{s.device.id for s in v.addressable_shards}}),
                    sorted({{tuple(s.data.shape)
                            for s in v.addressable_shards}})]
                for k, v in views.items()}}

    def rel(got, want):
        want = np.asarray(want, np.float64)
        return float(np.max(np.abs(np.asarray(got, np.float64) - want))
                     / np.max(np.abs(want)))

    def reference(a):
        out, p = {{"A": a}}, jnp.asarray(a)
        for name in ("P2", "P4", "P8", "P16"):
            p = jnp.matmul(p, p, precision=HIGHEST)
            out[name] = p
        return out

    def program():
        return matrix_powers(k=16, n=n)

    report = {{"initialize": {{}}}}
    inputs = {{"host": A,
              "one device": jax.device_put(A, jax.devices()[0]),
              "sharded": jax.device_put(
                  A, NamedSharding(mesh, PartitionSpec("rows", None)))}}
    for origin, a in inputs.items():
        eng = IncrementalEngine(program(), {{"A": 1}}, mesh=mesh)
        rec = obs.Spans()
        before = obs.install(rec)
        eng.initialize({{"A": a}})
        obs.install(before)
        ref = reference(A)
        staged = eng._evaluator.lower({{"A": eng.views["A"]}}).compile()
        report["initialize"][origin] = {{
            "placement": placement(eng.views),
            "evaluate_out": sorted({{str(s.spec) for s in jax.tree.leaves(
                staged.output_shardings)}}),
            "rel_err": max(rel(eng.views[k], ref[k]) for k in ref),
            "spans": [[r.name, r.id, r.parent]
                      for r in rec.nodes(0.0, float("inf"))]}}

    # firings: per-update and batched, against one device and the
    # reference; the counter against each firing program's own text
    one = IncrementalEngine(program(), {{"A": 1}})
    one.initialize({{"A": A}})
    mirror = A.astype(np.float64)
    rises, parsed = [], []

    largest = []

    def expected(fn):
        ops = {{}}
        for op in parse_collectives(fn.executable.as_text()).ops:
            ops.setdefault(op.channel_id or id(op), op)
        largest.append(max(op.operand_bytes for op in ops.values()))
        return sum(op.operand_bytes for op in ops.values())

    for i in range(3):
        row = int(rng.integers(n))
        u = np.zeros((n, 1), np.float32)
        u[row, 0] = 1.0
        d = (rng.normal(size=(n, 1)) * 0.3 / np.sqrt(n)).astype(np.float32)
        mirror[row] += d[:, 0]
        c0 = eng.stats.collective_bytes
        eng.apply_update("A", u, d, block=True)
        one.apply_update("A", u, d, block=True)
        rises.append(eng.stats.collective_bytes - c0)
        parsed.append(expected(eng._trigger_fns["A"]))
    batch = []
    for row in (3, 17):
        u = np.zeros((n, 1), np.float32)
        u[row, 0] = 1.0
        d = (rng.normal(size=(n, 1)) * 0.3 / np.sqrt(n)).astype(np.float32)
        mirror[row] += d[:, 0]
        batch.append((u, d))
    c0 = eng.stats.collective_bytes
    eng.apply_updates("A", batch, block=True)
    one.apply_updates("A", batch, block=True)
    rises.append(eng.stats.collective_bytes - c0)
    parsed.append(expected(eng._batched_triggers[("A", 2)]))
    ref = reference(mirror.astype(np.float32))
    report["firings"] = {{
        "placement": placement(eng.views),
        "vs_one_device": max(rel(eng.views[k], one.views[k]) for k in ref),
        "vs_reference": max(rel(eng.views[k], ref[k]) for k in ref),
        "rises": rises, "parsed": parsed, "largest": largest}}

    # re-evaluations on the mesh: a fold's and refresh's
    eng._fold_reeval({{"P4", "P16"}})
    folded = placement(eng.views)
    fold_err = max(rel(eng.views[k], ref[k]) for k in ref)
    eng._stale = {{"P2", "P8"}}
    eng.refresh(block=True)
    report["reeval"] = {{
        "fold_placement": folded, "fold_rel_err": fold_err,
        "refresh_placement": placement(eng.views),
        "refresh_rel_err": max(rel(eng.views[k], ref[k]) for k in ref)}}
    print(json.dumps(report))
""")

_VIEWS = ("A", "P2", "P4", "P8", "P16")
_ROW_BLOCKS = ["PartitionSpec('rows', None)", [0, 1, 2, 3],
               [[_MESH4_N // 4, _MESH4_N]]]


@pytest.fixture(scope="module")
def mesh4_report():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", _MESH4_SCRIPT.format(n=_MESH4_N)], env=env,
        capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    import json
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("origin", ["host", "one device", "sharded"])
def test_mesh_initialize_places_every_view_in_row_blocks(mesh4_report,
                                                         origin):
    """Whether the input comes from the host, whole on one device or
    already sharded, every view ends split 4 ways by rows: each of the
    four devices holds one (n/4, n) block, none a whole view, and the
    values are a plain float32 HIGHEST re-evaluation's."""
    init = mesh4_report["initialize"][origin]
    # computed there: the evaluator's outputs are pinned to row blocks
    assert init["evaluate_out"] == [_ROW_BLOCKS[0]], init
    for name in _VIEWS:
        assert init["placement"][name] == _ROW_BLOCKS, (name, init)
    assert init["rel_err"] < 1e-5, init["rel_err"]


def test_mesh_initialize_spans(mesh4_report):
    """``engine.initialize`` is the root; ``engine.place`` (inputs to
    their shards) and ``engine.evaluate`` open under it."""
    spans = mesh4_report["initialize"]["host"]["spans"]
    by_name = {name: (sid, parent) for name, sid, parent in spans}
    assert set(by_name) == {"engine.initialize", "engine.place",
                            "engine.evaluate"}, spans
    root, root_parent = by_name["engine.initialize"]
    assert root_parent is None
    assert by_name["engine.place"][1] == root
    assert by_name["engine.evaluate"][1] == root


def test_mesh_firings_agree_with_one_device_and_reference(mesh4_report):
    fired = mesh4_report["firings"]
    for name in _VIEWS:
        assert fired["placement"][name] == _ROW_BLOCKS, (name, fired)
    assert fired["vs_one_device"] < 1e-5, fired
    assert fired["vs_reference"] < 1e-5, fired


def test_mesh_collective_bytes_count_each_firing_program(mesh4_report):
    """``EngineStats.collective_bytes`` rises on each firing by the
    collective operand bytes that ``parse_collectives`` reads from the
    compiled text of the program that firing ran (each collective once).
    Only skinny n×r factors cross: a rank-1 firing moves less than half
    of one view's shard (n·n·4/4 bytes), and no collective of any firing
    does.  (At n=256 the factors of five levels are a fair share of n;
    at n=32768 the same count is about 3.7 MiB against a 1 GiB shard.)"""
    fired = mesh4_report["firings"]
    assert fired["rises"] == fired["parsed"], fired
    half_shard = _MESH4_N * _MESH4_N * 4 // 4 // 2
    assert all(0 < r < half_shard for r in fired["rises"][:3]), fired
    assert max(fired["largest"]) < half_shard, fired


def test_mesh_fold_and_refresh_keep_row_blocks(mesh4_report):
    """A fold's re-evaluation and ``refresh()`` run the row-sharded
    evaluator: the views stay in row blocks and stay exact."""
    reeval = mesh4_report["reeval"]
    for key in ("fold_placement", "refresh_placement"):
        for name in _VIEWS:
            assert reeval[key][name] == _ROW_BLOCKS, (key, name, reeval)
    assert reeval["fold_rel_err"] < 1e-5, reeval
    assert reeval["refresh_rel_err"] < 1e-5, reeval


# -- cost-model-driven auto-flush ---------------------------------------------


def test_cost_flush_rank_matches_cost_model():
    """The 'cost' policy's flush point is the first stacked rank where
    batched_strategy stops answering 'stacked' for some view."""
    eng = IncrementalEngine(build_ols_program(96, 48, 1),
                            flush_policy="cost", flush_age=1e9)
    eng.initialize(_ols_inputs(96, 48))
    k_star = eng.cost_flush_rank("X")
    assert k_star > 1
    costs = eng._lowrank_view_costs("X")
    assert costs, "OLS trigger maintains factored views"
    # one rank below: every view still prefers the stacked trigger
    assert all(batched_strategy(shape, k_star - 1, k_star - 1, re) ==
               "stacked" for shape, re in costs)
    # at k_star: some view's incremental sweep loses to re-evaluation
    assert any(batched_strategy(shape, k_star, k_star, re) != "stacked"
               for shape, re in costs)


def test_cost_policy_flushes_exactly_at_crossover():
    eng = IncrementalEngine(build_ols_program(96, 48, 1),
                            flush_policy="cost", flush_age=1e9)
    eng.initialize(_ols_inputs(96, 48))
    k_star = eng.cost_flush_rank("X")
    ups = _updates(96, 48, k_star, seed=29)
    for i, (u, v) in enumerate(ups):
        flushed = eng.enqueue_update("X", u, v)
        assert (flushed is not None) == (i == k_star - 1), (i, k_star)
    assert eng.pending_rank("X") == 0
    assert eng.stats.batches_applied == 1
    assert eng.stats.updates_applied == k_star

    ree = ReevalEngine(build_ols_program(96, 48, 1))
    ree.initialize(_ols_inputs(96, 48))
    for u, v in ups:
        ree.apply_update("X", jnp.asarray(u), jnp.asarray(v))
    assert max_abs_diff(eng.views, ree.views, ("beta",)) < 1e-3


def test_cost_policy_staleness_still_bounds_latency():
    eng = IncrementalEngine(build_ols_program(96, 48, 1),
                            flush_policy="cost", flush_age=0.0)
    eng.initialize(_ols_inputs(96, 48))
    (u, v), = _updates(96, 48, 1, seed=31)
    assert eng.enqueue_update("X", u, v) is not None


def test_flush_policy_validated():
    with pytest.raises(ValueError):
        IncrementalEngine(build_ols_program(96, 48, 1), flush_policy="vibes")


# -- serve checkpoint hooks ---------------------------------------------------


def test_serve_engine_checkpoint_roundtrip(tmp_path):
    from repro.configs import get_config
    from repro.dist.checkpoint import CheckpointManager
    from repro.models import build_model
    from repro.serve import ServeEngine

    cfg = get_config("starcoder2-7b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, batch_size=1, max_seq=64)
    prompts = np.asarray([[5, 9, 2, 7]], np.int32)
    want = eng.generate(prompts, max_new=4)

    mgr = CheckpointManager(str(tmp_path), async_save=False)
    eng.save_checkpoint(mgr, step=1, blocking=True)
    # corrupt the live weights, then restore
    eng.params = jax.tree.map(lambda p: p * 0.0, eng.params)
    eng.restore_checkpoint(mgr, step=1)
    got = eng.generate(prompts, max_new=4)
    np.testing.assert_array_equal(got, want)

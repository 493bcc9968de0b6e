"""Batched trigger pipeline: equivalence, kernels, queue, stats, cost.

The contract under test (ISSUE 1): for any update stream,

    apply_updates([u_1..u_T])  ==  T × apply_update  ==  reevaluate

within fp tolerance, including the QR/SVD re-compression path and
ragged (non-power-of-two) batch sizes; plus the batched rank-update
kernel against its pure-jnp oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.ols import build_ols_program
from repro.core.compiler import batch_bucket, compile_batched_trigger
from repro.core.factored import (pad_factors_to_rank, recompress_factors,
                                 row_support, stack_update_arrays)
from repro.core.iterative import matrix_powers
from repro.core.runtime import IncrementalEngine, ReevalEngine, max_abs_diff
from repro.data.updates import UpdateStream
from repro.kernels import ops, ref

from conftest import assert_close


def _updates(n, m, count, seed=3, rank=1, zipf=None):
    it = iter(UpdateStream(n=n, m=m, rank=rank, scale=0.02, seed=seed,
                           zipf=zipf))
    return [next(it) for _ in range(count)]


def _ols_inputs(m, n, seed=0):
    rng = np.random.default_rng(seed)
    return {"X": jnp.asarray(rng.normal(size=(m, n)), jnp.float32),
            "Y": jnp.asarray(rng.normal(size=(m, 1)), jnp.float32)}


def _powers_inputs(n, seed=0):
    rng = np.random.default_rng(seed)
    a = (0.5 / np.sqrt(n)) * rng.normal(size=(n, n))
    return {"A": jnp.asarray(a, jnp.float32)}


PROGRAMS = {
    "ols": (lambda: build_ols_program(96, 48, 1), lambda: _ols_inputs(96, 48),
            "X", 96, 48),
    "powers": (lambda: matrix_powers(k=8, n=48, model="exp"),
               lambda: _powers_inputs(48), "A", 48, 48),
}


# -- property: batched == sequential == reevaluation -------------------------


@pytest.mark.parametrize("prog_name", sorted(PROGRAMS))
@pytest.mark.parametrize("t_batch", [1, 3, 8, 16])  # 3: ragged, pads to 4
def test_batched_equals_sequential_and_reeval(prog_name, t_batch):
    build, inputs_fn, name, n, m = PROGRAMS[prog_name]
    ups = _updates(n, m, t_batch, seed=11 + t_batch)

    seq = IncrementalEngine(build())
    seq.initialize(inputs_fn())
    for u, v in ups:
        seq.apply_update(name, jnp.asarray(u), jnp.asarray(v))

    bat = IncrementalEngine(build())
    bat.initialize(inputs_fn())
    bat.apply_updates(name, ups, block=True)

    ree = ReevalEngine(build())
    ree.initialize(inputs_fn())
    for u, v in ups:
        ree.apply_update(name, jnp.asarray(u), jnp.asarray(v))

    assert max_abs_diff(seq.views, bat.views) < 1e-3
    outs = tuple(bat.program.output_names())
    assert max_abs_diff(bat.views, ree.views, outs) < 1e-3
    assert bat.stats.updates_applied == t_batch
    assert bat.stats.triggers_fired == 1


@pytest.mark.parametrize("prog_name", sorted(PROGRAMS))
def test_recompression_path_equivalence(prog_name):
    """Zipf-skewed streams exceed max_batch_rank → QR/SVD compaction fires
    and the result still matches plain re-evaluation."""
    build, inputs_fn, name, n, m = PROGRAMS[prog_name]
    ups = _updates(n, m, 16, seed=5, zipf=3.0)

    bat = IncrementalEngine(build(), max_batch_rank=6)
    bat.initialize(inputs_fn())
    bat.apply_updates(name, ups, block=True)
    assert bat.stats.recompressions == 1

    ree = ReevalEngine(build())
    ree.initialize(inputs_fn())
    for u, v in ups:
        ree.apply_update(name, jnp.asarray(u), jnp.asarray(v))
    outs = tuple(bat.program.output_names())
    assert max_abs_diff(bat.views, ree.views, outs) < 1e-3


def test_rank_k_updates_stack():
    """Batches of rank-2 updates stack to rank 2T and stay exact."""
    build, inputs_fn, name, n, m = PROGRAMS["ols"]
    ups = _updates(n, m, 5, seed=9, rank=2)  # stacked rank 10 → bucket 16
    bat = IncrementalEngine(build())
    bat.initialize(inputs_fn())
    bat.apply_updates(name, ups, block=True)
    ree = ReevalEngine(build())
    ree.initialize(inputs_fn())
    for u, v in ups:
        ree.apply_update(name, jnp.asarray(u), jnp.asarray(v))
    assert max_abs_diff(bat.views, ree.views, ("beta",)) < 1e-3


def test_batched_pipeline_pallas_backend():
    """The batched engine with apply_backend='pallas' routes every view
    apply through the one-pass rank_update_batched kernel (interpret mode
    on CPU) and stays exact."""
    build, inputs_fn, name, n, m = PROGRAMS["powers"]
    bat = IncrementalEngine(build(), apply_backend="pallas")
    bat.initialize(inputs_fn())
    ups = _updates(n, m, 8, seed=17)
    bat.apply_updates(name, ups, block=True)

    ree = ReevalEngine(build())
    ree.initialize(inputs_fn())
    for u, v in ups:
        ree.apply_update(name, jnp.asarray(u), jnp.asarray(v))
    outs = tuple(bat.program.output_names())
    assert max_abs_diff(bat.views, ree.views, outs) < 1e-3


# -- factored-stack helpers ---------------------------------------------------


def test_stack_pad_recompress_roundtrip(rng):
    ups = [(rng.normal(size=(32, 2)).astype(np.float32),
            rng.normal(size=(24, 2)).astype(np.float32)) for _ in range(4)]
    P, Q = stack_update_arrays(ups)
    assert P.shape == (32, 8) and Q.shape == (24, 8)
    dense = sum(u @ v.T for u, v in ups)
    assert_close(P @ Q.T, dense)
    P2, Q2 = pad_factors_to_rank(P, Q, batch_bucket(11))
    assert P2.shape[1] == Q2.shape[1] == 16
    assert_close(P2 @ Q2.T, dense)
    # lossless re-compression: numerical rank of 8 random outer products is 8
    P3, Q3 = recompress_factors(P, Q)
    assert P3.shape[1] <= 8
    assert_close(P3 @ Q3.T, dense, rtol=1e-3, atol=1e-3)


def test_recompress_caps_rank(rng):
    # 8 copies of the same rank-1 update: numerical rank is 1
    u = rng.normal(size=(32, 1)).astype(np.float32)
    v = rng.normal(size=(24, 1)).astype(np.float32)
    P, Q = stack_update_arrays([(u, v)] * 8)
    P2, Q2 = recompress_factors(P, Q, tol=1e-4)
    assert P2.shape[1] == 1
    assert_close(P2 @ Q2.T, 8 * (u @ v.T), rtol=1e-3, atol=1e-3)


def _recompress_full_qr(P, Q, max_rank=None, tol=1e-7):
    """Re-compression by thin QR of both whole factors, as it was before
    the row-support route: the reference for factors it does not take."""
    qp, rp = np.linalg.qr(P)
    qq, rq = np.linalg.qr(Q)
    uc, s, vct = np.linalg.svd(rp @ rq.T)
    r = int(np.sum(s > tol * (s[0] if s.size else 0.0)))
    r = max(1, r)
    if max_rank is not None:
        r = min(r, max_rank)
    P2 = qp @ (uc[:, :r] * s[:r])
    Q2 = qq @ vct[:r].T
    return P2.astype(np.float32), Q2.astype(np.float32)


def _factors(case, rng, n=96, m=80):
    """Stacked factors ``(P, Q)`` of one kind of batch."""
    if case == "zipf_one_hot":      # rank-1 row updates, rows repeat
        return stack_update_arrays(_updates(n, m, 48, seed=5, zipf=2.0))
    if case == "sparse_multi_col":  # rank-3 updates of non-unit rows
        rows = rng.choice(n, 10, replace=False)
        ups = []
        for _ in range(8):
            u = np.zeros((n, 3), np.float32)
            u[rng.choice(rows, 4, replace=False)] = rng.normal(size=(4, 3))
            ups.append((u, rng.normal(size=(m, 3)).astype(np.float32)))
        return stack_update_arrays(ups)
    if case == "s_equals_k":        # as many nonzero rows as columns
        P = np.zeros((n, 16), np.float32)
        P[rng.choice(n, 16, replace=False)] = rng.normal(size=(16, 16))
        return P, rng.normal(size=(m, 16)).astype(np.float32)
    if case == "dense":
        return (rng.normal(size=(n, 24)).astype(np.float32),
                rng.normal(size=(m, 24)).astype(np.float32))
    # "spectrum": 12 nonzero rows, K=32, singular values 2^-i, so both a
    # tolerance and a cap cut at a clear gap
    s, K = 12, 32
    W = np.linalg.qr(rng.normal(size=(K, s)))[0]
    U = np.linalg.qr(rng.normal(size=(s, s)))[0]
    V = np.linalg.qr(rng.normal(size=(m, s)))[0]
    P = np.zeros((n, K))
    P[rng.choice(n, s, replace=False)] = U * 2.0 ** -np.arange(s) @ W.T
    return P.astype(np.float32), (V @ W.T).astype(np.float32)


@pytest.mark.parametrize("case,tol,max_rank", [
    ("zipf_one_hot", 1e-7, None),
    ("sparse_multi_col", 1e-7, None),
    ("s_equals_k", 1e-7, None),
    ("dense", 1e-7, None),
    ("spectrum", 1e-2, None),       # the tolerance cuts at rank 7
    ("spectrum", 1e-7, 5),          # the cap cuts below the rank, 12
    ("zipf_one_hot", 1e-7, 4),      # the cap cuts a Zipf batch
])
def test_recompress_factors_row_support_route(case, tol, max_rank, rng):
    """Factors nonzero on fewer rows than columns re-compress on those
    rows: the same product and rank rule as a float64 SVD, and a left
    factor zero off the support.  Other factors take the full QR route,
    bit for bit as before."""
    P, Q = _factors(case, rng)
    K = P.shape[1]
    support = np.flatnonzero(np.any(P != 0, axis=1))
    rows = row_support(P)
    assert (rows is not None) == (support.size < K)
    if rows is not None:
        assert np.array_equal(rows, support)
    P2, Q2 = recompress_factors(P, Q, max_rank=max_rank, tol=tol)
    assert P2.dtype == Q2.dtype == np.float32

    # rank: the float64 SVD of P Qᵀ under the same tolerance and cap
    exact = P.astype(np.float64) @ Q.astype(np.float64).T
    U, s, Vt = np.linalg.svd(exact)
    r = max(1, int(np.sum(s > tol * s[0])))
    r = r if max_rank is None else min(r, max_rank)
    assert P2.shape[1] == Q2.shape[1] == r
    best = (U[:, :r] * s[:r]) @ Vt[:r]
    got = P2.astype(np.float64) @ Q2.astype(np.float64).T
    assert np.abs(got - best).max() <= 1e-5 * np.abs(exact).max()

    off = np.setdiff1d(np.arange(P.shape[0]), support)
    if rows is not None:
        assert not P2[off].any()
    else:
        # the full QR leaves rounding off the support
        assert np.abs(P2[off]).max(initial=0.0) <= 1e-6 * np.abs(P2).max()
        B2, V2 = _recompress_full_qr(P, Q, max_rank=max_rank, tol=tol)
        assert np.array_equal(P2, B2) and np.array_equal(Q2, V2)


def _dense_batch(n, count, seed=1):
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal((n, 1)).astype(np.float32) * 0.1,
             rng.standard_normal((n, 1)).astype(np.float32) * 0.1)
            for _ in range(count)]


@pytest.mark.parametrize("guarded", [False, True])
def test_zipf_batch_recompresses_on_row_support(guarded):
    """A batch of one-hot Zipf row updates over the rank cap fires once,
    re-compressed on its row support, and matches re-evaluation."""
    from repro.guard import GuardConfig
    build, inputs_fn, name, n, m = PROGRAMS["powers"]
    ups = _updates(n, m, 64, seed=5, zipf=2.0)
    distinct = len({int(np.flatnonzero(u)[0]) for u, _ in ups})
    assert distinct <= 16   # under the cap: re-compression is lossless

    bat = IncrementalEngine(build(), max_batch_rank=16,
                            guard=GuardConfig() if guarded else None)
    bat.initialize(inputs_fn())
    bat.apply_updates(name, ups, block=True)
    assert bat.stats.recompressions == 1
    assert bat.stats.support_recompressions == 1
    assert bat.stats.stacked_rank == 64
    assert bat.stats.fired_rank == distinct

    ree = ReevalEngine(build())
    ree.initialize(inputs_fn())
    for u, v in ups:
        ree.apply_update(name, jnp.asarray(u), jnp.asarray(v))
    # every view, A included: the output P8 alone moves by less than
    # 1e-3 under a wrongly re-compressed batch of deltas this small
    assert max_abs_diff(bat.views, ree.views) < 1e-3


@pytest.mark.parametrize("guarded", [False, True])
def test_dense_batch_recompresses_by_full_qr(guarded):
    from repro.guard import GuardConfig
    build, inputs_fn, name, n, _ = PROGRAMS["powers"]
    eng = IncrementalEngine(build(), max_batch_rank=5,
                            guard=GuardConfig() if guarded else None)
    eng.initialize(inputs_fn())
    eng.apply_updates(name, _dense_batch(n, 12), block=True)
    assert eng.stats.recompressions == 1
    assert eng.stats.support_recompressions == 0


def test_batch_bucket():
    assert [batch_bucket(k) for k in (1, 2, 3, 4, 5, 8, 9, 64)] == \
        [1, 2, 4, 4, 8, 8, 16, 64]
    with pytest.raises(ValueError):
        batch_bucket(0)


def test_compile_batched_trigger_rank():
    build, _, name, _, _ = PROGRAMS["ols"]
    eng = IncrementalEngine(build())
    trig = compile_batched_trigger(eng.compiled, name, 8)
    assert trig.rank == 8
    assert trig.input_name == name


# -- batched rank-update kernel ----------------------------------------------


@pytest.mark.parametrize("n,p,k,t", [
    (64, 64, 1, 1), (128, 64, 2, 4), (64, 128, 4, 3),
    (96, 160, 3, 5), (8, 8, 1, 2), (64, 32, 2, 16),
])
def test_rank_update_batched_kernel(n, p, k, t, rng):
    m = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(t, n, k)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(t, p, k)), jnp.float32)
    assert_close(ops.rank_update_batched(m, u, v),
                 ref.rank_update_batched(m, u, v))


def test_rank_update_batched_2d_degenerate(rng):
    m = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(64, 3)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(64, 3)), jnp.float32)
    assert_close(ops.rank_update_batched(m, u, v), ref.rank_update(m, u, v))


def test_rank_update_batched_ragged_fallback(rng):
    # 17 is prime → no usable block, wrapper must fall back to the oracle
    m = jnp.asarray(rng.normal(size=(17, 23)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(2, 17, 1)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 23, 1)), jnp.float32)
    assert_close(ops.rank_update_batched(m, u, v),
                 ref.rank_update_batched(m, u, v))


def test_pick_block_properties():
    from repro.kernels.ops import _pick_block
    for n in (1, 8, 63, 64, 96, 100, 160, 256, 512, 777, 1000, 1024):
        for cap in (8, 100, 512):
            for align in (8, 128):
                b = _pick_block(n, cap, align)
                # an aligned divisor within the cap, else the whole dim
                assert n % b == 0
                assert b == n or (b % align == 0 and b <= cap)
    assert _pick_block(1000, 512, 128) == 1000   # no 128-multiple divides
    assert _pick_block(1000, 512, 8) == 200


# -- update queue -------------------------------------------------------------


def test_queue_flushes_on_size():
    build, inputs_fn, name, n, m = PROGRAMS["ols"]
    eng = IncrementalEngine(build(), flush_size=4, flush_age=1e9)
    eng.initialize(inputs_fn())
    ups = _updates(n, m, 4, seed=21)
    for i, (u, v) in enumerate(ups):
        flushed = eng.enqueue_update(name, u, v)
        assert (flushed is not None) == (i == 3)
    assert eng.pending_rank(name) == 0
    assert eng.stats.batches_applied == 1
    assert eng.stats.updates_applied == 4

    ree = ReevalEngine(build())
    ree.initialize(inputs_fn())
    for u, v in ups:
        ree.apply_update(name, jnp.asarray(u), jnp.asarray(v))
    assert max_abs_diff(eng.views, ree.views, ("beta",)) < 1e-3


def test_queue_flushes_on_staleness():
    build, inputs_fn, name, n, m = PROGRAMS["ols"]
    eng = IncrementalEngine(build(), flush_size=100, flush_age=0.0)
    eng.initialize(inputs_fn())
    (u, v), = _updates(n, m, 1, seed=22)
    assert eng.enqueue_update(name, u, v) is not None  # age 0 → immediate
    assert eng.pending_rank(name) == 0


def test_explicit_flush_all_inputs():
    build, inputs_fn, name, n, m = PROGRAMS["ols"]
    eng = IncrementalEngine(build(), flush_size=100, flush_age=1e9)
    eng.initialize(inputs_fn())
    for u, v in _updates(n, m, 3, seed=23):
        assert eng.enqueue_update(name, u, v) is None
    assert eng.pending_rank(name) == 3
    eng.flush(block=True)
    assert eng.pending_rank(name) == 0
    assert eng.stats.updates_applied == 3


# -- stats accounting ---------------------------------------------------------


def test_stats_timed_vs_untimed():
    """Async firings are counted but never timed; every firing, timed or
    not, adds its rank to the rank counters."""
    build, inputs_fn, name, n, m = PROGRAMS["ols"]
    eng = IncrementalEngine(build())
    eng.initialize(inputs_fn())
    ups = _updates(n, m, 3, seed=31)
    eng.apply_update(name, *map(jnp.asarray, ups[0]))            # async
    eng.apply_update(name, *map(jnp.asarray, ups[1]), block=True)  # timed
    eng.apply_updates(name, [ups[2]], block=True)                  # timed
    assert eng.stats.updates_applied == 3
    assert eng.stats.triggers_fired == 3
    assert eng.stats.trigger_seconds > 0.0
    # three rank-1 firings: nothing to re-compress, bucket 1 each
    assert (eng.stats.stacked_rank, eng.stats.fired_rank,
            eng.stats.padded_rank) == (3, 3, 3)


# -- serving-path contract ----------------------------------------------------


def test_logit_view_batched_contract(rng):
    """Adapter hot-swap deltas coalesce into one batched sweep of the
    corpus logits, matching the dense recompute."""
    from repro.serve.incremental_views import IncrementalLogitView
    H = rng.normal(size=(40, 16)).astype(np.float32)
    W = rng.normal(size=(10, 16)).astype(np.float32)
    view = IncrementalLogitView(H, W, flush_size=3, flush_age=1e9)
    ups = [(0.05 * rng.normal(size=(10, 1)).astype(np.float32),
            0.05 * rng.normal(size=(16, 1)).astype(np.float32))
           for _ in range(3)]
    assert not view.submit_head_update(*ups[0])
    assert not view.submit_head_update(*ups[1])
    assert view.pending_updates == 2
    assert view.submit_head_update(*ups[2])  # third delta trips flush_size
    assert view.pending_updates == 0
    W_new = W + sum(u @ v.T for u, v in ups)
    assert_close(view.logits, H @ W_new.T, rtol=1e-3, atol=1e-3)
    # batched entrypoint, no queue
    view2 = IncrementalLogitView(H, W)
    view2.update_head_batch(ups)
    assert_close(view2.logits, H @ W_new.T, rtol=1e-3, atol=1e-3)


# -- batched cost model -------------------------------------------------------


def test_batched_cost_model():
    from repro.core.cost import (apply_update_cost, batch_crossover_rank,
                                 batched_apply_cost, batched_strategy,
                                 recompress_cost)
    shape = (256, 256)
    seq = apply_update_cost(shape, 1)
    bat = batched_apply_cost(shape, 1, 16)
    assert bat.flops == pytest.approx(16 * seq.flops)
    # the batched pass reads/writes M once, not 16 times
    assert bat.bytes_rw < 16 * seq.bytes_rw
    assert recompress_cost(256, 256, 16).flops > 0

    reeval = 2.0 * 256 ** 3
    assert batched_strategy(shape, 4, 4, reeval) == "stacked"
    # stacked rank beyond the crossover with no compressibility → reeval
    assert batched_strategy(shape, 4096, 4096, reeval) == "reeval"
    assert batch_crossover_rank(shape, reeval) == 256
    # big views, wide batch, tiny numerical rank → compaction wins:
    # QR/SVD is view-size independent while the rank-K sweep is not
    big = (4096, 4096)
    assert batched_strategy(big, 512, 2, 2.0 * 4096 ** 3) == "recompress"


def _prime_chain(n: int):
    """``Y1 = X·W1; Y2 = Y1·W2`` with every view ``n × n``."""
    from repro.core import Program, dim, matmul
    p = Program(name="prime_chain")
    X = p.input("X", (dim("N"), dim("N")))
    W1 = p.input("W1", (dim("N"), dim("N")))
    W2 = p.input("W2", (dim("N"), dim("N")))
    Y1 = p.let("Y1", matmul(X, W1))
    p.let("Y2", matmul(Y1, W2))
    p.outputs = ["Y1", "Y2"]
    return p.bind_dims(N=n)


@pytest.mark.parametrize("path", ["plain", "guarded", "rowlocal",
                                  "guarded_rowlocal"])
def test_pallas_fallbacks_counted(path):
    """A prime width has no 8- or 128-aligned divisor and its whole
    block overflows VMEM, so every Pallas apply takes the XLA reference:
    each firing counts one fallback per view it writes, on every firing
    path, and the views still match the XLA engine."""
    from repro.core.factored import RowLocalCarrier
    n = 1021
    assert ops.rank_update_blocks(n, n, 1, 1) is None
    assert ops.slab_plan(n, np.array([3])) is None
    rng = np.random.default_rng(0)
    inputs = {name: (rng.standard_normal((n, n)) / n ** 0.5
                     ).astype(np.float32) for name in ("X", "W1", "W2")}
    guarded = path.startswith("guarded")
    engines = [IncrementalEngine(_prime_chain(n), {"X": 1},
                                 apply_backend=backend,
                                 guard=True if guarded else None)
               for backend in ("pallas", "xla")]
    for eng in engines:
        eng.initialize(inputs)
    eng, ref_eng = engines
    assert eng._guard_fast_path == guarded
    per_firing = sum(up.kind == "lowrank"
                     for up in eng.compiled.triggers["X"].updates)
    assert per_firing == 3
    firings = 3
    for i in range(firings):
        rows = np.array([7 * i, 7 * i + 500], np.int32)
        block = rng.standard_normal((2, 1)).astype(np.float32)
        V = (rng.standard_normal((n, 1)) / n).astype(np.float32)
        carrier = RowLocalCarrier(rows, block, V, n)
        for e in engines:
            if path.endswith("rowlocal"):
                e.apply_update("X", carrier, block=True)
            else:
                e.apply_update("X", *carrier.factors(), block=True)
        assert eng.stats.pallas_fallbacks == per_firing * (i + 1)
    if path.endswith("rowlocal"):
        assert eng.stats.rowlocal_firings == firings
        assert eng.stats.widened_carriers == 0
    assert ref_eng.stats.pallas_fallbacks == 0
    for name in ("X", "Y1", "Y2"):
        assert_close(eng.views[name], ref_eng.views[name])


# -- one pass per view: apply and products (codegen.fused_view_passes) -------

# each view's delta rank in a rank-1 matrix-powers firing; the top view
# (P16) is the big operand of no product
POWERS_RANK1 = {"A": 1, "P2": 2, "P4": 4, "P8": 8}


def _on_tpu(monkeypatch, yes: bool = True) -> None:
    from repro.core import codegen
    monkeypatch.setattr(codegen, "on_tpu", lambda: yes)


def _fused(trigger, dims):
    from repro.core.codegen import fused_view_passes
    return [fp.view for fp in fused_view_passes(trigger, dims)]


def _fused_rank1():
    from repro.core.codegen import FUSED_PASS_RANKS
    lo, hi = FUSED_PASS_RANKS
    return [v for v, r in POWERS_RANK1.items() if lo <= r <= hi]


def test_fused_pass_selection(monkeypatch):
    """On a TPU a rank-1 matrix-powers firing applies P2, P4 and P8 in
    one pass with their products; a rank-64 bucket, a trigger whose
    views are each the big operand of two products (OLS through
    Woodbury), one whose products read only inputs (the feature chain),
    and any other backend take none."""
    from repro.core.compiler import compile_program
    compiled = compile_program(matrix_powers(k=16, n=256), {"A": 1})
    trig, dims = compiled.triggers["A"], compiled.program.dims
    _on_tpu(monkeypatch)
    assert {"P2", "P4", "P8"} <= set(_fused_rank1())
    assert _fused(trig, dims) == _fused_rank1()
    assert _fused(compile_batched_trigger(compiled, "A", 64), dims) == []
    ols = compile_program(build_ols_program(96, 48, 1), {"X": 1})
    assert _fused(ols.triggers["X"], ols.program.dims) == []
    chain = compile_program(_prime_chain(64), {"X": 1})
    assert _fused(chain.triggers["X"], chain.program.dims) == []
    _on_tpu(monkeypatch, False)
    assert _fused(trig, dims) == []


def _powers_trigger(n):
    from repro.core.compiler import compile_program
    compiled = compile_program(matrix_powers(k=16, n=n), {"A": 1})
    return compiled.triggers["A"], compiled.program.dims


def test_fused_pass_selection_one_shard_is_the_one_chip_selection(
        monkeypatch):
    """A shard count of 1 selects what the one-device firing selects at
    ``powers16k``'s n=16384: A, P2, P4 and P8."""
    from repro.core.codegen import fused_view_passes
    trig, dims = _powers_trigger(16384)
    _on_tpu(monkeypatch)
    assert fused_view_passes(trig, dims, 1) == fused_view_passes(trig, dims)
    assert _fused(trig, dims) == _fused_rank1() == ["A", "P2", "P4", "P8"]


def test_fused_pass_selection_judges_the_row_block(monkeypatch):
    """With the views in four row blocks (``powers32k``'s n=32768), the
    tile fit is judged on the 8192×32768 block a device holds, and the
    same four views qualify."""
    from repro.core.codegen import fused_view_passes
    judged = []
    fit = ops.apply_project_blocks

    def spy(n, m, k, kx, ky):
        judged.append((n, m))
        return fit(n, m, k, kx, ky)

    trig, dims = _powers_trigger(32768)
    _on_tpu(monkeypatch)
    monkeypatch.setattr(ops, "apply_project_blocks", spy)
    passes = fused_view_passes(trig, dims, 4)
    assert [fp.view for fp in passes] == _fused_rank1()
    assert set(judged) == {(8192, 32768)}


def test_fused_pass_selection_block_without_tile_takes_no_pass(monkeypatch):
    """A view whose whole fits a tile but whose row block does not takes
    no pass on the mesh: at n = 16·3·1031 the whole view has 8-row tiles,
    while a quarter's 4·3·1031 rows have no multiple of 8 to divide them,
    so its one tile is the whole block, far past VMEM.  Rows that do not
    split evenly over the shards take no pass either."""
    from repro.core.codegen import fused_view_passes
    n = 16 * 3 * 1031
    assert ops.apply_project_blocks(n, n, 1, 1, 1) is not None
    assert ops.apply_project_blocks(n // 4, n, 1, 1, 1) is None
    _on_tpu(monkeypatch)
    trig, dims = _powers_trigger(n)
    assert [fp.view for fp in fused_view_passes(trig, dims, 1)] == \
        _fused_rank1()
    assert fused_view_passes(trig, dims, 4) == ()
    trig, dims = _powers_trigger(256)
    assert fused_view_passes(trig, dims, 3) == ()


@pytest.mark.parametrize("donate", [False, True])
def test_fused_pass_engine_matches_reeval(monkeypatch, donate):
    """Rank-1 updates through the pass (the kernel interpreted on the
    CPU; with donation, writing over the view's buffer) match
    re-evaluation and the engine staged as before, and each firing
    counts one pass per view the selection picked."""
    n = 256
    plain = IncrementalEngine(matrix_powers(k=16, n=n))
    _on_tpu(monkeypatch)
    eng = IncrementalEngine(matrix_powers(k=16, n=n), donate=donate)
    ree = ReevalEngine(matrix_powers(k=16, n=n))
    for e in (plain, eng, ree):
        e.initialize(_powers_inputs(n))
    assert plain._trigger_fns["A"].fused_views == ()
    assert list(eng._trigger_fns["A"].fused_views) == _fused_rank1()
    ups = _updates(n, n, 6, seed=21)
    for u, v in ups:
        plain.apply_update("A", u, v, block=True)
        eng.apply_update("A", u, v, block=True)
        ree.apply_update("A", jnp.asarray(u), jnp.asarray(v))
    assert max_abs_diff(eng.views, ree.views) < 1e-3
    for name in POWERS_RANK1:
        assert_close(eng.views[name], plain.views[name], rtol=1e-5,
                     atol=1e-6)
    assert eng.stats.triggers_fired == len(ups)
    assert eng.stats.fused_view_passes == len(_fused_rank1()) * len(ups)
    assert plain.stats.fused_view_passes == 0


@pytest.mark.parametrize("fault", ["chaos", "nonfinite"])
def test_fused_pass_guarded_rollback(monkeypatch, fault):
    """A guarded engine whose firings take the pass still rolls an
    aborted firing back bit for bit: a fault raised in the trigger
    restores the same buffers and every counter (the trigger cache's
    build count aside), and a non-finite output is selected out inside
    the fused program."""
    import dataclasses
    from repro.guard import ChaosConfig, GuardConfig
    _on_tpu(monkeypatch)
    n = 256
    eng = IncrementalEngine(matrix_powers(k=16, n=n), guard=GuardConfig(),
                            chaos=ChaosConfig(seed=0))
    eng.initialize(_powers_inputs(n))
    for u, v in _updates(n, n, 2, seed=5):
        eng.apply_update("A", u, v, block=True)
    eng.guard.sync()
    assert eng.stats.fused_view_passes == 2 * len(_fused_rank1())
    before_views = dict(eng.views)
    before = {k: np.asarray(a) for k, a in eng.views.items()}
    before_stats = dataclasses.replace(eng.stats)
    u = np.zeros((n, 1), np.float32)
    u[3] = 1.0
    if fault == "chaos":
        eng.chaos.config = dataclasses.replace(eng.chaos.config,
                                               trigger_raise_p=1.0)
        v = np.full((n, 1), 0.01, np.float32)
        out = eng.apply_update("A", u, v, block=True)
        for k, arr in before_views.items():
            assert out[k] is arr, k
        assert eng.stats == dataclasses.replace(
            before_stats, trigger_builds=eng.stats.trigger_builds)
    else:
        v = np.full((n, 1), 3e38, np.float32)
        eng.apply_update("A", u, v, block=True)
        eng.guard.sync()
    for k, arr in before.items():
        np.testing.assert_array_equal(np.asarray(eng.views[k]), arr)
    assert eng.guard.stats.rollbacks == 1

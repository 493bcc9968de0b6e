"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (ref.py),
in interpret mode (kernel bodies execute on CPU)."""

import pytest

pytest.importorskip("hypothesis", reason="hypothesis is not installed in this container")

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.rank_update import rank_update_pallas
from repro.kernels.dual_matmul import dual_matmul_pallas
from repro.kernels.apply_project import apply_project_pallas

from conftest import assert_close


@pytest.mark.parametrize("n,p,k", [
    (64, 64, 1), (128, 64, 2), (64, 128, 4), (256, 256, 8),
    (96, 160, 3), (8, 8, 1), (512, 64, 16),
])
def test_rank_update_shapes(n, p, k, rng):
    m = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(p, k)), jnp.float32)
    assert_close(ops.rank_update(m, u, v), ref.rank_update(m, u, v))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rank_update_dtypes(dtype, rng):
    m = jnp.asarray(rng.normal(size=(64, 64)), dtype)
    u = jnp.asarray(rng.normal(size=(64, 2)), dtype)
    v = jnp.asarray(rng.normal(size=(64, 2)), dtype)
    got = ops.rank_update(m, u, v)
    want = ref.rank_update(m, u, v)
    assert_close(got.astype(jnp.float32), want.astype(jnp.float32),
                 rtol=2e-2, atol=2e-2)


@settings(max_examples=20, deadline=None)
@given(n=st.sampled_from([32, 64, 96]), p=st.sampled_from([32, 64, 128]),
       k=st.integers(min_value=1, max_value=8),
       seed=st.integers(0, 1000))
def test_rank_update_property(n, p, k, seed):
    rng = np.random.default_rng(seed)
    m = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(p, k)), jnp.float32)
    assert_close(ops.rank_update(m, u, v), ref.rank_update(m, u, v))


@pytest.mark.parametrize("n,m,k", [
    (64, 64, 1), (128, 256, 4), (256, 128, 2), (96, 96, 8),
])
def test_dual_matmul_shapes(n, m, k, rng):
    a = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    p1, q1 = ops.dual_matmul(a, u, v)
    p2, q2 = ref.dual_matmul(a, u, v)
    assert_close(p1, p2, rtol=1e-3)
    assert_close(q1, q2, rtol=1e-3)


def test_dual_matmul_explicit_blocks(rng):
    a = jnp.asarray(rng.normal(size=(128, 128)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(128, 2)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(128, 2)), jnp.float32)
    for bm, bn in ((32, 128), (64, 64), (128, 32)):
        p1, q1 = dual_matmul_pallas(a, u, v, bm=bm, bn=bn, interpret=True)
        p2, q2 = ref.dual_matmul(a, u, v)
        assert_close(p1, p2, rtol=1e-3)
        assert_close(q1, q2, rtol=1e-3)


def test_sherman_morrison_fused(rng):
    base = rng.normal(size=(96, 96))
    w = jnp.asarray(np.linalg.inv(base.T @ base + 5 * np.eye(96)),
                    jnp.float32)
    u = jnp.asarray(rng.normal(size=(96, 1)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(96, 1)), jnp.float32)
    l1, r1 = ops.sherman_morrison_delta(w, u, v)
    l2, r2 = ref.sherman_morrison_delta(w, u, v)
    assert_close(l1, l2, rtol=1e-3)
    assert_close(r1, r2, rtol=1e-3)
    # applying the delta gives the true new inverse
    from repro.core import sherman_morrison
    assert_close(w + l1 @ r1.T, sherman_morrison(w, u, v), rtol=1e-3)


def _apply_project_case(rng, n, m, k, kx=None, ky=None):
    kx = k if kx is None else kx
    ky = k if ky is None else ky
    f32 = lambda *shape: np.asarray(rng.normal(size=shape), np.float32)
    return f32(n, m), f32(n, k), f32(m, k), f32(m, kx), f32(n, ky)


def _assert_apply_project(got, p, u, v, x, y):
    """Each result within 1e-5 of its float64 value, relative to the
    largest entry of that value."""
    p64, u64, v64 = (a.astype(np.float64) for a in (p, u, v))
    want = (p64 + u64 @ v64.T,
            None if x is None else p64 @ x.astype(np.float64),
            None if y is None else p64.T @ y.astype(np.float64))
    for g, w in zip(got, want):
        if w is None:
            assert g is None
            continue
        g = np.asarray(g, np.float64)
        assert g.shape == w.shape
        assert np.abs(g - w).max() <= 1e-5 * np.abs(w).max()


@pytest.mark.parametrize("n,m", [(128, 128), (64, 256), (256, 128)])
@pytest.mark.parametrize("k", [1, 2, 4, 8])
def test_apply_project_shapes(n, m, k, rng):
    p, u, v, x, y = _apply_project_case(rng, n, m, k)
    _assert_apply_project(ops.apply_project(p, u, v, x, y), p, u, v, x, y)


@pytest.mark.parametrize("k", [1, 2, 4, 8])
def test_apply_project_explicit_blocks(k, rng):
    p, u, v, x, y = _apply_project_case(rng, 128, 256, k)
    for bm, bn in ((8, 128), (32, 256), (64, 128), (128, 256)):
        got = apply_project_pallas(p, u, v, x, y, bm=bm, bn=bn,
                                   alias=False, interpret=True)
        _assert_apply_project(got, p, u, v, x, y)


def test_apply_project_one_product_and_other_widths(rng):
    """Either product alone, and product widths other than the
    update's rank."""
    p, u, v, x, y = _apply_project_case(rng, 64, 256, 2, kx=3, ky=5)
    for xx, yy in ((x, None), (None, y), (x, y)):
        _assert_apply_project(ops.apply_project(p, u, v, xx, yy),
                              p, u, v, xx, yy)


def test_apply_project_falls_back_to_reference(rng):
    """A prime width has no aligned divisor and its whole block
    overflows VMEM: the XLA reference answers instead."""
    n = 3001
    assert ops.apply_project_blocks(n, n, 2, 2, 2) is None
    p, u, v, x, y = _apply_project_case(rng, n, n, 2)
    got = ops.apply_project(p, u, v, x, y)
    _assert_apply_project(got, p, u, v, x, y)
    for g, w in zip(got, ref.apply_project(p, u, v, x, y)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_apply_project_leaves_undonated_input(rng):
    """Without ``alias`` the new view is a fresh buffer: the input view
    keeps its old values, eagerly and inside a jitted program."""
    p, u, v, x, y = _apply_project_case(rng, 128, 256, 4)
    view = jnp.asarray(p)
    got = ops.apply_project(view, u, v, x, y)
    np.testing.assert_array_equal(np.asarray(view), p)
    _assert_apply_project(got, p, u, v, x, y)
    jitted = jax.jit(lambda view, u, v, x, y: ops.apply_project(
        view, u, v, x, y))
    got = jitted(view, u, v, x, y)
    np.testing.assert_array_equal(np.asarray(view), p)
    _assert_apply_project(got, p, u, v, x, y)


@pytest.mark.parametrize("h,hkv,d,s,extra", [
    (8, 2, 64, 512, 0), (4, 4, 32, 256, 100), (16, 1, 64, 1024, 5),
    (8, 8, 128, 256, 0),
])
def test_flash_decode_shapes(h, hkv, d, s, extra, rng):
    q = jnp.asarray(rng.normal(size=(h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(s, hkv, d)), jnp.float32)
    ln = jnp.asarray(s - extra, jnp.int32)
    assert_close(ops.flash_decode(q, k, v, ln),
                 ref.flash_decode(q, k, v, ln), rtol=2e-3, atol=2e-3)


@settings(max_examples=15, deadline=None)
@given(s=st.sampled_from([128, 256, 384]),
       h=st.sampled_from([4, 8]),
       length_frac=st.floats(min_value=0.1, max_value=1.0),
       seed=st.integers(0, 500))
def test_flash_decode_property(s, h, length_frac, seed):
    rng = np.random.default_rng(seed)
    d = 32
    q = jnp.asarray(rng.normal(size=(h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32)
    ln = jnp.asarray(max(1, int(s * length_frac)), jnp.int32)
    got = ops.flash_decode(q, k, v, ln)
    want = ref.flash_decode(q, k, v, ln)
    assert_close(got, want, rtol=2e-3, atol=2e-3)


def test_trigger_with_pallas_backend(rng):
    """The codegen hook: triggers applied through the Pallas rank-update
    kernel give the same views as the XLA path."""
    from repro.apps import MatrixPowers
    ax = MatrixPowers(n=64, k=4, model="exp", apply_backend="pallas")
    bx = MatrixPowers(n=64, k=4, model="exp", apply_backend="xla")
    inputs = MatrixPowers.synthesize(64, seed=9)
    ax.initialize(inputs)
    bx.initialize(inputs)
    u, v = ax.row_update(0, rng.normal(size=64) * 0.1)
    assert_close(ax.update(u, v), bx.update(u, v), rtol=1e-4)


@pytest.mark.parametrize("s,hd,causal,bq,bk", [
    (256, 64, True, 128, 128), (512, 32, True, 256, 128),
    (256, 64, False, 64, 256), (384, 128, True, 128, 128),
])
def test_flash_attention_shapes(s, hd, causal, bq, bk, rng):
    from repro.kernels.flash_attention import flash_attention_pallas
    q = jnp.asarray(rng.normal(size=(s, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(s, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(s, hd)), jnp.float32)
    got = flash_attention_pallas(q, k, v, bq=bq, bk=bk, causal=causal,
                                 interpret=True)
    want = ref.flash_attention(q, k, v, causal=causal)
    assert_close(got, want, rtol=2e-3, atol=2e-3)


def test_flash_attention_multihead_matches_blockwise(rng):
    """The Pallas kernel agrees with the model substrate's XLA blockwise
    attention (the thing it replaces on TPU)."""
    from repro.kernels import ops as kops
    from repro.models.attention import blockwise_attention
    b, s, h, hd = 2, 256, 4, 64
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    got = kops.flash_attention(q, k, v, causal=True)
    want = blockwise_attention(q, k, v, causal=True)
    assert_close(got, want, rtol=2e-3, atol=2e-3)

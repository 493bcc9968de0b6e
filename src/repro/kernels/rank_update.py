"""Pallas TPU kernel: rank-k view update  ``M += U Vᵀ``  (the trigger hot loop).

Every LINVIEW trigger ends in one rank-k GER per maintained view (paper
Alg. 1's ``+=`` statements).  With k ≪ n the op is memory-bound
(arithmetic intensity ≈ k/6 FLOP/byte in f32), so the kernel's job is to
stream M through VMEM exactly once at full HBM bandwidth while the MXU
computes the (bm × k) @ (k × bn) tile products.

TPU adaptation (vs the paper's BLAS GER):
  * M is tiled (bm × bn): bm a multiple of 8 and bn of 128 (or the whole
    dimension), the (8, 128) f32 VREG tile; U/V tiles live in VMEM across
    a whole row / column of the grid.  A k-skinny tile still pads its k
    lanes to 128, which ``ops.rank_update_blocks`` budgets for.
  * the update is done in place via input/output aliasing — M is read and
    written once, the roofline optimum for this op.
  * rank k is padded to the lane width (128) by ``ops.rank_update`` when
    it pays off on the MXU; the kernel itself takes any static k.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BLOCK = (256, 256)
# full f32 products: the default TPU precision rounds f32 operands to bf16
HIGHEST = jax.lax.Precision.HIGHEST


def _rank_update_kernel(m_ref, u_ref, v_ref, o_ref):
    # one (bm, bn) tile of M; U tile (bm, k); V tile (bn, k).
    # accumulate in f32 on the MXU, store back in the view dtype.
    upd = jnp.dot(u_ref[...], v_ref[...].T, precision=HIGHEST,
                  preferred_element_type=jnp.float32)
    o_ref[...] = (m_ref[...].astype(jnp.float32) + upd).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def rank_update_pallas(m: jax.Array, u: jax.Array, v: jax.Array,
                       *, bm: int = DEFAULT_BLOCK[0], bn: int = DEFAULT_BLOCK[1],
                       interpret: bool) -> jax.Array:
    """``m + u @ v.T`` with m: (n, p), u: (n, k), v: (p, k)."""
    n, p = m.shape
    k = u.shape[1]
    assert u.shape == (n, k) and v.shape == (p, k), (m.shape, u.shape, v.shape)
    bm = min(bm, n)
    bn = min(bn, p)
    if n % bm or p % bn:
        raise ValueError(f"shape ({n},{p}) not divisible by block ({bm},{bn})")
    grid = (n // bm, p // bn)
    return pl.pallas_call(
        _rank_update_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),   # M tile
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),    # U row-panel
            pl.BlockSpec((bn, k), lambda i, j: (j, 0)),    # V row-panel
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, p), m.dtype),
        input_output_aliases={0: 0},                        # in-place on M
        interpret=interpret,
    )(m, u, v)


def _rank_update_batched_kernel(m_ref, u_ref, v_ref, o_ref):
    # one (bm, bn) tile of M; U stack (T, bm, k); V stack (T, bn, k).
    # All T tile-products accumulate in a VMEM f32 register tile; M is
    # read once and written once — the single-pass contract that makes a
    # batch of T updates cost one HBM sweep instead of T.
    t = u_ref.shape[0]
    acc = m_ref[...].astype(jnp.float32)

    def body(i, acc):
        return acc + jnp.dot(u_ref[i], v_ref[i].T, precision=HIGHEST,
                             preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(0, t, body, acc)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def rank_update_batched_pallas(m: jax.Array, u: jax.Array, v: jax.Array,
                               *, bm: int = DEFAULT_BLOCK[0],
                               bn: int = DEFAULT_BLOCK[1],
                               interpret: bool) -> jax.Array:
    """``m + Σ_t u[t] @ v[t].T`` — the batched trigger hot loop.

    m: (n, p); u: (T, n, k); v: (T, p, k) — a stream of T rank-k updates
    applied in ONE tiled pass over m.  The sequential path streams m
    through HBM T times (arithmetic intensity k/6); the batched kernel
    streams it once (intensity T·k/6), which is exactly the §6 batching
    argument restated on the roofline.
    """
    n, p = m.shape
    t, _, k = u.shape
    assert u.shape == (t, n, k) and v.shape == (t, p, k), \
        (m.shape, u.shape, v.shape)
    bm = min(bm, n)
    bn = min(bn, p)
    if n % bm or p % bn:
        raise ValueError(f"shape ({n},{p}) not divisible by block ({bm},{bn})")
    grid = (n // bm, p // bn)
    return pl.pallas_call(
        _rank_update_batched_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),      # M tile
            pl.BlockSpec((t, bm, k), lambda i, j: (0, i, 0)),  # U panels
            pl.BlockSpec((t, bn, k), lambda i, j: (0, j, 0)),  # V panels
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, p), m.dtype),
        input_output_aliases={0: 0},                           # in-place on M
        interpret=interpret,
    )(m, u, v)

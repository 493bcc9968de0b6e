"""Pallas TPU kernel: row-local rank-k view update (sparse trigger hot loop).

A row-local carrier touches ``r`` of ``n`` rows (``ΔM = scatter(rows, B) Vᵀ``
with row support ⊆ ``rows``).  The dense kernel in
:mod:`repro.kernels.rank_update` still streams all ``n·m`` of M through
VMEM; at 1% affected rows that is a 100x overshoot in HBM traffic for an
op that was memory-bound to begin with.  This kernel sweeps only the
**touched row slabs**:

  * the affected rows are grouped into ``slab``-row blocks; the ids of
    the touched slabs are *scalar-prefetched* (``PrefetchScalarGridSpec``)
    so the BlockSpec index maps gather exactly those M/U slabs — the
    pipeline's double-buffered DMA then only ever moves touched slabs;
  * M is updated in place via input/output aliasing; untouched slabs are
    never fetched or written (the alias keeps their bytes);
  * the left factor U is the dense-shaped ``(n, k)`` array the trigger
    already computed — zero outside the affected rows for any
    row-support-preserving view — so gathering its slabs via the same
    prefetched ids is exact, and a *padding* slab id (an untouched slab,
    used to keep the grid static) contributes ``+ 0``.

Exactness contract: padding slab ids must reference **distinct untouched
slabs** (each grid row writes its slab once — a repeated id would make
the aliased read-modify-write order-dependent).  ``ops.rank_update_rows``
enforces this and takes the dense kernel when the affected fraction
makes slab sweeping pointless.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rows_kernel(ids_ref, m_ref, u_ref, v_ref, o_ref):
    # one (slab, bn) tile of a touched M slab; U slab (1, slab, k);
    # V tile (bn, k).  ids_ref is consumed by the index maps only.
    del ids_ref
    upd = jnp.dot(u_ref[0], v_ref[...].T, precision=jax.lax.Precision.HIGHEST,
                  preferred_element_type=jnp.float32)
    o_ref[...] = (m_ref[...].astype(jnp.float32) + upd).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("slab", "bn", "interpret"))
def rank_update_rows_pallas(m: jax.Array, slab_ids: jax.Array,
                            u: jax.Array, v: jax.Array, *,
                            slab: int, bn: int,
                            interpret: bool) -> jax.Array:
    """``m + u @ v.T`` sweeping only the row slabs named by ``slab_ids``.

    m: (n, p); u: (n, k) with row support contained in the listed slabs;
    v: (p, k); slab_ids: (S,) int32 — **distinct** slab indices, touched
    slabs plus optional untouched-slab padding (u is zero there).  The
    grid is (S, p/bn): wall-clock scales with the touched row count, not
    n.  Jit-compatible — slab ids are data, their count is static.
    """
    n, p = m.shape
    k = u.shape[1]
    s = slab_ids.shape[0]
    assert u.shape == (n, k) and v.shape == (p, k), (m.shape, u.shape, v.shape)
    if n % slab or p % bn:
        raise ValueError(f"shape ({n},{p}) not divisible by ({slab},{bn})")
    u_slabs = u.reshape(n // slab, slab, k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s, p // bn),
        in_specs=[
            pl.BlockSpec((slab, bn), lambda i, j, ids: (ids[i], j)),     # M
            pl.BlockSpec((1, slab, k), lambda i, j, ids: (ids[i], 0, 0)),  # U
            pl.BlockSpec((bn, k), lambda i, j, ids: (j, 0)),             # V
        ],
        out_specs=pl.BlockSpec((slab, bn), lambda i, j, ids: (ids[i], j)),
    )
    return pl.pallas_call(
        _rows_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, p), m.dtype),
        input_output_aliases={1: 0},  # in-place on M (arg 0 is slab_ids)
        interpret=interpret,
    )(slab_ids, m, u_slabs, v)


def rank_update_rows_ref(m: jax.Array, rows: jax.Array, block: jax.Array,
                         v: jax.Array) -> jax.Array:
    """XLA scatter reference: ``m.at[rows].add(block[rows-compact] @ v.T)``.

    ``rows`` may be padded with the out-of-bounds sentinel ``n`` (matching
    ``block`` rows zero): JAX drops out-of-bounds scatter indices, so the
    padding contributes nothing — this is what lets callers keep a static
    row bucket under jit.
    """
    # no unique_indices promise: sentinel padding repeats the value n
    return m.at[rows].add(jnp.dot(block, v.T,
                                  precision=jax.lax.Precision.HIGHEST,
                                  preferred_element_type=jnp.float32),
                          indices_are_sorted=True)

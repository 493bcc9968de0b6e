"""Pallas TPU kernel: fused dual tall-skinny matmul  ``(A·U, Aᵀ·V)``.

Factored delta propagation (paper §4.3, Example 4.6) evaluates, for every
squaring-style statement, *both* ``B·U`` and ``Bᵀ·V`` against the same big
view B.  Done as two XLA matmuls, B is streamed from HBM twice; both are
memory-bound (intensity ≈ k/2), so the second pass is pure waste.  This
kernel reads each tile of B once and feeds both products — halving HBM
traffic for the dominant term of the trigger.

Layout: the k-skinny factors travel transposed, ``(k, ·)``, so their long
dimension lies along the 128 lanes; an ``(n, k)`` block would pad its k
lanes to 128 and cost 128/k times its size in VMEM.

Grid design (TPU revisit-safety): a 2-D grid, column panels ``j`` of A
outer, row blocks ``i`` inner.
  * ``Qᵀ[:, j] += Vᵀ[:, i] · A[i, j]`` accumulates into one (k × bn)
    output block whose index only changes with ``j`` — consecutive
    revisits, the standard reduction pattern.
  * ``Pᵀ[:, i] += U[j]ᵀ · A[i, j]ᵀ`` lands in a slab of the whole
    ``Pᵀ``, which stays resident in VMEM for the entire grid, as does
    ``Vᵀ``.  Both are held as ``(n/bm, k, bm)`` so the slab is picked by
    a leading index.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

HIGHEST = jax.lax.Precision.HIGHEST


def _dual_matmul_kernel(a_ref, ut_ref, vt_ref, pt_ref, qt_ref):
    j, i = pl.program_id(0), pl.program_id(1)
    a = a_ref[...]                                        # (bm, bn) tile
    pu = jax.lax.dot_general(ut_ref[...], a, (((1,), (1,)), ((), ())),
                             precision=HIGHEST,
                             preferred_element_type=jnp.float32)  # (k, bm)
    qv = jnp.dot(vt_ref[i], a, precision=HIGHEST,
                 preferred_element_type=jnp.float32)      # (k, bn)

    @pl.when(j == 0)
    def _init_p():
        pt_ref[i] = pu

    @pl.when(j != 0)
    def _acc_p():
        pt_ref[i] = pt_ref[i] + pu

    @pl.when(i == 0)
    def _init_q():
        qt_ref[...] = qv

    @pl.when(i != 0)
    def _acc_q():
        qt_ref[...] = qt_ref[...] + qv


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def dual_matmul_pallas(a: jax.Array, u: jax.Array, v: jax.Array,
                       *, bm: int, bn: int, interpret: bool):
    """Returns ``(a @ u, a.T @ v)``; a: (n, m), u: (m, k), v: (n, k)."""
    n, m = a.shape
    k = u.shape[1]
    assert u.shape == (m, k) and v.shape == (n, k), (a.shape, u.shape, v.shape)
    if n % bm or m % bn:
        raise ValueError(f"({n},{m}) not divisible by tile ({bm},{bn})")
    nb = n // bm
    ut = u.T                                              # (k, m)
    vt = v.reshape(nb, bm, k).transpose(0, 2, 1)          # (nb, k, bm)
    pt, qt = pl.pallas_call(
        _dual_matmul_kernel,
        grid=(m // bn, nb),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda j, i: (i, j)),        # A tile
            pl.BlockSpec((k, bn), lambda j, i: (0, j)),         # U panelᵀ
            pl.BlockSpec((nb, k, bm), lambda j, i: (0, 0, 0)),  # Vᵀ, whole
        ],
        out_specs=[
            pl.BlockSpec((nb, k, bm), lambda j, i: (0, 0, 0)),  # Pᵀ, whole
            pl.BlockSpec((k, bn), lambda j, i: (0, j)),         # Q panelᵀ
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, k, bm), jnp.float32),
            jax.ShapeDtypeStruct((k, m), jnp.float32),
        ],
        interpret=interpret,
    )(a, ut, vt)
    return pt.transpose(0, 2, 1).reshape(n, k), qt.T

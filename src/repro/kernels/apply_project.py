"""Pallas TPU kernel: apply a view's update and project the old view, in
one pass — ``(P + U Vᵀ, P·X, Pᵀ·Y)``.

A squaring-style trigger level (paper §4.3, Example 4.6) takes both
products ``P·X`` and ``Pᵀ·Y`` of a view against skinny factors and then
applies the view's own rank-k update ``P += U Vᵀ``.  Staged as XLA ops,
the apply and each product read P from HBM on their own: three reads
and one write of a view that one read and one write would serve.  This
kernel reads each tile of P once, writes the updated tile, and feeds
both products from the same (old) tile.

All arithmetic is float32 on the VPU, with no bfloat16 rounding
anywhere: per element of P, the apply and each product take k
multiplies and k adds.  That is cheap next to the HBM traffic at the
small ranks the engine gives the pass (``codegen.FUSED_PASS_RANKS``);
the rank-k product is summed before it meets the view, so the view
takes one rounding per firing, as in ``P + (U Vᵀ)``.

Grid: row blocks ``i`` outer, column panels ``j`` inner.
  * ``P·X[i] += P[i, j] · X[j]`` lands in one ``(bm, kx)`` output block
    whose index changes only with ``i``: consecutive revisits.
  * ``(Pᵀ·Y)ᵀ[:, j] += Y[i]ᵀ · P[i, j]`` lands in a slab of the whole
    ``(Pᵀ·Y)ᵀ``, held as ``(m/bn, ky, bn)`` and resident in VMEM for the
    whole grid, picked by the leading index ``j``.
Skinny factors whose columns are broadcast along lanes (U, Y) travel as
``(n, k)`` blocks; those whose rows are broadcast along sublanes (Vᵀ,
Xᵀ) travel transposed, ``(k, m)``, with the long dimension on lanes.

The updated view is a fresh output unless ``alias`` is set: aliasing an
operand the caller did not donate makes XLA copy the whole view first.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(has_x: bool, has_y: bool, p_ref, u_ref, vt_ref, *refs):
    refs = list(refs)
    xt_ref = refs.pop(0) if has_x else None
    y_ref = refs.pop(0) if has_y else None
    o_ref = refs.pop(0)
    px_ref = refs.pop(0) if has_x else None
    qt_ref = refs.pop(0) if has_y else None
    i, j = pl.program_id(0), pl.program_id(1)
    p = p_ref[...]                                        # (bm, bn), old
    u, vt = u_ref[...], vt_ref[...]                       # (bm, k), (k, bn)
    # the rank-k product first, then one rounding at the view's scale
    uv = u[:, 0:1] * vt[0:1, :]
    for t in range(1, u.shape[1]):
        uv = uv + u[:, t:t + 1] * vt[t:t + 1, :]
    o_ref[...] = p + uv
    if has_x:
        xt = xt_ref[...]                                  # (kx, bn)
        cols = [jnp.sum(p * xt[s:s + 1, :], axis=1, keepdims=True)
                for s in range(xt.shape[0])]
        part = jnp.concatenate(cols, axis=1) if len(cols) > 1 else cols[0]

        @pl.when(j == 0)
        def _init_px():
            px_ref[...] = part

        @pl.when(j != 0)
        def _acc_px():
            px_ref[...] += part

    if has_y:
        y = y_ref[...]                                    # (bm, ky)
        rows = [jnp.sum(p * y[:, t:t + 1], axis=0, keepdims=True)
                for t in range(y.shape[1])]
        part = jnp.concatenate(rows, axis=0) if len(rows) > 1 else rows[0]

        @pl.when(i == 0)
        def _init_qt():
            qt_ref[j] = part

        @pl.when(i != 0)
        def _acc_qt():
            qt_ref[j] = qt_ref[j] + part


@functools.partial(jax.jit, static_argnames=("bm", "bn", "alias",
                                             "interpret", "vmem_limit"))
def apply_project_pallas(p: jax.Array, u: jax.Array, v: jax.Array,
                         x: Optional[jax.Array], y: Optional[jax.Array],
                         *, bm: int, bn: int, alias: bool, interpret: bool,
                         vmem_limit: Optional[int] = None):
    """``(p + u @ v.T, p @ x, p.T @ y)`` with p: (n, m), u: (n, k),
    v: (m, k), x: (m, kx), y: (n, ky); ``x`` or ``y`` may be ``None``,
    and so is its product then.  ``vmem_limit`` raises the scoped VMEM
    Mosaic grants the kernel, for tiles larger than its default allows."""
    n, m = p.shape
    k = u.shape[1]
    assert u.shape == (n, k) and v.shape == (m, k), (p.shape, u.shape,
                                                     v.shape)
    if n % bm or m % bn:
        raise ValueError(f"({n},{m}) not divisible by tile ({bm},{bn})")
    args = [p, u, v.T]
    in_specs = [pl.BlockSpec((bm, bn), lambda i, j: (i, j)),   # P tile
                pl.BlockSpec((bm, k), lambda i, j: (i, 0)),    # U rows
                pl.BlockSpec((k, bn), lambda i, j: (0, j))]    # Vᵀ panel
    out_specs = [pl.BlockSpec((bm, bn), lambda i, j: (i, j))]  # new tile
    out_shape = [jax.ShapeDtypeStruct((n, m), p.dtype)]
    if x is not None:
        kx = x.shape[1]
        args.append(x.T)
        in_specs.append(pl.BlockSpec((kx, bn), lambda i, j: (0, j)))
        out_specs.append(pl.BlockSpec((bm, kx), lambda i, j: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((n, kx), jnp.float32))
    if y is not None:
        ky, nbj = y.shape[1], m // bn
        args.append(y)
        in_specs.append(pl.BlockSpec((bm, ky), lambda i, j: (i, 0)))
        out_specs.append(pl.BlockSpec((nbj, ky, bn),
                                      lambda i, j: (0, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((nbj, ky, bn), jnp.float32))
    params = ({} if interpret or vmem_limit is None else {
        "compiler_params": pltpu.CompilerParams(vmem_limit_bytes=vmem_limit)})
    outs = iter(pl.pallas_call(
        functools.partial(_kernel, x is not None, y is not None),
        grid=(n // bm, m // bn), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, input_output_aliases={0: 0} if alias else {},
        interpret=interpret, **params)(*args))
    new = next(outs)
    px = next(outs) if x is not None else None
    pty = (next(outs).transpose(0, 2, 1).reshape(m, -1)
           if y is not None else None)
    return new, px, pty

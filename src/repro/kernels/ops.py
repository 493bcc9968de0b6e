"""Jit'd public wrappers around the Pallas kernels.

Each wrapper:
  * picks block sizes Mosaic accepts — the last dimension of a block a
    multiple of 128 or the whole array dimension, the one before it a
    multiple of 8 or the whole dimension — against a VMEM budget that
    counts each block padded to the (8, 128) tile and double-buffered;
  * takes the XLA reference instead when no blocking fits
    (``rank_update_batched``, the engine's apply, reports that through
    ``on_fallback`` so the engine can count it);
  * runs the kernel compiled on a TPU backend and interpreted elsewhere
    (:func:`interpret_mode`, the one place that choice is made).
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import ref
from .apply_project import apply_project_pallas
from .dual_matmul import dual_matmul_pallas
from .flash_attention import flash_attention_pallas
from .flash_decode import flash_decode_pallas
from .rank_update import rank_update_batched_pallas, rank_update_pallas
from .rank_update_rows import rank_update_rows_pallas, rank_update_rows_ref

# what a kernel's pipelined blocks may take of the 16 MiB of scoped VMEM
# Mosaic grants a kernel on v5e by default; the rest is left for the
# kernel body's own temporaries
VMEM_BUDGET = 12 * 1024 * 1024
SUBLANE, LANE = 8, 128

def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether Pallas kernels run interpreted: an explicit choice wins,
    else compiled on a TPU backend and interpreted on any other."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


@functools.lru_cache(maxsize=4096)
def _divisors(n: int) -> Tuple[int, ...]:
    """Sorted divisors of n via O(√n) complement-pair enumeration."""
    small, large = [], []
    d = 1
    while d * d <= n:
        if n % d == 0:
            small.append(d)
            if d != n // d:
                large.append(n // d)
        d += 1
    return tuple(small + large[::-1])


@functools.lru_cache(maxsize=4096)
def _pick_block(n: int, cap: int, align: int = SUBLANE) -> int:
    """Largest divisor of ``n`` that is ≤ ``cap`` and a multiple of
    ``align``; ``n`` itself (the whole dimension, always a legal block)
    when there is none.  Memoized: it runs on every wrapper call."""
    best = n
    for b in _divisors(n):
        if b > cap:
            break
        if b % align == 0:
            best = b
    return best


def _shrink_block(n: int, b: int, align: int) -> Optional[int]:
    """Next divisor of ``n`` below ``b`` that is a multiple of ``align``
    (``None`` when there is none)."""
    cands = [d for d in _divisors(n) if d < b and d % align == 0]
    return cands[-1] if cands else None


def tile_bytes(shape: Sequence[int], itemsize: int = 4) -> int:
    """VMEM bytes of one block: its last two dimensions pad to the
    (sublane, 128) tile, 8 sublanes of 32-bit words."""
    *lead, r, c = (1, *shape) if len(shape) == 1 else shape
    sub = SUBLANE * 4 // itemsize
    return (math.prod(lead) * (-(-r // sub) * sub)
            * (-(-c // LANE) * LANE) * itemsize)


def vmem_bytes(blocks: Sequence[Sequence[int]],
               scratch: Sequence[Sequence[int]] = ()) -> int:
    """Footprint of a pipelined kernel: every block double-buffered,
    plus single-buffered f32 scratch tiles of the body."""
    return (2 * sum(tile_bytes(b) for b in blocks)
            + sum(tile_bytes(b) for b in scratch))


def _fit_blocks(n: int, p: int, footprint: Callable[[int, int], int],
                cap: int = 512, budget: int = VMEM_BUDGET
                ) -> Optional[Tuple[int, int]]:
    """Row block ``bm`` (8-aligned) and lane block ``bn`` (128-aligned)
    of an ``(n, p)`` array whose ``footprint(bm, bn)`` fits ``budget``,
    shrinking the larger one first; ``None`` when nothing fits."""
    bm = _pick_block(n, cap, SUBLANE)
    bn = _pick_block(p, cap, LANE)
    while footprint(bm, bn) > budget:
        sm = _shrink_block(n, bm, SUBLANE)
        sn = _shrink_block(p, bn, LANE)
        if sm is not None and (bm >= bn or sn is None):
            bm = sm
        elif sn is not None:
            bn = sn
        else:
            return None
    return bm, bn


def rank_update_blocks(n: int, p: int, t: int, k: int
                       ) -> Optional[Tuple[int, int]]:
    """``(bm, bn)`` for a ``(n, p)`` view under ``t`` stacked rank-``k``
    factor pairs, or ``None`` when no blocking fits VMEM."""
    return _fit_blocks(n, p, lambda bm, bn: vmem_bytes(
        [(bm, bn), (t, bm, k), (t, bn, k), (bm, bn)], scratch=[(bm, bn)]))


def rank_update(m: jax.Array, u: jax.Array, v: jax.Array,
                interpret: Optional[bool] = None) -> jax.Array:
    """``m + u @ v.T`` — in-place rank-k view update (trigger apply step)."""
    n, p = m.shape
    blocks = rank_update_blocks(n, p, 1, u.shape[1])
    if blocks is None:
        return ref.rank_update(m, u, v)
    return rank_update_pallas(m, u, v, bm=blocks[0], bn=blocks[1],
                              interpret=interpret_mode(interpret))


def rank_update_batched(m: jax.Array, u: jax.Array, v: jax.Array,
                        interpret: Optional[bool] = None,
                        on_fallback: Optional[Callable[[str], None]] = None
                        ) -> jax.Array:
    """``m + Σ_t u[t] @ v[t].T`` — T coalesced trigger applies, one pass.

    u: (T, n, k), v: (T, p, k).  Accepts 2-D (n, k)/(p, k) factors as the
    T=1 degenerate case.  The block picker budgets the full stacked panel
    (T·k columns of U and V per tile) against VMEM.
    """
    if u.ndim == 2:
        u = u[None]
        v = v[None]
    n, p = m.shape
    t, _, k = u.shape
    blocks = rank_update_blocks(n, p, t, k)
    if blocks is None:
        if on_fallback is not None:
            on_fallback(f"rank_update_batched: no VMEM-fitting blocks "
                        f"for {tuple(m.shape)}")
        return ref.rank_update_batched(m, u, v)
    return rank_update_batched_pallas(m, u, v, bm=blocks[0], bn=blocks[1],
                                      interpret=interpret_mode(interpret))


def slab_plan(n: int, rows, *, max_fraction: float = 0.25
              ) -> Optional[Tuple[int, "jnp.ndarray"]]:
    """Host-side slab plan for a row-local sweep: ``(slab, slab_ids)``.

    Groups the affected rows (concrete, host-visible indices) into
    ``slab``-row blocks and pads the touched-slab id list to a power-of-
    two bucket with **distinct untouched** slab ids, so repeated row
    patterns reuse one compiled kernel per bucket and the aliased
    in-place write stays order-independent (each slab visited once).
    Returns ``None`` when the slab sweep cannot win — touched fraction
    above ``max_fraction`` after padding, or too few untouched slabs to
    pad with — and the caller should take the dense kernel instead.
    """
    import numpy as np
    rows = np.asarray(rows)
    if rows.size == 0:
        return None
    slab = _pick_block(n, 256)
    if slab >= n:
        return None
    ids = np.unique(rows // slab)
    bucket = 1 << (int(ids.size) - 1).bit_length()
    num_slabs = n // slab
    if bucket * slab > max_fraction * n or bucket > num_slabs:
        return None
    if bucket > ids.size:
        touched = np.zeros(num_slabs, dtype=bool)
        touched[ids] = True
        free = np.flatnonzero(~touched)[:bucket - ids.size]
        if free.size < bucket - ids.size:
            return None
        ids = np.concatenate([ids, free])
    return slab, jnp.asarray(ids.astype(np.int32))


def rank_update_rows_block(slab: int, p: int, k: int) -> Optional[int]:
    """Lane block ``bn`` of the touched-slab kernel for ``slab``-row
    slabs of a ``(·, p)`` view, or ``None`` when no blocking fits."""
    bn = _pick_block(p, 512, LANE)
    while vmem_bytes([(slab, bn), (1, slab, k), (bn, k), (slab, bn)],
                     scratch=[(slab, bn)]) > VMEM_BUDGET:
        bn = _shrink_block(p, bn, LANE)
        if bn is None:
            return None
    return bn


def rank_update_rows(m: jax.Array, rows, block, v: jax.Array,
                     *, max_fraction: float = 0.25,
                     interpret: Optional[bool] = None) -> jax.Array:
    """Row-local rank-k view update: ``m + scatter(rows, block) @ v.T``.

    ``rows`` (r,) are the affected row indices (host-concrete), ``block``
    (r, k) the compact left factor, ``v`` (p, k).  Sweeps only the
    touched row slabs through the Pallas kernel — HBM traffic scales
    with r, not n — and takes the dense batched kernel when the
    affected fraction exceeds ``max_fraction`` (past the crossover the
    slab gather costs more than it saves).
    """
    import numpy as np
    n, p = m.shape
    rows = np.asarray(rows)
    block = jnp.asarray(block)
    k = v.shape[1]
    plan = slab_plan(n, rows, max_fraction=max_fraction)
    if plan is None:
        dense_u = jnp.zeros((n, k), v.dtype).at[jnp.asarray(rows)].set(block)
        return rank_update(m, dense_u, v, interpret=interpret)
    slab, slab_ids = plan
    bn = rank_update_rows_block(slab, p, k)
    if bn is None:
        return rank_update_rows_ref(m, jnp.asarray(rows.astype(np.int32)),
                                    block, v)
    u = jnp.zeros((n, k), v.dtype).at[jnp.asarray(rows)].set(block)
    return rank_update_rows_pallas(m, slab_ids, u, v, slab=slab, bn=bn,
                                   interpret=interpret_mode(interpret))


def dual_matmul_blocks(n: int, m: int, k: int) -> Optional[Tuple[int, int]]:
    """``(bm, bn)`` tile of ``a`` (n, m) for :func:`dual_matmul`: the
    tile and the ``k``-row factor panels, plus ``Vᵀ`` and the ``Pᵀ``
    accumulator held whole in VMEM; ``None`` when nothing fits."""
    return _fit_blocks(n, m, lambda bm, bn: vmem_bytes(
        [(bm, bn), (k, bn), (k, bn), (n // bm, k, bm), (n // bm, k, bm)],
        scratch=[(k, bm), (k, bn)]))


def dual_matmul(a: jax.Array, u: jax.Array, v: jax.Array,
                interpret: Optional[bool] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Fused ``(a @ u, a.T @ v)`` — one HBM pass over ``a``."""
    n, m = a.shape
    blocks = dual_matmul_blocks(n, m, u.shape[1])
    if blocks is None:
        return ref.dual_matmul(a, u, v)
    return dual_matmul_pallas(a, u, v, bm=blocks[0], bn=blocks[1],
                              interpret=interpret_mode(interpret))


# The apply-and-project pass takes tiles up to 1024²: at rank 8 its VPU
# work per element is the largest of any kernel here, and 1024² tiles
# ran a 1 GiB view 0.7–0.9 ms faster than 512² on a v5e (PERF.md §6).
# Mosaic is granted APPLY_PROJECT_VMEM of scoped VMEM for them, of which
# the pipelined blocks may take three quarters.
APPLY_PROJECT_VMEM = 48 * 1024 * 1024


def apply_project_blocks(n: int, m: int, k: int, kx: int, ky: int
                         ) -> Optional[Tuple[int, int]]:
    """``(bm, bn)`` tile of a ``(n, m)`` view for :func:`apply_project`
    at update rank ``k`` and product widths ``kx``, ``ky`` (0 for a
    product not taken): the old and new tiles, the factor blocks, the
    ``P·X`` block and the whole ``(Pᵀ·Y)ᵀ``, plus two tile-sized
    temporaries of the body; ``None`` when nothing fits."""
    return _fit_blocks(n, m, lambda bm, bn: vmem_bytes(
        [(bm, bn), (bm, k), (k, bn), (kx, bn), (bm, ky), (bm, bn),
         (bm, kx), (m // bn, ky, bn)], scratch=[(bm, bn), (bm, bn)]),
        cap=1024, budget=APPLY_PROJECT_VMEM * 3 // 4)


def apply_project(p: jax.Array, u: jax.Array, v: jax.Array,
                  x: Optional[jax.Array] = None,
                  y: Optional[jax.Array] = None, *, alias: bool = False,
                  interpret: Optional[bool] = None):
    """``(p + u @ v.T, p @ x, p.T @ y)`` in one HBM pass over ``p``, the
    products on the old ``p``; ``x`` or ``y`` may be ``None``.  ``alias``
    writes the new view over ``p``'s buffer (only for a donated view).
    The XLA reference when no blocking fits VMEM."""
    n, m = p.shape
    kx = 0 if x is None else x.shape[1]
    ky = 0 if y is None else y.shape[1]
    blocks = apply_project_blocks(n, m, u.shape[1], kx, ky)
    if blocks is None:
        return ref.apply_project(p, u, v, x, y)
    return apply_project_pallas(p, u, v, x, y, bm=blocks[0], bn=blocks[1],
                                alias=alias,
                                interpret=interpret_mode(interpret),
                                vmem_limit=APPLY_PROJECT_VMEM)


def sherman_morrison_delta(w: jax.Array, u: jax.Array, v: jax.Array,
                           interpret: Optional[bool] = None
                           ) -> Tuple[jax.Array, jax.Array]:
    """Fused Sherman–Morrison factored delta (paper §4.1) built on the
    dual-matmul kernel: one pass over W produces both W·u and Wᵀ·v."""
    u = u.reshape(-1, 1)
    v = v.reshape(-1, 1)
    wu, wtv = dual_matmul(w, u, v, interpret=interpret)
    denom = 1.0 + (v.T @ wu)[0, 0]
    return -wu / denom, wtv


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 length: Optional[jax.Array] = None, chunk: int = 512,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Single-token GQA decode attention over a cache.

    q: (h, d); k, v: (s, h_kv, d).  vmaps the per-kv-head kernel across
    the GQA groups.  Returns (h, d).
    """
    h, d = q.shape
    s, h_kv, _ = k.shape
    group = h // h_kv
    if length is None:
        length = jnp.asarray(s, dtype=jnp.int32)
    qg = q.reshape(h_kv, group, d)
    kt = k.transpose(1, 0, 2)  # (h_kv, s, d)
    vt = v.transpose(1, 0, 2)
    interp = interpret_mode(interpret)
    chunk = min(chunk, s)
    while s % chunk:
        chunk -= 1

    def per_head(qh, kh, vh):
        acc, m, l = flash_decode_pallas(qh, kh, vh, length, chunk=chunk,
                                        interpret=interp)
        return acc / l

    out = jax.vmap(per_head)(qg, kt, vt)  # (h_kv, g, d)
    return out.reshape(h, d)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, bq: int = 256, bk: int = 256,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Fused multi-head flash attention (training/prefill hot path).

    q: (b, s, h, hd); k/v: (b, s, h, hd) — expand GQA before calling.
    vmaps the per-(batch, head) kernel.
    """
    interp = interpret_mode(interpret)

    def per_bh(qh, kh, vh):
        return flash_attention_pallas(qh, kh, vh, bq=bq, bk=bk,
                                      causal=causal, interpret=interp)

    # outer vmap over heads (axis 2), inner over batch (axis 0)
    bh = jax.vmap(jax.vmap(per_bh), in_axes=2, out_axes=2)
    return bh(q, k, v)

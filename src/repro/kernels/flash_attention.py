"""Pallas TPU kernel: fused flash-attention forward (training/prefill).

EXPERIMENTS.md §Perf Cell A ends with: the remaining memory term of the
dense-train cells is the f32 logits/softmax traffic that XLA materializes
between fusion boundaries — exactly what this kernel removes on TPU by
keeping the (bq × bk) logits tile and the online-softmax state in VMEM.

Layout: one (batch, head) slice per call (vmap outside).
  q: (S, hd), k/v: (S, hd) → out (S, hd), with causal masking.

Grid: (nq, nk) with the KV loop innermost; the accumulator/max/sum blocks
have q-indexed maps (constant in the inner dim → consecutive revisits,
pipeline-legal).  Causal skip: kv blocks strictly above the diagonal are
masked entirely (the pl.when guard skips their FLOPs on TPU).
Normalization (acc / l) happens on the final kv block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                      *, bq: int, bk: int, seq: int, causal: bool,
                      scale: float):
    qi = pl.program_id(0)
    kj = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(kj == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: block (qi, kj) is live iff kj*bk <= qi*bq + bq - 1
    live = (not causal) or (kj * bk <= qi * bq + bq - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32,
                                                       (bq, bk), 0)
            k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32,
                                                       (bq, bk), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jnp.dot(p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
        o_ref[...] = o_ref[...] * alpha + pv
        m_ref[...] = m_new
        l_ref[...] = l_new

    # final kv block: normalize
    @pl.when(kj == nk - 1)
    def _norm():
        o_ref[...] = o_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit,
                   static_argnames=("bq", "bk", "causal", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           *, bq: int = 256, bk: int = 256,
                           causal: bool = True,
                           interpret: bool) -> jax.Array:
    """Single (batch, head) flash attention: q/k/v (S, hd) → (S, hd)."""
    s, hd = q.shape
    bq = min(bq, s)
    bk = min(bk, s)
    while s % bq:
        bq -= 1
    while s % bk:
        bk -= 1
    grid = (s // bq, s // bk)
    scale = hd ** -0.5
    kern = functools.partial(_flash_fwd_kernel, bq=bq, bk=bk, seq=s,
                             causal=causal, scale=scale)
    out, _, _ = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, hd), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, hd), lambda i, j: (j, 0)),
            pl.BlockSpec((bk, hd), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, hd), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, hd), jnp.float32),
            jax.ShapeDtypeStruct((s, 1), jnp.float32),
            jax.ShapeDtypeStruct((s, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out.astype(q.dtype)

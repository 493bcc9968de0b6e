"""Pallas TPU kernel: single-token decode attention over a long KV cache.

Serving hot-spot for the ``decode_32k`` / ``long_500k`` shapes: one query
token attends over an s-long cache.  The op is strictly memory-bound
(intensity ≈ 1 FLOP/byte on K/V), so the kernel streams K/V chunks through
VMEM once with an online-softmax running state — the TPU analogue of
flash-decoding (the GPU original splits across SMs; here the split across
cores happens one level up via shard_map over the sequence axis, and this
kernel handles the per-core chunk loop).

Layout: one kv-head group per call (vmap over kv heads / batch outside).
  q: (g, d)       — the g query heads sharing this kv head (GQA group)
  k, v: (s, d)    — this kv head's cache
  length: (1, 1)  — valid prefix of the cache (rest masked)

Grid: 1-D over cache chunks; running (acc, m, l) live in revisited
constant-index output blocks (consecutive revisits — pipeline-legal).
Normalization ``acc / l`` happens in ops.flash_decode after the call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


NEG_INF = -1e30


def _flash_decode_kernel(len_ref, q_ref, k_ref, v_ref,
                         acc_ref, m_ref, l_ref, *, chunk: int, scale: float):
    j = pl.program_id(0)
    start = j * chunk
    q = q_ref[...]                               # (g, d)
    k = k_ref[...]                               # (chunk, d)
    v = v_ref[...]                               # (chunk, d)
    length = len_ref[0, 0]

    logits = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    idx = start + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(idx < length, logits, NEG_INF)

    m_new = jnp.max(logits, axis=1, keepdims=True)          # (g, 1)
    p = jnp.exp(logits - m_new)                              # (g, chunk)
    l_new = jnp.sum(p, axis=1, keepdims=True)                # (g, 1)
    pv = jnp.dot(p, v, preferred_element_type=jnp.float32)   # (g, d)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = pv
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(j != 0)
    def _merge():
        m_old = m_ref[...]
        m_run = jnp.maximum(m_old, m_new)
        a_old = jnp.exp(m_old - m_run)
        a_new = jnp.exp(m_new - m_run)
        acc_ref[...] = acc_ref[...] * a_old + pv * a_new
        l_ref[...] = l_ref[...] * a_old + l_new * a_new
        m_ref[...] = m_run


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def flash_decode_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                        length: jax.Array, *, chunk: int = 512,
                        interpret: bool):
    """Returns (acc, m, l); attention output = acc / l.

    q: (g, d); k, v: (s, d); length: scalar int32 array.
    """
    g, d = q.shape
    s = k.shape[0]
    assert k.shape == (s, d) and v.shape == (s, d)
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError(f"s={s} not divisible by chunk={chunk}")
    grid = (s // chunk,)
    scale = 1.0 / (d ** 0.5)
    kern = functools.partial(_flash_decode_kernel, chunk=chunk, scale=scale)
    acc, m, l = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda j: (0, 0)),       # length
            pl.BlockSpec((g, d), lambda j: (0, 0)),       # q
            pl.BlockSpec((chunk, d), lambda j: (j, 0)),   # k chunk
            pl.BlockSpec((chunk, d), lambda j: (j, 0)),   # v chunk
        ],
        out_specs=[
            pl.BlockSpec((g, d), lambda j: (0, 0)),
            pl.BlockSpec((g, 1), lambda j: (0, 0)),
            pl.BlockSpec((g, 1), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, d), jnp.float32),
            jax.ShapeDtypeStruct((g, 1), jnp.float32),
            jax.ShapeDtypeStruct((g, 1), jnp.float32),
        ],
        interpret=interpret,
    )(length.reshape(1, 1).astype(jnp.int32), q, k, v)
    return acc, m, l

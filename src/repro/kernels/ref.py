"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract)."""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def rank_update(m: jax.Array, u: jax.Array, v: jax.Array) -> jax.Array:
    """Oracle for kernels.rank_update: ``m + u @ v.T``."""
    return m + u @ v.T


def rank_update_batched(m: jax.Array, u: jax.Array, v: jax.Array) -> jax.Array:
    """Oracle for kernels.rank_update_batched: ``m + Σ_t u[t] @ v[t].T``
    with u: (T, n, k), v: (T, p, k)."""
    return m + jnp.einsum("tnk,tpk->np", u, v)


def dual_matmul(a: jax.Array, u: jax.Array, v: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """Oracle for kernels.dual_matmul: ``(a @ u, a.T @ v)``."""
    return a @ u, a.T @ v


def apply_project(p: jax.Array, u: jax.Array, v: jax.Array, x, y):
    """Oracle for kernels.apply_project: ``(p + u vᵀ, p x, pᵀ y)`` in full
    float32, since ``ops.apply_project`` falls back to it; ``None`` for a
    product whose factor is ``None``."""
    def mm(a, b):
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    return (p + mm(u, v.T), None if x is None else mm(p, x),
            None if y is None else mm(p.T, y))


def sherman_morrison_delta(w: jax.Array, u: jax.Array, v: jax.Array
                           ) -> Tuple[jax.Array, jax.Array]:
    """Oracle for the fused SM delta: Δ(E⁻¹) = L Rᵀ (paper §4.1)."""
    u = u.reshape(-1, 1)
    v = v.reshape(-1, 1)
    wu = w @ u
    wtv = w.T @ v
    denom = 1.0 + (v.T @ wu)[0, 0]
    return -wu / denom, wtv


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 length: jax.Array | None = None) -> jax.Array:
    """Oracle for kernels.flash_decode: single-query attention over a cache.

    q: (h, d), k/v: (s, h_kv, d) with h a multiple of h_kv (GQA).
    ``length``: number of valid cache entries (rest masked).
    """
    s, h_kv, d = k.shape
    h = q.shape[0]
    group = h // h_kv
    qg = q.reshape(h_kv, group, d)
    logits = jnp.einsum("hgd,shd->hgs", qg, k) / jnp.sqrt(d).astype(q.dtype)
    if length is not None:
        mask = jnp.arange(s)[None, None, :] < length
        logits = jnp.where(mask, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("hgs,shd->hgd", p, v)
    return out.reshape(h, d)


def flash_attention(q, k, v, causal: bool = True):
    """Oracle for kernels.flash_attention: full softmax attention.

    q/k/v: (s, hd) → (s, hd), causal mask optional."""
    s_len, hd = q.shape
    logits = (q.astype(jnp.float32) @ k.astype(jnp.float32).T) * hd ** -0.5
    if causal:
        mask = jnp.tril(jnp.ones((s_len, s_len), bool))
        logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return (p @ v.astype(jnp.float32)).astype(q.dtype)

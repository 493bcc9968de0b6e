"""Mixture-of-Experts: token-choice top-k routing with static capacity.

Two execution paths, one routing semantics:

**Sharded path** (mesh active — the production configuration): a
shard_map over the full mesh.  Tokens arrive batch-sharded over
(pod, data) and replicated over model; expert weights are sharded over
the model axis.  Each chip routes its local tokens, serves only the
experts it owns (expert parallelism, qwen3: 128/16 = 8 per chip), and
the per-token combine is ONE psum over the model axis — the same
collective the TP attention block already pays, so MoE adds no new
collective class.  When the expert count doesn't divide the mesh
(qwen2: 60 experts), the same body falls back to tensor parallelism
*inside* every expert (d_ff sharded, contributions summed by the same
psum).  Dispatch is scatter-of-token-ids + gather, never a k-fold copy
of activations.

**Local path** (no mesh — CPU smoke tests): same math on one device.

Capacity semantics: positions are assigned per data shard
(C_local = T_local·k·cf/E), the standard practice for EP training; drops
are deterministic in token order.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import current_ctx, shard
from . import layers


def init_moe(cfg, dtype, rng) -> Dict:
    d = cfg.d_model
    e = cfg.moe
    ks = jax.random.split(rng, 5)
    sd_in = d ** -0.5
    sd_out = e.expert_d_ff ** -0.5
    p = {
        "router": (jax.random.normal(ks[0], (d, e.n_experts), jnp.float32)
                   * sd_in).astype(jnp.float32),   # router stays f32
        "w_in": (jax.random.normal(ks[1], (e.n_experts, d, e.expert_d_ff),
                                   jnp.float32) * sd_in).astype(dtype),
        "w_gate": (jax.random.normal(ks[2], (e.n_experts, d, e.expert_d_ff),
                                     jnp.float32) * sd_in).astype(dtype),
        "w_out": (jax.random.normal(ks[3], (e.n_experts, e.expert_d_ff, d),
                                    jnp.float32) * sd_out).astype(dtype),
    }
    if e.n_shared_experts:
        p["shared"] = layers.init_mlp(d, e.shared_d_ff, True, dtype, ks[4])
        p["shared_gate"] = jnp.zeros((d, 1), jnp.float32)
    return p


def axes_moe(cfg) -> Dict:
    p = {
        "router": (None, None),
        "w_in": ("experts", None, "ff"),
        "w_gate": ("experts", None, "ff"),
        "w_out": ("experts", "ff", None),
    }
    if cfg.moe.n_shared_experts:
        p["shared"] = layers.axes_mlp(True)
        p["shared_gate"] = (None, None)
    return p


def _capacity(n_tokens: int, cfg) -> int:
    e = cfg.moe
    if n_tokens * e.top_k <= 4096:
        # tiny token counts (decode steps, smoke tests): dense-safe capacity
        # — no drops even if every pair lands on one expert.
        return (n_tokens * e.top_k + 7) // 8 * 8
    c = int(n_tokens * e.top_k * e.capacity_factor / e.n_experts)
    return max(8, (c + 7) // 8 * 8)  # 8-align for TPU tiling


def _route(xt_f32: jax.Array, router: jax.Array, cfg):
    """→ (top_p (T,k), top_e (T,k), probs (T,E)) in f32."""
    e = cfg.moe
    logits = xt_f32 @ router
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, e.top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p, top_e, probs


def _dispatch_compute_combine(xt, top_p, top_e, w_in, w_gate, w_out, cfg,
                              expert_offset: int, n_local_experts: int,
                              cap: int):
    """Serve ``n_local_experts`` experts starting at ``expert_offset`` for
    the local tokens.  Returns the (partial) output (T, D)."""
    t, d = xt.shape
    k = cfg.moe.top_k
    flat_e = top_e.reshape(-1)                                   # (T*k,)
    local_e = flat_e - expert_offset
    mine = (local_e >= 0) & (local_e < n_local_experts)
    local_e = jnp.where(mine, local_e, 0)

    onehot = jax.nn.one_hot(local_e, n_local_experts,
                            dtype=jnp.int32) * mine[:, None].astype(jnp.int32)
    pos = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=-1)
    keep = mine & (pos < cap)
    slot = jnp.where(keep, local_e * cap + pos, n_local_experts * cap)

    # invert slot→(token, k-choice): scatter ids, then gather activations
    pair_tok = jnp.arange(t * k, dtype=jnp.int32) // k
    tok_of_slot = jnp.full((n_local_experts * cap,), t, jnp.int32
                           ).at[slot].set(pair_tok, mode="drop")
    prob_of_slot = jnp.zeros((n_local_experts * cap,), jnp.float32
                             ).at[slot].set(top_p.reshape(-1), mode="drop")
    filled = jnp.zeros((n_local_experts * cap,), jnp.bool_
                       ).at[slot].set(True, mode="drop")

    gather_idx = jnp.minimum(tok_of_slot, t - 1)
    buf = xt[gather_idx] * filled[:, None].astype(xt.dtype)
    buf = buf.reshape(n_local_experts, cap, d)

    h = jnp.einsum("ecd,edf->ecf", buf, w_in)
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
    out_buf = jnp.einsum("ecf,efd->ecd", h, w_out)
    out_buf = out_buf.reshape(n_local_experts * cap, d).astype(jnp.float32)
    out_buf = out_buf * prob_of_slot[:, None]

    out = jnp.zeros((t, d), jnp.float32
                    ).at[tok_of_slot].add(out_buf, mode="drop")
    return out


def _moe_body(xt, router, w_in, w_gate, w_out, shared, shared_gate, cfg,
              *, model_axis: Optional[str], ep: bool, return_aux: bool,
              batch_axes: Tuple[str, ...] = ()):
    """Per-chip MoE: xt (T_local, D); weights are local shards."""
    e = cfg.moe
    t = xt.shape[0]
    xt_f32 = xt.astype(jnp.float32)
    top_p, top_e, probs = _route(xt_f32, router, cfg)
    cap = _capacity(t, cfg)

    n_local = w_in.shape[0]
    if ep and model_axis is not None:
        offset = jax.lax.axis_index(model_axis) * n_local
    else:
        offset = 0
    out = _dispatch_compute_combine(xt, top_p, top_e, w_in, w_gate, w_out,
                                    cfg, offset, n_local, cap)

    if shared:
        # shared experts (w sharded over ff when on-mesh → partial, psum'd)
        h = xt @ shared["w_in"]
        g = xt @ shared["w_gate"]
        h = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
        sh = (h @ shared["w_out"]).astype(jnp.float32)
        gate = jax.nn.sigmoid(xt_f32 @ shared_gate)
        out = out + sh * gate

    if model_axis is not None:
        out = jax.lax.psum(out, model_axis)

    if not return_aux:
        return out.astype(xt.dtype), jnp.zeros((), jnp.float32)
    me = jnp.mean(jax.nn.one_hot(top_e, e.n_experts, dtype=jnp.float32),
                  axis=(0, 1))
    pe = jnp.mean(probs, axis=0)
    aux = e.n_experts * jnp.sum(me * pe) * e.router_aux_loss
    if batch_axes:
        # average the per-data-shard stats so the scalar is replicated
        aux = jax.lax.pmean(aux, batch_axes)
    return out.astype(xt.dtype), aux


def moe_block(params: Dict, cfg, x: jax.Array, return_aux: bool = False):
    """x: (B, S, D) → (B, S, D) [+ aux load-balancing loss]."""
    b, s, d = x.shape
    e = cfg.moe
    ctx = current_ctx()
    shared = params.get("shared")
    shared_gate = params.get("shared_gate")

    if ctx.mesh is None:
        xt = x.reshape(b * s, d)
        out, aux = _moe_body(xt, params["router"], params["w_in"],
                             params["w_gate"], params["w_out"], shared,
                             shared_gate, cfg, model_axis=None,
                             ep=False, return_aux=return_aux)
        out = out.reshape(b, s, d)
        return (out, aux) if return_aux else out

    mesh = ctx.mesh
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model_n = axes.get("model", 1)
    ep = e.n_experts % model_n == 0 and model_n > 1
    batch_axes = tuple(a for a in ("pod", "data") if a in axes)

    w_spec = (P("model", None, None) if ep else P(None, None, "model"))
    w_out_spec = (P("model", None, None) if ep else P(None, "model", None))
    if shared:
        shared_specs = {"w_in": P(None, "model"), "w_gate": P(None, "model"),
                        "w_out": P("model", None)}
        shared_args = (shared, shared_gate)
        shared_in = (shared_specs, P())
    else:
        shared_args = ({}, jnp.zeros((d, 1), jnp.float32))
        shared_in = ({}, P())

    body = functools.partial(_moe_body, cfg=cfg, model_axis="model",
                             ep=ep, return_aux=return_aux,
                             batch_axes=batch_axes)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_axes, None), P(), w_spec, w_spec, w_out_spec)
        + shared_in,
        out_specs=(P(batch_axes, None), P()),
        check_vma=False)
    xt = x.reshape(b * s, d)
    out, aux = fn(xt, params["router"], params["w_in"], params["w_gate"],
                  params["w_out"], *shared_args)
    out = out.reshape(b, s, d)
    out = shard(out, "batch", None, None)
    if return_aux:
        # aux comes back identical on every shard (it's a psum-free scalar
        # computed from replicated routing stats); mean across shards is a
        # no-op numerically but keeps the value replicated for GSPMD.
        return out, aux
    return out

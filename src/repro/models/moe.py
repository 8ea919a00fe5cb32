"""Mixture-of-Experts block with sort-based (FLOP-honest) dispatch.

Dispatch uses argsort + capacity slots + gather/scatter so the compiled HLO's
FLOPs equal the ACTIVE expert FLOPs (6·N_active·D accounting in §Roofline
stays honest); token movement is gathers/scatters (bytes, not FLOPs) — the
XLA analogue of the all-to-all dispatch in DP+EP serving systems.

Supports softmax (classic) and sigmoid (DeepSeek-V3) scoring, shared experts,
routed scaling, capacity-factor token dropping, and the load-balance aux loss.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import MoEConfig
from repro.distributed.annotate import constrain
from repro.models.layers import init_linear


def init_moe_params(key, d_model: int, mc: MoEConfig, dtype) -> Dict:
    ks = jax.random.split(key, 6)
    p = {
        "router": init_linear(ks[0], d_model, mc.num_experts, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (mc.num_experts, d_model, mc.d_expert), jnp.float32)
                   / math.sqrt(d_model)).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (mc.num_experts, d_model, mc.d_expert), jnp.float32)
                 / math.sqrt(d_model)).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (mc.num_experts, mc.d_expert, d_model), jnp.float32)
                   / math.sqrt(mc.d_expert)).astype(dtype),
    }
    if mc.score_fn == "sigmoid":
        p["router_bias"] = jnp.zeros((mc.num_experts,), jnp.float32)
    if mc.num_shared:
        p["shared_gate"] = init_linear(ks[4], d_model, mc.num_shared * mc.d_shared, dtype)
        p["shared_up"] = init_linear(ks[4], d_model, mc.num_shared * mc.d_shared, dtype)
        p["shared_down"] = init_linear(ks[5], mc.num_shared * mc.d_shared, d_model, dtype)
    return p


def _capacity_axis():
    """'tokens' if the token axes are disjoint from the expert axes."""
    from repro.distributed import annotate as _ann
    ctx = _ann.active()
    if ctx is None:
        return None
    amap = ctx["map"]
    tok = amap.get("tokens") or ()
    tok = {tok} if isinstance(tok, str) else set(tok)
    ep = amap.get("experts") or ()
    ep = {ep} if isinstance(ep, str) else set(ep)
    return None if (tok & ep) else "tokens"


def route(x2d: jnp.ndarray, params: Dict, mc: MoEConfig
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Token-choice top-k routing. x2d: (T, D) -> weights/ids (T, k), probs (T, E)."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), params["router"])
    if mc.score_fn == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores + params.get("router_bias", 0.0)  # aux-loss-free bias (DS-V3)
        top_w, top_e = jax.lax.top_k(sel, mc.top_k)
        # weights from raw scores at selected experts, normalized
        top_w = jnp.take_along_axis(scores, top_e, axis=-1)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
        top_w = top_w * mc.routed_scaling
        probs = scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, mc.top_k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    return top_w, top_e, probs


def aux_loss(probs: jnp.ndarray, top_e: jnp.ndarray, num_experts: int) -> jnp.ndarray:
    """Switch-style load-balance loss: E · Σ_e f_e · P_e."""
    T = probs.shape[0]
    counts = jnp.zeros((num_experts,), jnp.float32).at[top_e.reshape(-1)].add(1.0)
    f = counts / jnp.maximum(T * top_e.shape[-1], 1)
    p = probs.mean(axis=0)
    return num_experts * jnp.sum(f * p)


@jax.named_scope("moe")
def moe_block(x: jnp.ndarray, params: Dict, mc: MoEConfig,
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Apply MoE. x: (B, S, D) or (T, D). Returns (out, aux_loss).

    Under an annotate.activate(..., ep_shard_map=True) context this
    delegates to the explicit all-to-all EP path when the shapes divide."""
    from repro.distributed import annotate as _ann
    ctx = _ann.active()
    if ctx is not None and ctx.get("ep"):
        import numpy as np
        mesh = ctx["mesh"]
        amap = ctx["map"]
        tok = amap.get("tokens") or ()
        tok = (tok,) if isinstance(tok, str) else tuple(tok)
        ep = amap.get("experts") or ()
        ep = (ep,) if isinstance(ep, str) else tuple(ep)
        T = int(np.prod(x.shape[:-1]))
        n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        G = int(np.prod([mesh.shape[a] for a in ep])) if ep else 0
        if (ep and G and mc.num_experts % G == 0 and T % n_dev == 0
                and T // n_dev >= 1):
            from repro.models.moe_ep import moe_block_ep
            return moe_block_ep(x, params, mc, mesh, tok, ep)
    orig_shape = x.shape
    x2d = x.reshape(-1, x.shape[-1])
    T, D = x2d.shape
    E, k = mc.num_experts, mc.top_k

    top_w, top_e, probs = route(x2d, params, mc)
    laux = aux_loss(probs, top_e, E)

    # capacity per expert
    C = max(int(math.ceil(T * k / E * mc.capacity_factor)), 1)

    # ---- sort-based dispatch ----
    # §Perf iteration 1 (see EXPERIMENTS.md): dispatch/combine are expressed
    # as SMALL integer-index exchanges plus big gathers whose outputs carry
    # explicit sharding annotations ("experts" / "tokens"). The original
    # formulation scattered through a flat (E·C+1, D) buffer whose
    # data-dependent indices made GSPMD replicate 240 GB f32 intermediates
    # and all-reduce them (28 TB/device for DeepSeek-V3 prefill_32k).
    flat_e = top_e.reshape(-1)                       # (T*k,)
    order = jnp.argsort(flat_e, stable=True)         # token-major within expert
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    pos_in_e = jnp.arange(T * k) - first[sorted_e]
    keep = pos_in_e < C
    c_idx = jnp.where(keep, pos_in_e, C)              # column C = drop bin
    tok = order // k                                  # source token per flat slot

    # (E, C+1) int32 routing table: slot -> source token (T = padding row)
    tok_buf = jnp.full((E, C + 1), T, jnp.int32).at[sorted_e, c_idx].set(
        jnp.where(keep, tok, T))
    x_pad = jnp.concatenate([x2d, jnp.zeros((1, D), x2d.dtype)])
    h = x_pad[tok_buf[:, :C]]                         # (E, C, D) gather
    # capacity dim sharded over the token axes (when disjoint from the
    # expert axes): otherwise expert compute replicates across data —
    # measured as 16× over-compute on jamba train (§Perf iteration 3).
    c_axis = _capacity_axis()
    h = constrain(h, "experts", c_axis, None)

    # ---- expert computation (active FLOPs only) ----
    g = jnp.einsum("ecd,edf->ecf", h, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", h, params["w_up"])
    act = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * u
    y = jnp.einsum("ecf,efd->ecd", act, params["w_down"])
    y = constrain(y, "experts", c_axis, None)

    # ---- combine: pure per-token gather (no scatter-add) ----
    pos_tk = jnp.zeros((T * k,), jnp.int32).at[order].set(c_idx).reshape(T, k)
    y_pad = jnp.concatenate([y, jnp.zeros((E, 1, D), y.dtype)], axis=1)
    contrib = y_pad[top_e, pos_tk]                    # (T, k, D)
    contrib = constrain(contrib, "tokens", None, None)
    out = (contrib * top_w[..., None].astype(y.dtype)).sum(axis=1)

    if mc.num_shared:
        gs = jnp.einsum("td,df->tf", x2d, params["shared_gate"])
        us = jnp.einsum("td,df->tf", x2d, params["shared_up"])
        hs = jax.nn.silu(gs.astype(jnp.float32)).astype(x2d.dtype) * us
        out = out + jnp.einsum("tf,fd->td", hs, params["shared_down"])

    return out.reshape(orig_shape), laux


def moe_block_dense_reference(x: jnp.ndarray, params: Dict, mc: MoEConfig
                              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dense (all-experts) oracle — O(E) FLOPs, used only in tests to verify
    the sort-based dispatch (identical when no token is dropped)."""
    orig_shape = x.shape
    x2d = x.reshape(-1, x.shape[-1])
    top_w, top_e, probs = route(x2d, params, mc)
    laux = aux_loss(probs, top_e, mc.num_experts)
    g = jnp.einsum("td,edf->tef", x2d, params["w_gate"])
    u = jnp.einsum("td,edf->tef", x2d, params["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x2d.dtype) * u
    y = jnp.einsum("tef,efd->ted", h, params["w_down"])       # (T, E, D)
    w_full = jnp.zeros((x2d.shape[0], mc.num_experts), y.dtype)
    w_full = jax.vmap(lambda w, e, r: w.at[e].add(r))(w_full, top_e, top_w.astype(y.dtype))
    out = jnp.einsum("te,ted->td", w_full, y)
    if mc.num_shared:
        gs = jnp.einsum("td,df->tf", x2d, params["shared_gate"])
        us = jnp.einsum("td,df->tf", x2d, params["shared_up"])
        hs = jax.nn.silu(gs.astype(jnp.float32)).astype(x2d.dtype) * us
        out = out + jnp.einsum("tf,fd->td", hs, params["shared_down"])
    return out.reshape(orig_shape), laux

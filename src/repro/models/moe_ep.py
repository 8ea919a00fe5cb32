"""Expert-parallel MoE via shard_map + explicit all-to-all (§Perf iter. 2).

This is the TPU-native analogue of the DeepSeek-V3 production EP dispatch
the paper's cluster runs: each device routes its token slice into per-expert
capacity buckets, a pair of all-to-alls moves only the routed token rows
(≈ T·k·D bytes globally, vs. GSPMD's replicated-gather all-reduces measured
at 240 GB f32 per layer), experts compute locally, and the combine is a
local gather.

Semantics note: capacity is enforced PER SOURCE RANK (C_dev each), like real
EP systems — the drop pattern differs slightly from the single-program
moe_block under overload; with a non-binding capacity factor the outputs
match exactly (tested).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config.base import MoEConfig
from repro.models.moe import aux_loss, route


def _local_dispatch(x_loc, top_w, top_e, E: int, C: int):
    """Bucket the local token slice by expert. Returns (buckets (E,C,D),
    routing table back-refs)."""
    T, D = x_loc.shape
    k = top_e.shape[-1]
    flat_e = top_e.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    pos_in_e = jnp.arange(T * k) - first[sorted_e]
    keep = pos_in_e < C
    c_idx = jnp.where(keep, pos_in_e, C)
    tok = order // k
    tok_buf = jnp.full((E, C + 1), T, jnp.int32).at[sorted_e, c_idx].set(
        jnp.where(keep, tok, T))
    x_pad = jnp.concatenate([x_loc, jnp.zeros((1, D), x_loc.dtype)])
    buckets = x_pad[tok_buf[:, :C]]                   # (E, C, D)
    pos_tk = jnp.zeros((T * k,), jnp.int32).at[order].set(c_idx).reshape(T, k)
    return buckets, pos_tk


def moe_block_ep(x: jnp.ndarray, params: Dict, mc: MoEConfig, mesh,
                 token_axes: Tuple[str, ...], ep_axes: Tuple[str, ...],
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """EP MoE with explicit all-to-all. x: (B, S, D) sharded tokens@token_axes.

    Requires E % G_ep == 0 where G_ep = prod(mesh[a] for a in ep_axes).
    Non-EP axes of the mesh replicate the expert weights.
    """
    orig_shape = x.shape
    x2d = x.reshape(-1, x.shape[-1])
    T, D = x2d.shape
    E, k = mc.num_experts, mc.top_k
    import numpy as np
    G = int(np.prod([mesh.shape[a] for a in ep_axes]))
    E_per = E // G
    n_tok_shards = int(np.prod([mesh.shape[a] for a in token_axes]))
    # token slice per device = T / (all mesh axes), since every axis either
    # shards tokens or splits the replicated copy
    all_axes = tuple(mesh.axis_names)
    n_dev = int(np.prod([mesh.shape[a] for a in all_axes]))
    T_loc = T // n_dev
    C_dev = max(int(math.ceil(T_loc * k / E * mc.capacity_factor)), 1)

    other_axes = tuple(a for a in all_axes if a not in token_axes)

    def body(x_blk, router_p, w_gate, w_up, w_down, bias):
        # x_blk: (T/n_tok_shards, D) — replicated over other_axes; take the
        # slice this device owns along the replicated axes.
        n_rep = int(np.prod([mesh.shape[a] for a in other_axes])) or 1
        Tb = x_blk.shape[0]
        if n_rep > 1:
            idx = jax.lax.axis_index(other_axes)
            x_loc = jax.lax.dynamic_slice_in_dim(
                x_blk, idx * (Tb // n_rep), Tb // n_rep, axis=0)
        else:
            x_loc = x_blk
        rp = {"router": router_p}
        if bias is not None:
            rp["router_bias"] = bias
        top_w, top_e, probs = route(x_loc, rp, mc)
        laux = aux_loss(probs, top_e, E)
        laux = jax.lax.pmean(laux, all_axes)

        buckets, pos_tk = _local_dispatch(x_loc, top_w, top_e, E, C_dev)
        # Dispatch all-to-all convention (both a2a calls in this body):
        # tiled=True on a (G, E_per·C, D) operand SPLITS axis 0 across the
        # EP group (slice g goes to rank g) and CONCATS the received
        # slices back on axis 0 — so post-a2a axis 0 indexes the SOURCE
        # rank, and slice s holds the E_per local experts' capacity rows
        # that rank s routed to this device.  The combine a2a below is the
        # exact inverse (same split/concat axis ⇒ self-inverse).
        b = buckets.reshape(G, E_per * C_dev, D)
        b = jax.lax.all_to_all(b, ep_axes, split_axis=0, concat_axis=0,
                               tiled=True)
        h = b.reshape(G, E_per, C_dev, D).transpose(1, 0, 2, 3)
        h = h.reshape(E_per, G * C_dev, D)
        g = jnp.einsum("ecd,edf->ecf", h, w_gate)
        u = jnp.einsum("ecd,edf->ecf", h, w_up)
        act = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * u
        y = jnp.einsum("ecf,efd->ecd", act, w_down)   # (E_per, G·C, D)
        y = y.reshape(E_per, G, C_dev, D).transpose(1, 0, 2, 3)
        y = y.reshape(G, E_per * C_dev, D)
        y = jax.lax.all_to_all(y, ep_axes, split_axis=0, concat_axis=0,
                               tiled=True)
        y = y.reshape(E, C_dev, D)
        y_pad = jnp.concatenate([y, jnp.zeros((E, 1, D), y.dtype)], axis=1)
        contrib = y_pad[top_e, pos_tk]                # (T_loc, k, D)
        out_loc = (contrib * top_w[..., None].astype(y.dtype)).sum(axis=1)
        # reassemble the replicated block: all_gather over other_axes
        if n_rep > 1:
            out = jax.lax.all_gather(out_loc, other_axes, axis=0, tiled=True)
        else:
            out = out_loc
        return out, laux

    tok_spec = P(token_axes if token_axes else None, None)
    w_spec = P(ep_axes if len(ep_axes) > 1 else ep_axes[0], None, None)
    bias = params.get("router_bias")
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(tok_spec, P(None, None), w_spec, w_spec, w_spec,
                  P(None) if bias is not None else None),
        out_specs=(tok_spec, P()),
        check_vma=False)
    out, laux = fn(x2d, params["router"], params["w_gate"], params["w_up"],
                   params["w_down"], bias)

    if mc.num_shared:
        gs = jnp.einsum("td,df->tf", x2d, params["shared_gate"])
        us = jnp.einsum("td,df->tf", x2d, params["shared_up"])
        hs = jax.nn.silu(gs.astype(jnp.float32)).astype(x2d.dtype) * us
        out = out + jnp.einsum("tf,fd->td", hs, params["shared_down"])
    return out.reshape(orig_shape), laux

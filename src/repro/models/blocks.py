"""Transformer blocks: one (mixer + FFN) block per LayerKind.

kind DENSE   = attention + dense SwiGLU
kind MOE     = attention + MoE
kind SSM     = Mamba2 mixer + dense SwiGLU (or nothing when d_ff == 0)
kind SSM_MOE = Mamba2 mixer + MoE           (jamba)

Each block has a full-sequence path (train / prefill, optionally emitting the
cache entry) and a decode path (single token against the cache entry).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import AttentionKind, LayerKind, ModelConfig
from repro.models import attention as A
from repro.models.layers import init_linear, rms_norm, swiglu, apply_rope
from repro.models.mamba import (
    init_mamba_params, mamba_forward, mamba_decode_step, ssm_dims,
)
from repro.models.moe import init_moe_params, moe_block


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def init_attn_params(key, cfg: ModelConfig, dtype, cross: bool = False) -> Dict:
    D = cfg.d_model
    hd = cfg.resolved_head_dim
    H, K = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 8)
    if cfg.attention == AttentionKind.MLA and not cross:
        m = cfg.mla
        p: Dict = {}
        q_in = D
        if m.q_lora_rank:
            p["w_dq"] = init_linear(ks[0], D, m.q_lora_rank, dtype)
            p["q_norm"] = jnp.ones((m.q_lora_rank,), dtype)
            q_in = m.q_lora_rank
        p["w_uq"] = init_linear(
            ks[1], q_in, H * (m.qk_nope_head_dim + m.qk_rope_head_dim), dtype
        ).reshape(q_in, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
        p["w_dkv"] = init_linear(ks[2], D, m.kv_lora_rank, dtype)
        p["kv_norm"] = jnp.ones((m.kv_lora_rank,), dtype)
        p["w_kr"] = init_linear(ks[3], D, m.qk_rope_head_dim, dtype)
        p["w_uk"] = (jax.random.normal(
            ks[4], (m.kv_lora_rank, H, m.qk_nope_head_dim), jnp.float32)
            / math.sqrt(m.kv_lora_rank)).astype(dtype)
        p["w_uv"] = (jax.random.normal(
            ks[5], (m.kv_lora_rank, H, m.v_head_dim), jnp.float32)
            / math.sqrt(m.kv_lora_rank)).astype(dtype)
        p["w_o"] = init_linear(ks[6], H * m.v_head_dim, D, dtype
                               ).reshape(H, m.v_head_dim, D)
        return p
    return {
        "w_q": init_linear(ks[0], D, H * hd, dtype).reshape(D, H, hd),
        "w_k": init_linear(ks[1], D, K * hd, dtype).reshape(D, K, hd),
        "w_v": init_linear(ks[2], D, K * hd, dtype).reshape(D, K, hd),
        "w_o": init_linear(ks[3], H * hd, D, dtype).reshape(H, hd, D),
    }


def init_dense_mlp_params(key, cfg: ModelConfig, dtype) -> Dict:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": init_linear(ks[0], cfg.d_model, cfg.d_ff, dtype),
        "w_up": init_linear(ks[1], cfg.d_model, cfg.d_ff, dtype),
        "w_down": init_linear(ks[2], cfg.d_ff, cfg.d_model, dtype),
    }


def init_block_params(key, cfg: ModelConfig, kind: LayerKind, dtype,
                      cross: bool = False, is_encoder: bool = False) -> Dict:
    ks = jax.random.split(key, 5)
    D = cfg.d_model
    p: Dict = {"ln1": jnp.ones((D,), dtype)}
    if kind in (LayerKind.DENSE, LayerKind.MOE):
        p["attn"] = init_attn_params(ks[0], cfg, dtype)
    else:
        p["mamba"] = init_mamba_params(ks[0], D, cfg.ssm, dtype)
    if cross:
        p["ln_x"] = jnp.ones((D,), dtype)
        p["xattn"] = init_attn_params(ks[1], cfg, dtype, cross=True)
    # FFN
    if kind in (LayerKind.MOE, LayerKind.SSM_MOE) and cfg.moe.num_experts:
        p["ln2"] = jnp.ones((D,), dtype)
        p["moe"] = init_moe_params(ks[2], D, cfg.moe, dtype)
    elif cfg.d_ff > 0:
        p["ln2"] = jnp.ones((D,), dtype)
        p["mlp"] = init_dense_mlp_params(ks[2], cfg, dtype)
    return p


# ---------------------------------------------------------------------------
# Attention sub-layer
# ---------------------------------------------------------------------------

FLASH_THRESHOLD = 2048  # use online-softmax scan beyond this KV length
FLASH_BLOCK = 512


def _qkv_full(p, x, cfg: ModelConfig, positions, use_rope=True):
    B, S, D = x.shape
    hd = cfg.resolved_head_dim
    H, K = cfg.num_heads, cfg.num_kv_heads
    q = jnp.einsum("bsd,dhe->bshe", x, p["w_q"])
    k = jnp.einsum("bsd,dhe->bshe", x, p["w_k"])
    v = jnp.einsum("bsd,dhe->bshe", x, p["w_v"])
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_full(p, x, cfg: ModelConfig, positions, seg=None,
              causal=True, use_rope=True):
    """Full-sequence self attention. Returns (out, (k, v)) for the cache."""
    B, S, _ = x.shape
    window = cfg.sliding_window if cfg.attention == AttentionKind.SWA else 0
    q, k, v = _qkv_full(p, x, cfg, positions, use_rope)
    if S > FLASH_THRESHOLD:
        o = A.flash_attention_xla(q, k, v, positions, positions, seg, seg,
                                  causal=causal, window=window,
                                  block=FLASH_BLOCK, sorted_layout=causal)
    else:
        mask = A.build_mask(positions, positions, seg, seg, causal, window)
        o = A.gqa_reference(q, k, v, mask)
    out = jnp.einsum("bshe,hed->bsd", o, p["w_o"])
    return out, (k, v)


def cross_attn_full(p, x, enc_out, cfg: ModelConfig, enc_kv=None):
    """Cross attention (whisper decoder). No RoPE, full visibility."""
    B, S, D = x.shape
    hd = cfg.resolved_head_dim
    H, K = cfg.num_heads, cfg.num_kv_heads
    q = jnp.einsum("bsd,dhe->bshe", x, p["w_q"])
    if enc_kv is None:
        Se = enc_out.shape[1]
        k = jnp.einsum("bsd,dhe->bshe", enc_out, p["w_k"])
        v = jnp.einsum("bsd,dhe->bshe", enc_out, p["w_v"])
    else:
        k, v = enc_kv
        Se = k.shape[1]
    qpos = jnp.zeros((B, S), jnp.int32)
    kpos = jnp.zeros((B, Se), jnp.int32)
    mask = A.build_mask(qpos, kpos, causal=False)
    o = A.gqa_reference(q, k, v, mask)
    out = jnp.einsum("bshe,hed->bsd", o, p["w_o"])
    return out, (k, v)


def attn_decode(p, x, cfg: ModelConfig, k_cache, v_cache, kv_pos, pos):
    """Single-token decode; cache write handled by caller (returns new k,v)."""
    B, _, D = x.shape
    hd = cfg.resolved_head_dim
    H, K = cfg.num_heads, cfg.num_kv_heads
    window = cfg.sliding_window if cfg.attention == AttentionKind.SWA else 0
    q = jnp.einsum("bsd,dhe->bshe", x, p["w_q"])
    k = jnp.einsum("bsd,dhe->bshe", x, p["w_k"])
    v = jnp.einsum("bsd,dhe->bshe", x, p["w_v"])
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k = apply_rope(k, pos[:, None], cfg.rope_theta)
    # write into cache at ring (SWA) or linear position; for non-SWA caches
    # Sc == max_len so pos % Sc == pos.
    Sc = k_cache.shape[1]
    idx = pos % Sc
    bidx = jnp.arange(B)
    k_cache = k_cache.at[bidx, idx].set(k[:, 0])
    v_cache = v_cache.at[bidx, idx].set(v[:, 0])
    kv_pos = kv_pos.at[bidx, idx].set(pos)
    o = A.decode_attention(q, k_cache, v_cache, kv_pos, pos, window)
    out = jnp.einsum("bshe,hed->bsd", o, p["w_o"])
    return out, (k_cache, v_cache, kv_pos)


def _paged_write_site(block_tab, pos, block_size):
    """Physical (block, offset) of each row's current token.  Rows whose
    logical block is unset (inactive slots, or cur past the table) write
    into physical block 0 — the reserved null block — so they can keep
    stepping on garbage without touching live pages."""
    nbt = block_tab.shape[1]
    lb = jnp.clip(pos // block_size, 0, nbt - 1)
    phys = jnp.take_along_axis(block_tab, lb[:, None], axis=1)[:, 0]
    return jnp.maximum(phys, 0), pos % block_size


def attn_decode_paged(p, x, cfg: ModelConfig, k_pool, v_pool, kv_pos_pool,
                      block_tab, pos):
    """Single-token decode against a paged pool: scatter the new K/V into
    the row's current physical block, then block-gather attend.  Pools
    (N, bs, K, hd); kv_pos_pool (N, bs); block_tab (B, nbt); pos (B,)."""
    window = cfg.sliding_window if cfg.attention == AttentionKind.SWA else 0
    q = jnp.einsum("bsd,dhe->bshe", x, p["w_q"])
    k = jnp.einsum("bsd,dhe->bshe", x, p["w_k"])
    v = jnp.einsum("bsd,dhe->bshe", x, p["w_v"])
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k = apply_rope(k, pos[:, None], cfg.rope_theta)
    phys, off = _paged_write_site(block_tab, pos, k_pool.shape[1])
    k_pool = k_pool.at[phys, off].set(k[:, 0].astype(k_pool.dtype))
    v_pool = v_pool.at[phys, off].set(v[:, 0].astype(v_pool.dtype))
    kv_pos_pool = kv_pos_pool.at[phys, off].set(pos)
    with jax.named_scope("attention"):
        o = A.decode_attention_paged(q, k_pool, v_pool, kv_pos_pool,
                                     block_tab, pos, window)
    out = jnp.einsum("bshe,hed->bsd", o, p["w_o"])
    return out, (k_pool, v_pool, kv_pos_pool)


def mla_decode_paged(p, x, cfg: ModelConfig, ckv_pool, kr_pool, kv_pos_pool,
                     block_tab, pos):
    """Absorbed-form MLA decode over a paged latent pool (ckv_pool
    (N, bs, r); kr_pool (N, bs, dr))."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    q_nope, q_rope = _mla_q(p, x, cfg, pos[:, None])
    q_nope, q_rope = q_nope[:, 0], q_rope[:, 0]
    ckv = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["w_dkv"]), p["kv_norm"],
                   cfg.norm_eps)[:, 0]
    kr = apply_rope(
        jnp.einsum("bsd,de->bse", x, p["w_kr"])[:, :, None, :],
        pos[:, None], cfg.rope_theta)[:, 0, 0]
    phys, off = _paged_write_site(block_tab, pos, ckv_pool.shape[1])
    ckv_pool = ckv_pool.at[phys, off].set(ckv.astype(ckv_pool.dtype))
    kr_pool = kr_pool.at[phys, off].set(kr.astype(kr_pool.dtype))
    kv_pos_pool = kv_pos_pool.at[phys, off].set(pos)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, p["w_uk"])
    with jax.named_scope("attention"):
        ckv_g = A.gather_paged(ckv_pool, block_tab)
        kr_g = A.gather_paged(kr_pool, block_tab)
        kv_pos_g = A.gather_paged_pos(kv_pos_pool, block_tab)
        pattn, _ = A.mla_scores_decode(
            (q_lat * scale).astype(ckv_g.dtype),
            (q_rope * scale).astype(kr_g.dtype),
            ckv_g, kr_g, kv_pos_g, pos)
        ctx = jnp.einsum("bhs,bsr->bhr", pattn.astype(ckv_g.dtype), ckv_g)
    o = jnp.einsum("bhr,rhe->bhe", ctx, p["w_uv"])
    out = jnp.einsum("bhe,hed->bd", o, p["w_o"])[:, None]
    return out, (ckv_pool, kr_pool, kv_pos_pool)


# ---------------------------------------------------------------------------
# MLA sub-layer
# ---------------------------------------------------------------------------

def _mla_q(p, x, cfg: ModelConfig, positions):
    m = cfg.mla
    B, S, D = x.shape
    H = cfg.num_heads
    h = x
    if m.q_lora_rank:
        h = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["w_dq"]), p["q_norm"],
                     cfg.norm_eps)
    q = jnp.einsum("bsr,rhe->bshe", h, p["w_uq"])
    q_nope = q[..., :m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_full(p, x, cfg: ModelConfig, positions, seg=None):
    """Full-sequence MLA. Returns (out, (ckv, k_rope)) latent cache entries."""
    m = cfg.mla
    B, S, D = x.shape
    H = cfg.num_heads
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    ckv = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["w_dkv"]), p["kv_norm"],
                   cfg.norm_eps)
    k_rope = apply_rope(
        jnp.einsum("bsd,de->bse", x, p["w_kr"])[:, :, None, :], positions,
        cfg.rope_theta)[:, :, 0]                                   # (B,S,dr)
    k_nope = jnp.einsum("bsr,rhe->bshe", ckv, p["w_uk"])
    v = jnp.einsum("bsr,rhe->bshe", ckv, p["w_uv"])
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, H, m.qk_rope_head_dim))], axis=-1)
    if S > FLASH_THRESHOLD:
        o = A.flash_attention_xla(q, k, v, positions, positions, seg, seg,
                                  causal=True, block=FLASH_BLOCK,
                                  sorted_layout=True)
    else:
        mask = A.build_mask(positions, positions, seg, seg, True, 0)
        o = A.gqa_reference(q, k, v, mask)
    out = jnp.einsum("bshe,hed->bsd", o, p["w_o"])
    return out, (ckv, k_rope)


def mla_decode(p, x, cfg: ModelConfig, ckv_cache, kr_cache, kv_pos, pos):
    """Absorbed-form MLA decode against the latent cache (no per-head K/V)."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.num_heads
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    q_nope, q_rope = _mla_q(p, x, cfg, pos[:, None])
    q_nope, q_rope = q_nope[:, 0], q_rope[:, 0]            # (B,H,·)
    ckv = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["w_dkv"]), p["kv_norm"],
                   cfg.norm_eps)[:, 0]                      # (B,r)
    kr = apply_rope(
        jnp.einsum("bsd,de->bse", x, p["w_kr"])[:, :, None, :],
        pos[:, None], cfg.rope_theta)[:, 0, 0]              # (B,dr)
    Sc = ckv_cache.shape[1]
    bidx = jnp.arange(B)
    idx = pos % Sc
    ckv_cache = ckv_cache.at[bidx, idx].set(ckv)
    kr_cache = kr_cache.at[bidx, idx].set(kr)
    kv_pos = kv_pos.at[bidx, idx].set(pos)
    # absorb W_uk into q
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, p["w_uk"])
    pattn, _ = A.mla_scores_decode(
        (q_lat * scale).astype(ckv_cache.dtype),
        (q_rope * scale).astype(kr_cache.dtype),
        ckv_cache, kr_cache, kv_pos, pos)
    ctx = jnp.einsum("bhs,bsr->bhr", pattn.astype(ckv_cache.dtype), ckv_cache)
    o = jnp.einsum("bhr,rhe->bhe", ctx, p["w_uv"])
    out = jnp.einsum("bhe,hed->bd", o, p["w_o"])[:, None]
    return out, (ckv_cache, kr_cache, kv_pos)


# ---------------------------------------------------------------------------
# Chunk-extend attention (chunked prefill — the paper's C_chunk unit)
# ---------------------------------------------------------------------------

def attn_extend(p, x, cfg: ModelConfig, k_cache, v_cache, kv_pos, positions):
    """Multi-token extend: write the chunk's K/V into the cache, then attend
    q against the WHOLE cache with position masking (covers both history and
    intra-chunk causality in one pass)."""
    B, Sc, D = x.shape
    hd = cfg.resolved_head_dim
    H, K = cfg.num_heads, cfg.num_kv_heads
    window = cfg.sliding_window if cfg.attention == AttentionKind.SWA else 0
    q = jnp.einsum("bsd,dhe->bshe", x, p["w_q"])
    k = jnp.einsum("bsd,dhe->bshe", x, p["w_k"])
    v = jnp.einsum("bsd,dhe->bshe", x, p["w_v"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    S_buf = k_cache.shape[1]
    bidx = jnp.arange(B)[:, None]
    idx = positions % S_buf
    k_cache = k_cache.at[bidx, idx].set(k.astype(k_cache.dtype))
    v_cache = v_cache.at[bidx, idx].set(v.astype(v_cache.dtype))
    kv_pos = kv_pos.at[bidx, idx].set(positions)
    o = A.flash_attention_xla(
        q, k_cache, v_cache, positions, kv_pos,
        causal=True, window=window,
        block=min(FLASH_BLOCK, S_buf)) if S_buf > FLASH_THRESHOLD else None
    if o is None:
        mask = A.build_mask(positions, kv_pos, causal=True, window=window)
        mask &= (kv_pos >= 0)[:, None, :]
        o = A.gqa_reference(q, k_cache, v_cache, mask)
    out = jnp.einsum("bshe,hed->bsd", o, p["w_o"])
    return out, (k_cache, v_cache, kv_pos)


def mla_extend(p, x, cfg: ModelConfig, ckv_cache, kr_cache, kv_pos, positions):
    """Chunk extend for MLA in absorbed form (latent cache only)."""
    m = cfg.mla
    B, Sc, D = x.shape
    H = cfg.num_heads
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    q_nope, q_rope = _mla_q(p, x, cfg, positions)           # (B,Sc,H,·)
    ckv = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["w_dkv"]), p["kv_norm"],
                   cfg.norm_eps)
    kr = apply_rope(
        jnp.einsum("bsd,de->bse", x, p["w_kr"])[:, :, None, :], positions,
        cfg.rope_theta)[:, :, 0]
    S_buf = ckv_cache.shape[1]
    bidx = jnp.arange(B)[:, None]
    idx = positions % S_buf
    ckv_cache = ckv_cache.at[bidx, idx].set(ckv.astype(ckv_cache.dtype))
    kr_cache = kr_cache.at[bidx, idx].set(kr.astype(kr_cache.dtype))
    kv_pos = kv_pos.at[bidx, idx].set(positions)
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, p["w_uk"]) * scale
    s = jnp.einsum("bshr,btr->bhst", q_lat.astype(jnp.float32),
                   ckv_cache.astype(jnp.float32))
    s += jnp.einsum("bshd,btd->bhst", (q_rope * scale).astype(jnp.float32),
                    kr_cache.astype(jnp.float32))
    valid = (kv_pos >= 0)[:, None, None, :] & \
        (kv_pos[:, None, None, :] <= positions[:, None, :, None])
    s = jnp.where(valid, s, A.NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    w = jnp.where(valid.any(-1)[..., None], w, 0.0)
    ctx = jnp.einsum("bhst,btr->bshr", w.astype(ckv_cache.dtype), ckv_cache)
    o = jnp.einsum("bshr,rhe->bshe", ctx, p["w_uv"])
    out = jnp.einsum("bshe,hed->bsd", o, p["w_o"])
    return out, (ckv_cache, kr_cache, kv_pos)


def block_extend(p, x, kind: LayerKind, cfg: ModelConfig, cache_entry,
                 kv_pos, positions):
    """Chunked-prefill block step: like block_decode but for Sc tokens."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind in (LayerKind.DENSE, LayerKind.MOE):
        if "xattn" in p:
            kv, enc_kv = cache_entry
        else:
            kv, enc_kv = cache_entry, None
        if cfg.attention == AttentionKind.MLA:
            y, new3 = mla_extend(p["attn"], h, cfg, kv[0], kv[1], kv_pos,
                                 positions)
        else:
            y, new3 = attn_extend(p["attn"], h, cfg, kv[0], kv[1], kv_pos,
                                  positions)
        new_entry, kv_pos = (new3[0], new3[1]), new3[2]
        if enc_kv is not None:
            new_entry = (new_entry, enc_kv)
    else:
        ssm_state, conv_state = cache_entry
        y, (ssm_state, conv_state) = mamba_forward(
            h, p["mamba"], cfg.ssm, ssm_state.astype(jnp.float32), conv_state)
        new_entry = (ssm_state, conv_state)
    x = x + y
    if "xattn" in p:
        h = rms_norm(x, p["ln_x"], cfg.norm_eps)
        _, enc_kv = cache_entry
        y, _ = cross_attn_full(p["xattn"], h, None, cfg, enc_kv=enc_kv)
        x = x + y
    if "moe" in p:
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        y, _ = moe_block(h, p["moe"], cfg.moe)
        x = x + y
    elif "mlp" in p:
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + swiglu(h, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                       p["mlp"]["w_down"])
    return x, new_entry, kv_pos


# ---------------------------------------------------------------------------
# Paged chunk-extend (page-native prefill: chunks write straight into the
# BlockPool pages a finished request will hand to decode, so there is no
# dense staging cache and no handoff-realization scatter)
# ---------------------------------------------------------------------------

def _paged_write_sites(block_tab, positions, block_size):
    """Per-token physical (block, offset) write sites for a chunk.
    block_tab (B, nbt); positions (B, Sc).  Positions past the table (or
    rows with unset logical blocks) write into the reserved null block."""
    nbt = block_tab.shape[1]
    lb = jnp.clip(positions // block_size, 0, nbt - 1)       # (B, Sc)
    phys = jnp.take_along_axis(block_tab, lb, axis=1)        # (B, Sc)
    return jnp.maximum(phys, 0), positions % block_size


def attn_extend_paged(p, x, cfg: ModelConfig, k_pool, v_pool, kv_pos_pool,
                      block_tab, positions):
    """Chunk extend against a paged pool: scatter the chunk's K/V into the
    row's physical blocks, then attend q over the block-table gather of
    the whole pool view — earlier-chunk (and shared-prefix) KV is read
    THROUGH the table, exactly like `attn_decode_paged`, with
    `attn_extend`'s position masking for intra-chunk causality."""
    window = cfg.sliding_window if cfg.attention == AttentionKind.SWA else 0
    q = jnp.einsum("bsd,dhe->bshe", x, p["w_q"])
    k = jnp.einsum("bsd,dhe->bshe", x, p["w_k"])
    v = jnp.einsum("bsd,dhe->bshe", x, p["w_v"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    phys, off = _paged_write_sites(block_tab, positions, k_pool.shape[1])
    k_pool = k_pool.at[phys, off].set(k.astype(k_pool.dtype))
    v_pool = v_pool.at[phys, off].set(v.astype(v_pool.dtype))
    kv_pos_pool = kv_pos_pool.at[phys, off].set(positions)
    kg = A.gather_paged(k_pool, block_tab)                   # (B, nbt*bs, ...)
    vg = A.gather_paged(v_pool, block_tab)
    kv_pos_g = A.gather_paged_pos(kv_pos_pool, block_tab)
    S_view = kg.shape[1]
    o = A.flash_attention_xla(
        q, kg, vg, positions, kv_pos_g,
        causal=True, window=window,
        block=min(FLASH_BLOCK, S_view)) if S_view > FLASH_THRESHOLD else None
    if o is None:
        mask = A.build_mask(positions, kv_pos_g, causal=True, window=window)
        mask &= (kv_pos_g >= 0)[:, None, :]
        o = A.gqa_reference(q, kg, vg, mask)
    out = jnp.einsum("bshe,hed->bsd", o, p["w_o"])
    return out, (k_pool, v_pool, kv_pos_pool)


def mla_extend_paged(p, x, cfg: ModelConfig, ckv_pool, kr_pool, kv_pos_pool,
                     block_tab, positions):
    """Chunk extend for MLA (absorbed form) over paged latent pools."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    q_nope, q_rope = _mla_q(p, x, cfg, positions)            # (B,Sc,H,·)
    ckv = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["w_dkv"]), p["kv_norm"],
                   cfg.norm_eps)
    kr = apply_rope(
        jnp.einsum("bsd,de->bse", x, p["w_kr"])[:, :, None, :], positions,
        cfg.rope_theta)[:, :, 0]
    phys, off = _paged_write_sites(block_tab, positions, ckv_pool.shape[1])
    ckv_pool = ckv_pool.at[phys, off].set(ckv.astype(ckv_pool.dtype))
    kr_pool = kr_pool.at[phys, off].set(kr.astype(kr_pool.dtype))
    kv_pos_pool = kv_pos_pool.at[phys, off].set(positions)
    ckv_g = A.gather_paged(ckv_pool, block_tab)
    kr_g = A.gather_paged(kr_pool, block_tab)
    kv_pos_g = A.gather_paged_pos(kv_pos_pool, block_tab)
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, p["w_uk"]) * scale
    s = jnp.einsum("bshr,btr->bhst", q_lat.astype(jnp.float32),
                   ckv_g.astype(jnp.float32))
    s += jnp.einsum("bshd,btd->bhst", (q_rope * scale).astype(jnp.float32),
                    kr_g.astype(jnp.float32))
    valid = (kv_pos_g >= 0)[:, None, None, :] & \
        (kv_pos_g[:, None, None, :] <= positions[:, None, :, None])
    s = jnp.where(valid, s, A.NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    w = jnp.where(valid.any(-1)[..., None], w, 0.0)
    ctx = jnp.einsum("bhst,btr->bshr", w.astype(ckv_g.dtype), ckv_g)
    o = jnp.einsum("bshr,rhe->bshe", ctx, p["w_uv"])
    out = jnp.einsum("bshe,hed->bsd", o, p["w_o"])
    return out, (ckv_pool, kr_pool, kv_pos_pool)


def block_extend_paged(p, x, kind: LayerKind, cfg: ModelConfig, cache_entry,
                       kv_pos_pool, block_tab, positions):
    """Chunked-prefill block step writing into paged pools.  Page-native
    prefill is attention-only (per-slot SSM / encoder state has no page
    representation — those configs keep the dense staging path)."""
    if kind not in (LayerKind.DENSE, LayerKind.MOE) or "xattn" in p:
        raise ValueError("paged prefill supports attention-only layers")
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    kv = cache_entry
    if cfg.attention == AttentionKind.MLA:
        y, new3 = mla_extend_paged(p["attn"], h, cfg, kv[0], kv[1],
                                   kv_pos_pool, block_tab, positions)
    else:
        y, new3 = attn_extend_paged(p["attn"], h, cfg, kv[0], kv[1],
                                    kv_pos_pool, block_tab, positions)
    new_entry, kv_pos_pool = (new3[0], new3[1]), new3[2]
    x = x + y
    if "moe" in p:
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        y, _ = moe_block(h, p["moe"], cfg.moe)
        x = x + y
    elif "mlp" in p:
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + swiglu(h, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                       p["mlp"]["w_down"])
    return x, new_entry, kv_pos_pool


# ---------------------------------------------------------------------------
# Block-level apply
# ---------------------------------------------------------------------------

def block_full(p, x, kind: LayerKind, cfg: ModelConfig, positions, seg=None,
               causal=True, use_rope=True, enc_out=None,
               ssm_init=None, conv_init=None):
    """Full-sequence block. Returns (x, cache_entry, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind in (LayerKind.DENSE, LayerKind.MOE):
        if cfg.attention == AttentionKind.MLA:
            y, kv = mla_full(p["attn"], h, cfg, positions, seg)
        else:
            y, kv = attn_full(p["attn"], h, cfg, positions, seg, causal,
                              use_rope)
        cache_entry = kv
    else:
        y, state = mamba_forward(h, p["mamba"], cfg.ssm, ssm_init, conv_init)
        cache_entry = state
    x = x + y
    if "xattn" in p:
        h = rms_norm(x, p["ln_x"], cfg.norm_eps)
        y, enc_kv = cross_attn_full(p["xattn"], h, enc_out, cfg)
        x = x + y
        cache_entry = (cache_entry, enc_kv)
    if "moe" in p:
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        y, laux = moe_block(h, p["moe"], cfg.moe)
        x = x + y
        aux = aux + laux
    elif "mlp" in p:
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + swiglu(h, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                       p["mlp"]["w_down"])
    return x, cache_entry, aux


def block_decode(p, x, kind: LayerKind, cfg: ModelConfig, cache_entry,
                 kv_pos, pos):
    """Single-token decode block. Returns (x, new_cache_entry, new_kv_pos).

    kv_pos is the shared per-model position map for attention caches
    (None for pure-SSM blocks).
    """
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind in (LayerKind.DENSE, LayerKind.MOE):
        if "xattn" in p:
            kv, enc_kv = cache_entry
        else:
            kv, enc_kv = cache_entry, None
        if cfg.attention == AttentionKind.MLA:
            y, new_kv3 = mla_decode(p["attn"], h, cfg, kv[0], kv[1], kv_pos, pos)
            new_entry, kv_pos = (new_kv3[0], new_kv3[1]), new_kv3[2]
        else:
            y, new_kv3 = attn_decode(p["attn"], h, cfg, kv[0], kv[1], kv_pos, pos)
            new_entry, kv_pos = (new_kv3[0], new_kv3[1]), new_kv3[2]
        if enc_kv is not None:
            new_entry = (new_entry, enc_kv)
    else:
        ssm_state, conv_state = cache_entry
        y, (ssm_state, conv_state) = mamba_decode_step(
            h, p["mamba"], cfg.ssm, ssm_state, conv_state)
        new_entry = (ssm_state, conv_state)
    x = x + y
    if "xattn" in p:
        h = rms_norm(x, p["ln_x"], cfg.norm_eps)
        _, enc_kv = cache_entry
        y, _ = cross_attn_full(p["xattn"], h, None, cfg, enc_kv=enc_kv)
        x = x + y
    if "moe" in p:
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        y, _ = moe_block(h, p["moe"], cfg.moe)
        x = x + y
    elif "mlp" in p:
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + swiglu(h, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                       p["mlp"]["w_down"])
    return x, new_entry, kv_pos


def block_decode_paged(p, x, kind: LayerKind, cfg: ModelConfig, cache_entry,
                       kv_pos_pool, block_tab, pos):
    """Single-token decode block over a paged cache.  Attention entries
    are physical block pools (no batch axis — the batch lives in
    `block_tab`); SSM states and encoder K/V stay per-slot exactly as in
    `block_decode` (their footprint is O(1) per request, paging them
    would buy nothing).  Returns (x, new_cache_entry, new_kv_pos_pool)."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind in (LayerKind.DENSE, LayerKind.MOE):
        if "xattn" in p:
            kv, enc_kv = cache_entry
        else:
            kv, enc_kv = cache_entry, None
        if cfg.attention == AttentionKind.MLA:
            y, new3 = mla_decode_paged(p["attn"], h, cfg, kv[0], kv[1],
                                       kv_pos_pool, block_tab, pos)
        else:
            y, new3 = attn_decode_paged(p["attn"], h, cfg, kv[0], kv[1],
                                        kv_pos_pool, block_tab, pos)
        new_entry, kv_pos_pool = (new3[0], new3[1]), new3[2]
        if enc_kv is not None:
            new_entry = (new_entry, enc_kv)
    else:
        ssm_state, conv_state = cache_entry
        y, (ssm_state, conv_state) = mamba_decode_step(
            h, p["mamba"], cfg.ssm, ssm_state, conv_state)
        new_entry = (ssm_state, conv_state)
    x = x + y
    if "xattn" in p:
        h = rms_norm(x, p["ln_x"], cfg.norm_eps)
        _, enc_kv = cache_entry
        y, _ = cross_attn_full(p["xattn"], h, None, cfg, enc_kv=enc_kv)
        x = x + y
    if "moe" in p:
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        y, _ = moe_block(h, p["moe"], cfg.moe)
        x = x + y
    elif "mlp" in p:
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + swiglu(h, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                       p["mlp"]["w_down"])
    return x, new_entry, kv_pos_pool

"""GQA decode-attention Pallas TPU kernel.

One new token per sequence attends to its KV cache — the decode phase's
memory-bound hot loop (it reads the entire cache every step; this is the K_i
term that Algorithm 3 balances). Grid (batch, kv_blocks) streams the cache
through VMEM one (block_kv, K·head_dim) tile per step — every KV head of a
block at once, the cache's (…, K, head_dim) minor dims merged (a free
reshape that also keeps an f32 cache from being relaid out for the call) —
and the online-softmax state of all H = K·G query heads rides in VMEM
scratch.

TPU block rule: the last two dims of every block equal the array's or are
multiples of (8, 128). Positions carry a unit axis for that reason; per-row
positions and block tables travel as scalar prefetch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attend_block(q_ref, k_ref, v_ref, valid, m_scr, l_scr, acc_scr, *,
                  scale: float, K: int):
    """Online-softmax update of every head group against one KV block.
    q_ref (1, K, G, hd); k_ref/v_ref (1, bk, K·hd); valid (1, bk)."""
    hd = q_ref.shape[-1]
    for h in range(K):
        q = q_ref[0, h]                       # (G, hd)
        k = k_ref[0, :, h * hd:(h + 1) * hd]  # (bk, hd)
        v = v_ref[0, :, h * hd:(h + 1) * hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[h]                     # (G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[h] = l_scr[h] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[h] = m_new


def _init_state(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _finalize(o_ref, l_scr, acc_scr):
    l = l_scr[...]
    out = jnp.where(l > 0, acc_scr[...] / jnp.maximum(l, 1e-30), 0.0)
    o_ref[0] = out.astype(o_ref.dtype)


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, kvpos_ref,
                   o_ref, m_scr, l_scr, acc_scr,
                   *, scale: float, window: int, kv_blocks: int, K: int):
    b = pl.program_id(0)
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _():
        _init_state(m_scr, l_scr, acc_scr)

    pos = pos_ref[b]
    kvpos = kvpos_ref[0, 0]                   # (1, bk)
    valid = (kvpos >= 0) & (kvpos <= pos)
    if window > 0:
        valid &= (pos - kvpos) < window
    _attend_block(q_ref, k_ref, v_ref, valid, m_scr, l_scr, acc_scr,
                  scale=scale, K=K)

    @pl.when(ik == kv_blocks - 1)
    def _():
        _finalize(o_ref, l_scr, acc_scr)


def _paged_decode_kernel(tab_ref, pos_ref, q_ref, k_ref, v_ref, kvpos_ref,
                         o_ref, m_scr, l_scr, acc_scr,
                         *, scale: float, window: int, nbt: int, K: int):
    """Block-table-aware decode attention.

    Grid (B, nbt): program (b, j) visits logical block j of row b. The
    physical block id comes from the scalar-prefetched block table —
    the BlockSpec index maps resolve `tab[b, j]` BEFORE the body runs, so
    the DMA streams exactly the row's own pages through VMEM (unset
    entries clamp to physical block 0, the null block, and are masked
    out via the prefetched table value)."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        _init_state(m_scr, l_scr, acc_scr)

    pos = pos_ref[b]
    kvpos = kvpos_ref[0]                      # (1, bs)
    valid = (kvpos >= 0) & (kvpos <= pos) & (tab_ref[b, j] >= 0)
    if window > 0:
        valid &= (pos - kvpos) < window
    _attend_block(q_ref, k_ref, v_ref, valid, m_scr, l_scr, acc_scr,
                  scale=scale, K=K)

    @pl.when(j == nbt - 1)
    def _():
        _finalize(o_ref, l_scr, acc_scr)


def _scratch(K: int, G: int, hd: int):
    return [pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, hd), jnp.float32)]


def paged_decode_attention_pallas(
    q: jnp.ndarray,           # (B, H, hd) — one token per row
    k_pool: jnp.ndarray,      # (N, bs, K, hd) — physical block pool
    v_pool: jnp.ndarray,
    kv_pos_pool: jnp.ndarray,  # (N, bs) int32, -1 = empty
    block_tab: jnp.ndarray,   # (B, nbt) int32, -1 = unset (null block)
    pos: jnp.ndarray,         # (B,) int32 current positions
    *,
    window: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    B, H, hd = q.shape
    N, bs, K, _ = k_pool.shape
    G = H // K
    nbt = block_tab.shape[1]
    kernel = functools.partial(_paged_decode_kernel, scale=hd ** -0.5,
                               window=window, nbt=nbt, K=K)

    def row(b, j, tab, _pos):
        return (b, 0, 0, 0)

    def page(b, j, tab, _pos):
        return (jnp.maximum(tab[b, j], 0), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # block table + positions
        grid=(B, nbt),
        in_specs=[
            pl.BlockSpec((1, K, G, hd), row),
            pl.BlockSpec((1, bs, K * hd), page),
            pl.BlockSpec((1, bs, K * hd), page),
            pl.BlockSpec((1, 1, bs), page),
        ],
        out_specs=pl.BlockSpec((1, K, G, hd), row),
        scratch_shapes=_scratch(K, G, hd),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=interpret,
    )(block_tab, pos, q.reshape(B, K, G, hd), k_pool.reshape(N, bs, K * hd),
      v_pool.reshape(N, bs, K * hd), kv_pos_pool.reshape(N, 1, bs))
    return out.reshape(B, H, hd)


def decode_attention_pallas(
    q: jnp.ndarray,           # (B, H, hd) — one token per row
    k_cache: jnp.ndarray,     # (B, S, K, hd)
    v_cache: jnp.ndarray,
    kv_pos: jnp.ndarray,      # (B, S) int32, -1 = empty
    pos: jnp.ndarray,         # (B,) int32 current positions
    *,
    window: int = 0,
    block_kv: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    B, H, hd = q.shape
    S, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    block_kv = min(block_kv, S)
    assert S % block_kv == 0, "cache length must be a block multiple"
    nkv = S // block_kv
    kernel = functools.partial(_decode_kernel, scale=hd ** -0.5,
                               window=window, kv_blocks=nkv, K=K)

    def row(b, ik, _pos):
        return (b, 0, 0, 0)

    def kv_block(b, ik, _pos):
        return (b, ik, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,            # positions
        grid=(B, nkv),
        in_specs=[
            pl.BlockSpec((1, K, G, hd), row),
            pl.BlockSpec((1, block_kv, K * hd), kv_block),
            pl.BlockSpec((1, block_kv, K * hd), kv_block),
            pl.BlockSpec((1, 1, 1, block_kv), lambda b, ik, _pos:
                         (b, ik, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, K, G, hd), row),
        scratch_shapes=_scratch(K, G, hd),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=interpret,
    )(pos, q.reshape(B, K, G, hd), k_cache.reshape(B, S, K * hd),
      v_cache.reshape(B, S, K * hd), kv_pos.reshape(B, nkv, 1, block_kv))
    return out.reshape(B, H, hd)

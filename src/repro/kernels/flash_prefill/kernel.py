"""Packed-varlen flash-attention Pallas TPU kernel.

This is the chunked-prefill compute unit (paper §4.2: C_chunk): a chunk
packs multiple requests' prompt segments; masking is causal WITHIN a segment
(segment ids + per-segment positions), with optional sliding window.

TPU schedule: grid (batch·kv_head, q_blocks, kv_blocks), kv innermost
("arbitrary" semantics) so the online-softmax running state (m, l, acc)
persists in VMEM scratch across kv iterations. BlockSpecs tile
q/k/v (block_q × head_dim) / (block_kv × head_dim) into VMEM; block sizes
default to 128/256 to keep MXU matmul dims at lane multiples of 128.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _flash_kernel(
    q_ref, k_ref, v_ref, qpos_ref, kvpos_ref, qseg_ref, kvseg_ref,  # inputs
    o_ref,                                                          # outputs
    m_scr, l_scr, acc_scr,                                          # scratch
    *, scale: float, causal: bool, window: int, kv_blocks: int,
):
    ikv = pl.program_id(2)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                    # (G·bq, hd)  — G query heads folded
    k = k_ref[0]                       # (bk, hd)
    v = v_ref[0]
    qpos = qpos_ref[0, 0]              # (G·bq, 1) — already folded like q
    qseg = qseg_ref[0, 0]
    kvpos = kvpos_ref[0, 0]            # (1, bk)
    kvseg = kvseg_ref[0, 0]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale     # (G·bq, bk)

    mask = (kvpos >= 0) & (kvseg == qseg) & (qseg >= 0)
    if causal:
        mask &= qpos >= kvpos
    if window > 0:
        mask &= (qpos - kvpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc = acc_scr[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ikv == kv_blocks - 1)
    def _finalize():
        l = l_scr[...]
        safe = jnp.maximum(l, 1e-30)
        out = jnp.where(l > 0, acc_scr[...] / safe, 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def flash_prefill_pallas(
    q: jnp.ndarray,            # (B, Sq, H, hd)
    k: jnp.ndarray,            # (B, Skv, K, hd)
    v: jnp.ndarray,
    q_pos: jnp.ndarray,        # (B, Sq) int32
    kv_pos: jnp.ndarray,       # (B, Skv)
    q_seg: jnp.ndarray,        # (B, Sq)   (-1 = pad)
    kv_seg: jnp.ndarray,       # (B, Skv)
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_kv: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Sq, H, hd = q.shape
    Skv, K = k.shape[1], k.shape[2]
    G = H // K
    scale = hd ** -0.5

    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    assert Sq % block_q == 0 and Skv % block_kv == 0, \
        "pad sequences to block multiples"
    nq, nkv = Sq // block_q, Skv // block_kv

    # layout: fold G into rows of the q tile -> (B, K, nq, G·bq, hd)
    qr = q.reshape(B, Sq, K, G, hd).transpose(0, 2, 1, 3, 4)  # B,K,Sq,G,hd
    qr = qr.reshape(B, K, nq, block_q, G, hd).transpose(0, 1, 2, 4, 3, 5)
    qr = qr.reshape(B * K, nq, G * block_q, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(B * K, Skv, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(B * K, Skv, hd)

    # positions/segments are shared by the K heads of a row (index maps
    # take b // K). q-side ones are folded like the q tile into a
    # (G·bq, 1) column; kv-side ones are (1, bk) rows. Both keep a unit
    # axis so each block's last two dims equal the array's.
    def q_col(a):
        a = a.reshape(B, nq, 1, block_q)
        return jnp.broadcast_to(a, (B, nq, G, block_q)).reshape(
            B, nq, G * block_q, 1)

    def kv_row(a):
        return a.reshape(B, nkv, 1, block_kv)

    grid = (B * K, nq, nkv)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        kv_blocks=nkv)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G * block_q, hd), lambda b, iq, ik: (b, iq, 0, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda b, iq, ik: (b, ik, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda b, iq, ik: (b, ik, 0)),
            pl.BlockSpec((1, 1, G * block_q, 1),
                         lambda b, iq, ik: (b // K, iq, 0, 0)),
            pl.BlockSpec((1, 1, 1, block_kv),
                         lambda b, iq, ik: (b // K, ik, 0, 0)),
            pl.BlockSpec((1, 1, G * block_q, 1),
                         lambda b, iq, ik: (b // K, iq, 0, 0)),
            pl.BlockSpec((1, 1, 1, block_kv),
                         lambda b, iq, ik: (b // K, ik, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G * block_q, hd),
                               lambda b, iq, ik: (b, iq, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * K, nq, G * block_q, hd), q.dtype),
        scratch_shapes=[
            # m, l: (rows, 1) f32; acc: (rows, hd) f32 — persist across the
            # kv grid axis (innermost, sequential on TPU)
            pltpu.VMEM((G * block_q, 1), jnp.float32),
            pltpu.VMEM((G * block_q, 1), jnp.float32),
            pltpu.VMEM((G * block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr, q_col(q_pos), kv_row(kv_pos), q_col(q_seg),
      kv_row(kv_seg))

    # un-fold: (B·K, nq, G·bq, hd) -> (B, Sq, H, hd)
    out = out.reshape(B, K, nq, G, block_q, hd).transpose(0, 2, 4, 1, 3, 5)
    out = out.reshape(B, Sq, K, G, hd).reshape(B, Sq, H, hd)
    return out

"""Plain operations shared by the reference forward passes: RMS norm,
rotary embedding over the two halves of the last axis, and causal
attention computed in blocks of query rows so that a long sequence's
score matrix fits next to the weights."""
from __future__ import annotations

import jax
import jax.numpy as jnp

Q_BLOCK = 512          # attention query rows per block


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """Rotate the two halves of the last axis (x: (S, heads, dim))."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def causal_attention(q, k, v, scale):
    """q (S, H, dq), k (S, H, dq), v (S, H, dv); query rows in blocks of
    Q_BLOCK so the score matrix of a long sequence fits."""
    s = q.shape[0]
    nb = s // Q_BLOCK

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        sc = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(rows[:, None] >= jnp.arange(s)[None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    return jax.lax.map(block, jnp.arange(nb)).reshape(s, *v.shape[1:])

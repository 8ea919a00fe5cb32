"""Mamba2 SSD intra-chunk Pallas TPU kernel.

Computes, per (batch, chunk, head) grid cell, the two MXU-friendly pieces of
the SSD chunked algorithm (arXiv:2405.21060 §6):

    y_diag[q,p] = Σ_k  (C_q·B_k) · exp(Ā_q − Ā_k) · dt_k · x[k,p]   (k ≤ q)
    state[p,n]  = Σ_k  exp(Ā_last − Ā_k) · dt_k · B_k[n] · x[k,p]

where Ā is the within-chunk cumulative sum of dt·A for that head. The
inter-chunk linear recurrence (tiny, sequential) remains a jax.lax.scan in
repro.models.mamba — the kernel replaces the quadratic/matmul-heavy part.

Tiling: one (Q × hp) x-tile and (Q × ds) B/C tiles per grid cell; Q=chunk
size (≤256) and hp/ds are 64/128 ⇒ all matmul dims are MXU-aligned
multiples. The per-head scalars (dt, and the cumulative Ā computed by the
wrapper) enter as (1, Q) rows and (Q, 1) columns, so every block's last two
dims equal the array's, as the TPU compiler requires.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, dt_ref, cum_ref, cumc_ref, w_ref, b_ref, c_ref,
                y_ref, state_ref):
    x = x_ref[0, 0].astype(jnp.float32)        # (Q, hp)
    dt = dt_ref[0, 0]                           # (1, Q) f32
    cum_row = cum_ref[0, 0]                     # (1, Q) Ā, inclusive cumsum
    cum_col = cumc_ref[0, 0]                    # (Q, 1) the same, as a column
    w = w_ref[0, 0]                             # (Q, 1) exp(Ā_last − Ā_k)·dt_k
    Bm = b_ref[0].astype(jnp.float32)           # (Q, ds)
    Cm = c_ref[0].astype(jnp.float32)           # (Q, ds)

    Q = x.shape[0]
    rel = cum_col - cum_row                     # (Q, Q)
    causal = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.exp(jnp.where(causal, rel, -1e30))  # mask pre-exp (overflow)
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,Q)
    att = CB * L * dt
    y = jax.lax.dot_general(att, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q,hp)
    state = jax.lax.dot_general((Bm * w).T, x,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (ds,hp)
    y_ref[0, 0] = y.astype(y_ref.dtype)
    state_ref[0, 0] = state.astype(state_ref.dtype)


def ssd_chunk_pallas(
    x: jnp.ndarray,      # (B, nc, Q, nh, hp)
    dt: jnp.ndarray,     # (B, nc, Q, nh) f32 (softplus'd)
    A: jnp.ndarray,      # (nh,) f32 negative
    Bm: jnp.ndarray,     # (B, nc, Q, ds)   (n_groups = 1, shared by heads)
    Cm: jnp.ndarray,     # (B, nc, Q, ds)
    *,
    interpret: bool = False,
):
    """Returns (y_diag (B,nc,Q,nh,hp) f32, states (B,nc,nh,ds,hp) f32)."""
    B, nc, Q, nh, hp = x.shape
    ds = Bm.shape[-1]
    xr = x.transpose(0, 1, 3, 2, 4).reshape(B * nc, nh, Q, hp)
    dtr = dt.astype(jnp.float32).transpose(0, 1, 3, 2).reshape(
        B * nc, nh, 1, Q)
    cum = jnp.cumsum(dtr * A.astype(jnp.float32)[:, None, None], axis=-1)
    w = jnp.exp(cum[..., -1:] - cum) * dtr
    br = Bm.reshape(B * nc, Q, ds)
    cr = Cm.reshape(B * nc, Q, ds)

    def head(b, h):
        return (b, h, 0, 0)

    def chunk(b, h):
        return (b, 0, 0)

    y, st = pl.pallas_call(
        _ssd_kernel,
        grid=(B * nc, nh),
        in_specs=[
            pl.BlockSpec((1, 1, Q, hp), head),
            pl.BlockSpec((1, 1, 1, Q), head),
            pl.BlockSpec((1, 1, 1, Q), head),
            pl.BlockSpec((1, 1, Q, 1), head),
            pl.BlockSpec((1, 1, Q, 1), head),
            pl.BlockSpec((1, Q, ds), chunk),
            pl.BlockSpec((1, Q, ds), chunk),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, hp), head),
            pl.BlockSpec((1, 1, ds, hp), head),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * nc, nh, Q, hp), jnp.float32),
            jax.ShapeDtypeStruct((B * nc, nh, ds, hp), jnp.float32),
        ],
        interpret=interpret,
    )(xr, dtr, cum, cum.reshape(B * nc, nh, Q, 1),
      w.reshape(B * nc, nh, Q, 1), br, cr)
    y = y.reshape(B, nc, nh, Q, hp).transpose(0, 1, 3, 2, 4)
    st = st.reshape(B, nc, nh, ds, hp)
    return y, st

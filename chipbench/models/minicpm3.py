"""minicpm3: OpenBMB MiniCPM3 (multi-head latent attention, dense SwiGLU).

The same three parts as granitemoe.py: `program_config` (the serving
stack's `ModelConfig`), `init_weights` (the serving stack's parameter
layout, from the seed) and the plain reference (`jax.numpy` only,
nothing of the program).

The reference computes MLA in its plain, unabsorbed form: per head,
keys are the latent times W_uk next to one shared rotary key, values the
latent times W_uv, scores scaled by (qk_nope + qk_rope) ** -0.5.  The
MiniCPM scalars are applied as published (embedding times scale_emb;
each residual branch times scale_depth / sqrt(layers); logits over
hidden_size / dim_model_base).  The serving stack has none of them; the
program is served weights with them folded in (`serve_weights`).
Rotary embedding is the plain one over the two halves of the rotary
dims, as the program computes it: the program has no `longrope`
scaling, and the configuration file says so.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from chipbench.models._plain import Q_BLOCK, causal_attention, rms, rope


def program_config(conf: Dict):
    """The serving stack's `ModelConfig` (the scalars reach the program
    through `serve_weights`); refuses rope scaling, which it lacks."""
    from repro.config.base import (AttentionKind, LayerKind, MLAConfig,
                                   ModelConfig)
    n, d = conf["num_hidden_layers"], conf["hidden_size"]
    if conf["rope_scaling"] is not None:
        raise ValueError("the serving stack has no rope scaling")
    return ModelConfig(
        name=conf["name"], family="dense", num_layers=n, d_model=d,
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        attention=AttentionKind.MLA,
        mla=MLAConfig(q_lora_rank=conf["q_lora_rank"],
                      kv_lora_rank=conf["kv_lora_rank"],
                      qk_nope_head_dim=conf["qk_nope_head_dim"],
                      qk_rope_head_dim=conf["qk_rope_head_dim"],
                      v_head_dim=conf["v_head_dim"]),
        layer_pattern=(LayerKind.DENSE,),
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        max_seq_len=conf["max_position_embeddings"],
        source=conf["source"])


def _mla(conf: Dict):
    return (conf["q_lora_rank"], conf["kv_lora_rank"],
            conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
            conf["v_head_dim"])


def param_counts(conf: Dict) -> Dict[str, float]:
    d, h, f = (conf["hidden_size"], conf["num_attention_heads"],
               conf["intermediate_size"])
    qr, r, dn, dr, dv = _mla(conf)
    attn = (d * qr + qr * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv)
            + h * dv * d)
    embed = conf["vocab_size"] * d * (1 if conf["tie_word_embeddings"]
                                      else 2)
    per_layer = attn + 3 * d * f
    total = embed + conf["num_hidden_layers"] * per_layer
    return {"total": float(total), "active": float(total),
            "embed": float(embed)}


def cache_bytes_per_token(conf: Dict) -> int:
    """Bytes of one position's fp32 latent and rotary key over all
    layers."""
    _qr, r, _dn, dr, _dv = _mla(conf)
    return conf["num_hidden_layers"] * (r + dr) * 4


def logit_factor(conf: Dict) -> float:
    """The program's logits over the published ones under
    `serve_weights`."""
    return conf["scale_emb"] * conf["hidden_size"] / conf["dim_model_base"]


def _residual(conf: Dict) -> float:
    return conf["scale_depth"] / math.sqrt(conf["num_hidden_layers"])


def serve_weights(conf: Dict, params: Dict) -> Dict:
    """The weights the program is served: `params` with the MiniCPM
    scalars folded in, since the serving stack has none of them.

    - scale_emb scales the (tied) embedding table, so the program's
      logits come out `logit_factor` times the published ones: the same
      order, the same greedy tokens;
    - scale_depth / sqrt(layers) scales w_o and w_down, the last linear
      map of each residual branch."""
    res = _residual(conf)
    blk = params["blocks"]["p0"]
    attn = dict(blk["attn"], w_o=blk["attn"]["w_o"] * res)
    mlp = dict(blk["mlp"], w_down=blk["mlp"]["w_down"] * res)
    return dict(params, embed=params["embed"] * conf["scale_emb"],
                blocks={"p0": dict(blk, attn=attn, mlp=mlp)})


def attention_flops(conf: Dict, ctx: float) -> float:
    """Scores over (qk_nope + qk_rope) and values over v_head, per head,
    as the unabsorbed form counts them."""
    _qr, _r, dn, dr, dv = _mla(conf)
    return (conf["num_hidden_layers"] * 2.0 * ctx
            * conf["num_attention_heads"] * (dn + dr + dv))


def init_weights(conf: Dict, key) -> Dict:
    d, h, f = (conf["hidden_size"], conf["num_attention_heads"],
               conf["intermediate_size"])
    n, v = conf["num_hidden_layers"], conf["vocab_size"]
    qr, r, dn, dr, dv = _mla(conf)
    ks = iter(jax.random.split(key, 20))

    def normal(shape, scale):
        return jax.random.normal(next(ks), shape, jnp.float32) * scale

    def norm(shape):
        return 1.0 + normal(shape, 0.1)

    return {
        "embed": normal((v, d), 0.02),
        "ln_f": norm((d,)),
        "blocks": {"p0": {
            "ln1": norm((n, d)),
            "attn": {"w_dq": normal((n, d, qr), d ** -0.5),
                     "q_norm": norm((n, qr)),
                     "w_uq": normal((n, qr, h, dn + dr), qr ** -0.5),
                     "w_dkv": normal((n, d, r), d ** -0.5),
                     "kv_norm": norm((n, r)),
                     "w_kr": normal((n, d, dr), d ** -0.5),
                     "w_uk": normal((n, r, h, dn), r ** -0.5),
                     "w_uv": normal((n, r, h, dv), r ** -0.5),
                     "w_o": normal((n, h, dv, d), (h * dv) ** -0.5)},
            "ln2": norm((n, d)),
            "mlp": {"w_gate": normal((n, d, f), d ** -0.5),
                    "w_up": normal((n, d, f), d ** -0.5),
                    "w_down": normal((n, f, d), f ** -0.5)}}},
    }


def reference_hidden(params: Dict, tokens, conf: Dict):
    """Final hidden states (S, d); S a multiple of Q_BLOCK."""
    h, eps = conf["num_attention_heads"], conf["rms_norm_eps"]
    _qr, _r, dn, dr, _dv = _mla(conf)
    theta = conf["rope_theta"]
    res = _residual(conf)
    pos = jnp.arange(tokens.shape[0])
    x = params["embed"][tokens] * conf["scale_emb"]

    def layer(x, p):
        a = p["attn"]
        y = rms(x, p["ln1"], eps)
        q = jnp.einsum("sr,rhe->she", rms(y @ a["w_dq"], a["q_norm"], eps),
                       a["w_uq"])
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, theta)],
                            -1)
        lat = rms(y @ a["w_dkv"], a["kv_norm"], eps)
        kr = rope((y @ a["w_kr"])[:, None, :], pos, theta)
        k = jnp.concatenate(
            [jnp.einsum("sr,rhe->she", lat, a["w_uk"]),
             jnp.broadcast_to(kr, (kr.shape[0], h, dr))], -1)
        v = jnp.einsum("sr,rhe->she", lat, a["w_uv"])
        o = causal_attention(q, k, v, (dn + dr) ** -0.5)
        x = x + res * jnp.einsum("she,hed->sd", o, a["w_o"])
        m = p["mlp"]
        b = rms(x, p["ln2"], eps)
        act = jax.nn.silu(b @ m["w_gate"]) * (b @ m["w_up"])
        return x + res * (act @ m["w_down"]), None

    x, _ = jax.lax.scan(layer, x, params["blocks"]["p0"])
    return rms(x, params["ln_f"], eps)


def reference_logits(params: Dict, hidden, conf: Dict):
    head = params["embed"] if conf["tie_word_embeddings"] else \
        params["lm_head"].T
    scale = conf["hidden_size"] / conf["dim_model_base"]
    return (hidden / scale) @ head.T

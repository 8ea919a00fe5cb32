"""granitemoe: IBM Granite 3.0 MoE (GQA attention, dropless top-k experts).

Three things the benchmark needs of this architecture, each apart:

- `program_config` maps the configuration file onto the serving stack's
  `ModelConfig` (the system under test);
- `init_weights` makes the weights from the seed, in the parameter layout
  that the serving stack takes, and `serve_weights` folds the published
  multipliers into a copy of them for the program;
- `reference_hidden` / `reference_logits` are the plain forward pass that
  decides `correct`.  They use `jax.numpy` only and import nothing of
  the program.

The reference follows the published Granite MoE equations, multipliers
included (hidden = embed * embedding_multiplier; each residual branch
scaled by residual_multiplier; attention scores scaled by
attention_multiplier; logits divided by logits_scaling; router softmax
over the top-k logits).  The serving stack has no multipliers of its
own; the program is served weights with them folded in (see
`serve_weights`), which computes the published model up to a positive
factor on the logits, so its greedy tokens are the published model's.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from chipbench.models._plain import Q_BLOCK, causal_attention, rms, rope


def _dims(conf: Dict):
    d, h, k = (conf["hidden_size"], conf["num_attention_heads"],
               conf["num_key_value_heads"])
    return d, h, k, d // h


def program_config(conf: Dict):
    """The serving stack's `ModelConfig` for this file (the multipliers
    reach the program through `serve_weights`)."""
    from repro.config.base import (AttentionKind, LayerKind, ModelConfig,
                                   MoEConfig)
    d, h, k, hd = _dims(conf)
    e, top = conf["num_local_experts"], conf["num_experts_per_tok"]
    return ModelConfig(
        name=conf["name"], family="moe",
        num_layers=conf["num_hidden_layers"], d_model=d, num_heads=h,
        num_kv_heads=k, d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"], attention=AttentionKind.GQA,
        layer_pattern=(LayerKind.MOE,),
        # capacity factor E/k gives every expert room for every token:
        # dropless routing, as the published model routes
        moe=MoEConfig(num_experts=e, top_k=top,
                      d_expert=conf["intermediate_size"],
                      capacity_factor=e / top),
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        max_seq_len=conf["max_position_embeddings"],
        source=conf["source"])


def param_counts(conf: Dict) -> Dict[str, float]:
    """Parameters in all and per token (top-k experts), from the
    published shapes; the tied embedding counts once."""
    d, h, k, hd = _dims(conf)
    f, e = conf["intermediate_size"], conf["num_local_experts"]
    top, n = conf["num_experts_per_tok"], conf["num_hidden_layers"]
    embed = conf["vocab_size"] * d * (1 if conf["tie_word_embeddings"]
                                      else 2)
    attn = d * h * hd + 2 * d * k * hd + h * hd * d
    router = d * e
    return {"total": float(embed + n * (attn + router + e * 3 * d * f)),
            "active": float(embed + n * (attn + router + top * 3 * d * f)),
            "embed": float(embed)}


def cache_bytes_per_token(conf: Dict) -> int:
    """Bytes of one position's fp32 keys and values over all layers."""
    _d, _h, k, hd = _dims(conf)
    return conf["num_hidden_layers"] * 2 * k * hd * 4


def logit_factor(conf: Dict) -> float:
    """The program's logits over the published ones under
    `serve_weights`."""
    return conf["embedding_multiplier"] * conf["logits_scaling"]


def attention_flops(conf: Dict, ctx: float) -> float:
    """Score and value FLOPs of one token attending to `ctx` positions,
    over all layers."""
    d, h, k, hd = _dims(conf)
    return conf["num_hidden_layers"] * 2.0 * ctx * h * (hd + hd)


def init_weights(conf: Dict, key) -> Dict:
    """Random fp32 weights in the serving stack's layout: stacked over
    layers under blocks/p0.  Norm scales are 1 + 0.1 N(0, 1), so the
    comparison sees them; the embedding is drawn at 0.02, the published
    initializer range.  w_q is drawn wider by head_dim ** -0.5 /
    attention_multiplier, so that the scores have the spread they would
    have under the usual head_dim ** -0.5: with the published 1/64 and
    weights drawn for 1/8 attention is close to a plain mean over the
    context."""
    d, h, k, hd = _dims(conf)
    q_wide = hd ** -0.5 / conf["attention_multiplier"]
    f, e = conf["intermediate_size"], conf["num_local_experts"]
    n, v = conf["num_hidden_layers"], conf["vocab_size"]
    ks = iter(jax.random.split(key, 16))

    def normal(shape, scale):
        return jax.random.normal(next(ks), shape, jnp.float32) * scale

    def norm(shape):
        return 1.0 + normal(shape, 0.1)

    return {
        "embed": normal((v, d), 0.02),
        "ln_f": norm((d,)),
        "blocks": {"p0": {
            "ln1": norm((n, d)),
            "attn": {"w_q": normal((n, d, h, hd), d ** -0.5 * q_wide),
                     "w_k": normal((n, d, k, hd), d ** -0.5),
                     "w_v": normal((n, d, k, hd), d ** -0.5),
                     "w_o": normal((n, h, hd, d), (h * hd) ** -0.5)},
            "ln2": norm((n, d)),
            "moe": {"router": normal((n, d, e), d ** -0.5),
                    "w_gate": normal((n, e, d, f), d ** -0.5),
                    "w_up": normal((n, e, d, f), d ** -0.5),
                    "w_down": normal((n, e, f, d), f ** -0.5)}}},
    }


def serve_weights(conf: Dict, params: Dict) -> Dict:
    """The weights the program is served: `params` with the published
    multipliers folded in, since the serving stack scales attention
    scores by head_dim ** -0.5 and has no other multiplier.

    - embedding_multiplier scales the (tied) embedding table, so the
      program's logits come out embedding_multiplier * logits_scaling
      times the published ones: the same order, the same greedy tokens;
    - attention_multiplier / head_dim ** -0.5 scales w_q (RoPE is linear);
    - residual_multiplier scales w_o and every expert's w_down, the last
      linear map of each residual branch."""
    _d, _h, _k, hd = _dims(conf)
    rm = conf["residual_multiplier"]
    blk = params["blocks"]["p0"]
    attn = dict(blk["attn"],
                w_q=blk["attn"]["w_q"] * (conf["attention_multiplier"]
                                          * math.sqrt(hd)),
                w_o=blk["attn"]["w_o"] * rm)
    moe = dict(blk["moe"], w_down=blk["moe"]["w_down"] * rm)
    return dict(params, embed=params["embed"] * conf["embedding_multiplier"],
                blocks={"p0": dict(blk, attn=attn, moe=moe)})


def reference_hidden(params: Dict, tokens, conf: Dict):
    """Final hidden states (S, d) of one sequence; S is a multiple of
    Q_BLOCK (pad at the end: attention is causal)."""
    d, h, k, hd = _dims(conf)
    top, eps = conf["num_experts_per_tok"], conf["rms_norm_eps"]
    rm = conf["residual_multiplier"]
    pos = jnp.arange(tokens.shape[0])
    x = params["embed"][tokens] * conf["embedding_multiplier"]

    def layer(x, p):
        a = rms(x, p["ln1"], eps)
        q = rope(jnp.einsum("sd,dhe->she", a, p["attn"]["w_q"]), pos,
                  conf["rope_theta"])
        kk = rope(jnp.einsum("sd,dhe->she", a, p["attn"]["w_k"]), pos,
                   conf["rope_theta"])
        vv = jnp.einsum("sd,dhe->she", a, p["attn"]["w_v"])
        g = h // k
        o = causal_attention(q, jnp.repeat(kk, g, 1), jnp.repeat(vv, g, 1),
                              conf["attention_multiplier"])
        x = x + rm * jnp.einsum("she,hed->sd", o, p["attn"]["w_o"])
        m = p["moe"]
        b = rms(x, p["ln2"], eps)
        logit = b @ m["router"]
        top_l, top_e = jax.lax.top_k(logit, top)
        gate = jnp.zeros_like(logit).at[
            jnp.arange(logit.shape[0])[:, None], top_e].set(
            jax.nn.softmax(top_l, -1))
        up = jnp.einsum("sd,edf->sef", b, m["w_up"])
        act = jax.nn.silu(jnp.einsum("sd,edf->sef", b, m["w_gate"])) * up
        y = jnp.einsum("sef,efd->sed", act, m["w_down"])
        return x + rm * jnp.einsum("se,sed->sd", gate, y), None

    x, _ = jax.lax.scan(layer, x, params["blocks"]["p0"])
    return rms(x, params["ln_f"], eps)


def reference_logits(params: Dict, hidden, conf: Dict):
    """Logits of the rows of `hidden`."""
    head = params["embed"] if conf["tie_word_embeddings"] else \
        params["lm_head"].T
    return hidden @ head.T / conf["logits_scaling"]

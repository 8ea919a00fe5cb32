"""The benchmark's counts against the serving stack's own: parameter
counts agree with `ModelConfig.param_counts`, the weights the benchmark
makes have the program's parameter layout, and the FLOPs per token add
the attention term to twice the active parameters."""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from chipbench import flops, harness  # noqa: E402

CONFIGS = sorted(f for f in os.listdir(os.path.join(REPO, "chipbench",
                                                    "configs"))
                 if f.endswith(".json"))


def _load(fname):
    with open(os.path.join(REPO, "chipbench", "configs", fname)) as f:
        conf = json.load(f)
    return conf, harness.model_module(REPO, conf["model_type"])


@pytest.mark.parametrize("fname", CONFIGS)
def test_param_counts_match_the_program(fname):
    conf, model = _load(fname)
    ours = model.param_counts(conf)
    theirs = model.program_config(conf).param_counts()
    assert ours["total"] == theirs["total"]
    assert ours["active"] == theirs["active"]


@pytest.mark.parametrize("fname", CONFIGS)
def test_weights_have_the_program_layout(fname):
    import jax
    from repro.models import init_params
    conf, model = _load(fname)
    cfg = model.program_config(conf)
    key = jax.random.PRNGKey(0)
    prog = jax.eval_shape(lambda k: init_params(cfg, k), key)
    ours = jax.eval_shape(lambda k: model.init_weights(conf, k), key)
    assert jax.tree.structure(prog) == jax.tree.structure(ours)
    for a, b in zip(jax.tree.leaves(prog), jax.tree.leaves(ours)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


@pytest.mark.parametrize("fname", CONFIGS)
def test_flops_per_token(fname):
    conf, model = _load(fname)
    c = model.param_counts(conf)
    head = conf["vocab_size"] * conf["hidden_size"]
    f0 = flops.per_token(model, conf, 0)
    assert f0 == 2.0 * (c["active"] - c["embed"] + head)
    f1 = flops.per_token(model, conf, 1024)
    assert f1 - f0 == model.attention_flops(conf, 1024) > 0


def test_granite_numbers():
    conf, model = _load("granite-moe-1b-a400m.json")
    # 24 layers of attention (4.19e6) + router (32768) + 8 of 32 experts
    # (8 x 1.57e6), plus the tied 49155 x 1024 head
    assert model.param_counts(conf)["active"] == 428608512.0
    assert flops.per_token(model, conf, 0) == 2.0 * 428608512.0


def test_minicpm3_numbers():
    conf, model = _load("minicpm3-4b-l24.json")
    # 24 layers of MLA (13.5e6) and SwiGLU (49.2e6), plus the tied
    # 73448 x 2560 embedding: 1.69e9 parameters
    assert model.param_counts(conf)["total"] == 1692078080.0
    # latent 256 + rotary key 32, fp32, 24 layers; granite: K and V of 8
    # heads of 64, fp32, 24 layers
    assert model.cache_bytes_per_token(conf) == 24 * 288 * 4
    g, gm = _load("granite-moe-1b-a400m.json")
    assert gm.cache_bytes_per_token(g) == 24 * 2 * 8 * 64 * 4


@pytest.mark.parametrize("fname", CONFIGS)
def test_least_step_time_is_the_larger_bound(fname):
    conf, model = _load(fname)
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    wbytes = 4 * int(model.param_counts(conf)["total"])
    ctxs = [512, 1024]
    read = (wbytes + model.cache_bytes_per_token(conf) * 1536) / 819e9
    work = sum(flops.per_token(model, conf, c) for c in ctxs) / 197e12
    assert flops.least_step_s(model, conf, wbytes, ctxs, peaks) == \
        max(read, work)
    # a decode step of a few rows is bound by its reads
    assert read > work

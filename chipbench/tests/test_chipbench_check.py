"""Pieces of a run's check and report, each on its own: the published
scalars folded into the served weights, the decision that sets
`correct`, the row gaps and the sample, the backlog counts a run
reports, and the sweep's growth."""
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [REPO, os.path.join(REPO, "src"), HERE]

import tiny  # noqa: E402
from chipbench import check, harness, sweep  # noqa: E402

NEUTRAL = {"embedding_multiplier": 1.0, "attention_multiplier": 0.25,
           "residual_multiplier": 1.0, "logits_scaling": 1.0}
PUBLISHED = {"embedding_multiplier": 12.0, "attention_multiplier": 0.015625,
             "residual_multiplier": 0.22, "logits_scaling": 6.0}
MLA_NEUTRAL = {"scale_emb": 1.0, "scale_depth": 2 ** 0.5,
               "dim_model_base": 64}


@pytest.mark.parametrize("conf", [
    dict(tiny.TINY_CONF, **NEUTRAL), dict(tiny.TINY_CONF, **PUBLISHED),
    dict(tiny.TINY_MLA_CONF, **MLA_NEUTRAL), tiny.TINY_MLA_CONF],
    ids=["granite_neutral", "granite_published", "mla_neutral",
         "mla_published"])
def test_served_weights_compute_the_reference_up_to_a_logit_factor(conf):
    """The program's full-sequence forward on the folded weights equals
    the plain reference times the model file's logit factor."""
    import jax
    import numpy as np
    from repro.models.model import forward_full, logits_from_hidden
    model = harness.model_module(REPO, conf["model_type"])
    cfg = model.program_config(conf)
    params = model.init_weights(conf, jax.random.PRNGKey(3))
    served = model.serve_weights(conf, params)
    assert jax.tree.structure(served) == jax.tree.structure(params)
    toks = jax.random.randint(jax.random.PRNGKey(4), (model.Q_BLOCK,), 0,
                              conf["vocab_size"])
    with jax.default_matmul_precision("highest"):
        h, _c, _a, _e = forward_full(cfg, served, toks[None])
        prog = np.asarray(logits_from_hidden(cfg, served, h))[0]
        ref = np.asarray(model.reference_logits(
            params, model.reference_hidden(params, toks, conf), conf))
    factor = model.logit_factor(conf)
    np.testing.assert_allclose(prog / factor, ref, rtol=1e-4,
                               atol=1e-4 * np.abs(ref).max())
    assert (prog.argmax(-1) == ref.argmax(-1)).all()


CHECK = {"logit_err": 1e-4, "min_tokens_compared": 4}


@pytest.mark.parametrize("gaps,off,n_tok,want", [
    ([0.0] * 19 + [5.0], 0, 4, True),
    ([1e-4] * 18 + [5.0] * 2, 0, 4, True),
    ([0.0] * 17 + [2e-4] * 3, 0, 4, False),
    ([0.0] * 10 + [2e-4] * 10, 0, 4, False),
    ([0.0] * 20, 1, 4, False),
    ([0.0] * 20, 0, 3, False),
    ([], 0, 4, False),
], ids=["p90_under_one_far_row", "at_limit", "over_limit",
        "half_the_rows_over", "token_off_its_row", "too_few_tokens",
        "no_sample"])
def test_decide_holds_each_number_to_its_limit(gaps, off, n_tok, want):
    import numpy as np
    reading = {"gaps": np.asarray(gaps), "weights": np.ones(len(gaps)),
               "off": off, "tokens": n_tok, "missing": 0}
    correct, checks = check.decide(CHECK, reading)
    assert correct is want
    assert list(checks) == ["logit_err_p90", "tokens_not_argmax",
                            "tokens_compared"]
    assert checks["logit_err_p90"]["at_most"] == 1e-4
    assert checks["tokens_not_argmax"] == {"value": off, "at_most": 0}
    assert checks["tokens_compared"] == {"value": n_tok, "at_least": 4}


@pytest.mark.parametrize("values,weights,q,want", [
    ([3.0, 1.0, 2.0, 4.0], [1, 1, 1, 1], 50, 2.0),
    ([3.0, 1.0, 2.0, 4.0], [1, 1, 1, 1], 90, 4.0),
    ([1.0, 2.0, 9.0], [1, 1, 8], 50, 9.0),
    ([9.0] * 8 + [1.0, 1.0], [1 / 8] * 8 + [1, 1], 60, 1.0),
    ([], [], 90, float("inf")),
], ids=["median", "p90_of_four", "heavy_row", "one_long_request_of_three",
        "empty"])
def test_weighted_percentile(values, weights, q, want):
    assert check.weighted_percentile(values, weights, q) == want


def test_one_long_request_weighs_as_one_of_many():
    """Rows of one request of ten moved by a tie (a tenth of the weight,
    however long it is) stay under the 90th percentile; rows of two of
    ten do not."""
    import numpy as np
    gaps = [np.full(400, 0.05)] + [np.full(20, 1e-6)] * 9
    reading = {"gaps": np.concatenate(gaps), "off": 0, "tokens": 580,
               "missing": 0,
               "weights": np.concatenate([np.full(len(g), 1 / len(g))
                                          for g in gaps])}
    assert check.decide(CHECK, reading)[0]
    gaps[1] = np.full(20, 0.05)
    reading["gaps"] = np.concatenate(gaps)
    assert not check.decide(CHECK, reading)[0]


def test_row_gaps_are_in_reference_standard_deviations():
    import jax.numpy as jnp
    import numpy as np
    ref = jnp.asarray([[0.0, 1.0, 2.0, 3.0], [4.0, 0.0, 0.0, 0.0]])
    vals = jnp.asarray([[3.5, 2.0], [4.0, 0.0]])
    ids = jnp.asarray([[3, 2], [0, 1]], jnp.int32)
    got = np.asarray(check._row_gaps(ref, vals, ids))
    np.testing.assert_allclose(got, [0.5 / np.std([0, 1, 2, 3]), 0.0])


def test_sample_holds_the_longest_and_is_drawn_from_the_seed():
    reqs = [types.SimpleNamespace(rid=i, output_len=10 + (i == 7) * 90,
                                  input_len=64) for i in range(20)]
    a = check.pick_sample(reqs, 5, 2 ** 33 + 1)
    assert a[0].rid == 7 and len(a) == 5
    assert [r.rid for r in a] == [r.rid for r in
                                  check.pick_sample(reqs, 5, 2 ** 33 + 1)]
    assert check.pick_sample([], 5, 1) == []


def test_backlog_counts_requests_by_where_they_wait():
    def req(rid, due, first=None, done=None):
        return types.SimpleNamespace(rid=rid, arrival_time=due,
                                     first_token_time=first,
                                     finish_time=done)
    run = types.SimpleNamespace(
        requests=[req(0, 0.0, 0.1, 0.5),        # finished by t = 1
                  req(1, 0.2, 0.4, 3.0),        # decoding at t = 1
                  req(2, 0.3, 0.6, None),       # first token, not joined
                  req(3, 0.9),                  # no first token yet
                  req(4, 2.0)],                 # not due at t = 1
        emissions=[(0.3, 0, 5), (0.7, 1, 5), (1.5, 2, 5)])
    assert harness.backlog(run, 1.0) == {
        "in_system": 3, "no_first_token": 1, "not_joined": 1}
    assert harness.backlog(run, 2.0) == {
        "in_system": 4, "no_first_token": 2, "not_joined": 0}


@pytest.mark.parametrize("counts,rise", [
    ([30] * 52, 0.0),
    (list(range(20, 72)), 51.0),
    ([5], 0.0),
], ids=["flat", "growing", "one_sample"])
def test_sweep_growth_is_the_rise_of_a_least_squares_line(counts, rise):
    assert sweep.growth(counts) == pytest.approx(rise)

"""Whole runs of the harness on the CPU, at a tiny size.

The harness's look for a chip is skipped (`require_chip=False`);
everything else of a run happens: weights from the seed, the server,
warm-up, open-loop serving, metrics and the comparison that decides
`correct`.  A sound run must read correct; a run with its timed path
broken must not, once for each fault a serving cell can have.  New
files (a configuration, a traffic mix, a per-layer metric) placed in a
copy of the benchmark are found by name with no edit to any file there.
"""
import os
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [REPO, os.path.join(REPO, "src"), HERE]

import tiny  # noqa: E402

SECONDS = 2.0
NEW_METRIC = {"name": "tiny_tokens_served", "unit": "tokens",
              "better": "higher", "source": "program_counter",
              "layer": "decode engine", "moves": "output_tok_s",
              "workloads": ["tiny.poisson"]}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    import jax
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_default_matmul_precision",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    root = tiny.make_root(str(tmp_path_factory.mktemp("bench")),
                          extra_per_layer=[NEW_METRIC])
    with open(os.path.join(root, "chipbench", "metrics",
                           "tiny_tokens_served.py"), "w") as f:
        f.write("def read(run):\n"
                "    return float(sum(1 for t, _r, _c in run.emissions\n"
                "                     if run.in_window(t)))\n")
    yield root
    from jax.experimental.compilation_cache import compilation_cache
    for k, v in keep.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _run(root, seed, trace=False, tamper=None, cell="tiny.poisson"):
    from chipbench import harness
    return harness.run(root, cell, seed, SECONDS, trace,
                       time.monotonic(), require_chip=False, tamper=tamper)


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_sound_run_is_correct_and_finds_new_files_by_name(root, cell):
    out = _run(root, 2 ** 31 + 5, cell=cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"ttft_p50_ms", "itl_p95_ms",
                                   "output_tok_s", "setup_s"}
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert list(out["checks"]) == ["logit_err_p90", "tokens_not_argmax",
                                   "tokens_compared"]
    assert out["checks"]["tokens_compared"]["value"] >= 20


def test_new_metric_is_read_by_name(root):
    from chipbench import harness
    cell = harness.load_cell(root, "tiny.poisson")
    assert "tiny_tokens_served" in [m["name"] for m in cell.per_layer]
    assert cell.conf["name"] == "tiny-granitemoe"
    assert cell.mix["arrivals"]["process"] == "poisson"
    read = harness.metric_reader(root, "tiny_tokens_served")
    assert callable(read)


def _stale_cache(srv):
    """A decode step that returns its state unchanged: the new token's
    key and value never reach the cache."""
    step = srv.spec.jit_paged_decode

    def broken(p, t, c):
        logits, _new = step(p, t, c)
        return logits, c
    srv.spec.jit_paged_decode = broken


def _half_the_batch(srv):
    """Half of the batch left out: the rows of every other request (odd
    rid) are not advanced by the decode step and keep the logits their
    slot had the step before.  The tiny mixes load the server so lightly
    that sbs-la places nearly every request on the first DP unit, so
    half the batch is drawn by request, not by DP unit."""
    import jax.numpy as jnp
    eng = srv.decode_engines[0]
    step = srv.spec.jit_paged_decode
    before = {}

    def broken(p, t, c):
        logits, new = step(p, t, c)
        dp = next(d for d in eng.dp_ids if eng._dp[d].cache is c)
        odd = [s for r, s in eng._participants.get(dp, []) if r.rid % 2]
        out = logits
        if odd and dp in before:
            rows = jnp.asarray(odd)
            out = logits.at[rows].set(before[dp][rows])
        before[dp] = logits
        return out, new
    srv.spec.jit_paged_decode = broken


def _rows_copied(srv):
    """Every row of the decode batch gets the logits computed for the
    row before it."""
    import jax.numpy as jnp
    step = srv.spec.jit_paged_decode

    def broken(p, t, c):
        logits, new = step(p, t, c)
        return jnp.roll(logits, 1, axis=0), new
    srv.spec.jit_paged_decode = broken


def _first_token_shifted(srv):
    """A token altered where it is produced: each prefill engine's first
    token is the argmax plus one."""
    vocab = srv.cfg.vocab_size
    for eng in srv.engines:
        def wrapped(req, tok, eng=eng, run_chunk=eng._run_chunk):
            ctx = eng._ctx.get(req.rid)
            had = ctx is not None and ctx.first_token is not None
            run_chunk(req, tok)
            ctx = eng._ctx.get(req.rid)
            if ctx is not None and ctx.first_token is not None \
                    and not had:
                ctx.first_token = (ctx.first_token + 1) % vocab
        eng._run_chunk = wrapped


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
@pytest.mark.parametrize("fault,caught_by", [
    (_stale_cache, "logit_err_p90"),
    (_half_the_batch, "logit_err_p90"),
    (_rows_copied, "logit_err_p90"),
    (_first_token_shifted, "tokens_not_argmax")],
    ids=["state_unchanged", "half_the_batch", "rows_copied",
         "first_token_shifted"])
def test_broken_timed_path_is_not_correct(root, fault, caught_by, cell):
    out = _run(root, 11, tamper=fault, cell=cell)
    assert not out["correct"], out["checks"]
    assert out["failed"] == 0
    chk = out["checks"][caught_by]
    assert chk["value"] > chk["at_most"]


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_lower_precision_control_is_not_correct(root, cell, monkeypatch):
    """The control: the plain reference put in the program's place, one
    precision below the configuration's, read as calibrate.py reads it.
    The CPU computes every matmul precision alike, so here the step below
    is taken by rounding the reference's weights to bfloat16 (on the
    chip, `high` matmuls)."""
    import jax
    import jax.numpy as jnp
    from chipbench import check, harness
    ses = harness.open_session(root, cell, require_chip=False)
    c, seed = ses.cell, 7
    params, srv = harness.build_server(c, seed)
    harness.warm_up(c, srv)
    run, tokens, _rec = harness.serve_window(ses, srv, seed, SECONDS,
                                             harness.tree_bytes(params))
    chk = c.conf["check"]
    sample = check.pick_sample(harness.finished(run, tokens),
                               chk["sample_requests"], seed)
    del srv, params
    ref = check.Reference(c.model, c.conf, harness.reference_weights(c, seed))
    low = check.Reference(c.model, c.conf, jax.tree.map(
        lambda x: x.astype(jnp.bfloat16).astype(x.dtype), ref.params))
    monkeypatch.setattr(check.Reference, "control_logits",
                        lambda self, r, toks, _p: low.logits(r, toks))
    reading = check.compare(ref, sample, tokens, {}, 1.0,
                            control=chk["control_matmul"])
    correct, checks = check.decide(chk, reading)
    assert not correct
    assert checks["logit_err_p90"]["value"] > chk["logit_err"]
    assert checks["tokens_compared"]["value"] >= chk["min_tokens_compared"]


def test_traced_run_reports_per_layer_metrics_on_cpu_without_a_trace(
        root, monkeypatch):
    from chipbench import harness, trace_reduce
    fake = {"busy_s": 0.5, "window_s": 1.0, "device_ops": [["op", 0.5]],
            "idle_gaps": [["host", 0.1]]}
    monkeypatch.setattr(trace_reduce, "reduce", lambda d, n: fake)
    out = harness.run(root, "tiny.poisson", 3, SECONDS, True,
                      time.monotonic(), require_chip=False)
    assert out["correct"]
    m = out["metrics"]
    assert m["device_idle_share"]["value"] == pytest.approx(50.0)
    assert m["tiny_tokens_served"]["value"] > 0
    assert {"sched_wait_ms_p50", "decode_step_ms"} <= set(m)
    # no peaks on the CPU: the shares of a peak are left out, not 0
    assert "decode_mfu" not in m and "decode_roofline_pct" not in m
    assert out["device"]["busy_s"] == 0.5
    assert out["breakdown"]["device_ops"] == [["op", 0.5]]
    assert list(out)[-2:] == ["breakdown", "checks"]


def test_stall_watch_names_where_the_runtime_thread_is_held(root):
    """A decode step's finish that holds the runtime thread for 0.6 s
    shows on the `[stall]` line, with the holding frame named."""
    import io

    def held(srv):
        eng = srv.decode_engines[0]
        finish, calls = eng.finish_step, []

        def hold_once_mid_run(now, dp_states):
            calls.append(now)
            if len(calls) == 30:
                time.sleep(0.6)
            return finish(now, dp_states)
        eng.finish_step = hold_once_mid_run

    from chipbench import harness
    log = io.StringIO()
    out = harness.run(root, "tiny.poisson", 5, SECONDS, False,
                      time.monotonic(), require_chip=False, tamper=held,
                      log=log)
    assert out["correct"], out["checks"]
    stall = [ln for ln in log.getvalue().splitlines()
             if ln.startswith("[stall]")]
    assert len(stall) == 1
    assert "hold_once_mid_run" in stall[0]
    assert " 0 looks" not in stall[0]


def test_runner_exits_nonzero_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", "granite.bursty", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=str(tmp_path), timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_runner_exits_nonzero_without_the_serving_stack(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's
    own files has no system to run."""
    import shutil
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    tmp_path / "chipbench")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "granite.bursty",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=str(tmp_path), timeout=300,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no serving stack" in p.stderr

"""The in-program recorder (`repro.serving.trace`) and what the serving
path records with it:

  * off, a span is the shared no-op: nothing is recorded and no
    `TraceAnnotation` is made
  * on, spans carry their name, ids, parent (same thread) and cause
    (another thread), and counts land on every span open on the thread
  * every `EngineSpec` program is jitted under its model function's name
  * a paged P/D serve records the layer boundaries: one `decode.step` per
    `step_samples` entry, of the same length within 1 ms, each with its
    DP jobs' launch and readout, and the readout's rows brought to the
    host in one transfer
"""
import random
import re
import threading

import jax
import jax.numpy as jnp
import pytest

from repro.config import ServingConfig, get_arch
from repro.core.types import Request
from repro.models import init_params
from repro.serving import trace
from repro.serving.real_engine import EngineSpec
from repro.serving.server import RealSBSServer

MAX_LEN = 96
BLOCK = 16


@pytest.fixture
def annotations(monkeypatch):
    """Every TraceAnnotation the recorder makes: (name, ids)."""
    made = []

    class Note:
        def __init__(self, name, **ids):
            made.append((name, ids))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False
    monkeypatch.setattr(trace, "TraceAnnotation", Note)
    return made


def test_off_records_nothing_and_makes_no_annotation(annotations):
    trace.disable()
    trace.reset()
    s = trace.span("decode.step", instance=0, step=3, rows=16)
    assert s is trace.OFF
    assert trace.span("prefill.chunk", rid=1, tokens=64) is s
    with s as entered:
        assert entered is trace.OFF
        assert trace.current() is None
        trace.count("decode.host_reads", 16)
    assert trace.spans() == [] and trace.counters() == {}
    assert annotations == []


def test_on_records_ids_parent_and_cause_across_threads(recorder,
                                                        annotations):
    handed = {}

    def worker(cause):
        with trace.span("decode.step", instance=0, step=7, rows=2,
                        cause=cause):
            with trace.span("decode.readout", dp=1):
                trace.count("decode.host_reads", 16)
        handed["thread"] = threading.get_ident()

    with trace.span("runtime.step_end", instance=0) as outer:
        with trace.span("decode.joins", instance=0):
            trace.count("decode.joins", 2)
        t = threading.Thread(target=worker, args=(trace.current(),))
        t.start()
        t.join()
    by = {s.name: s for s in trace.spans()}
    assert set(by) == {"runtime.step_end", "decode.joins", "decode.step",
                       "decode.readout"}
    step, read = by["decode.step"], by["decode.readout"]
    assert step.ids == {"instance": 0, "step": 7, "rows": 2}
    assert read.ids == {"dp": 1}
    assert by["decode.joins"].parent == outer.id
    assert outer.parent is None and outer.cause is None
    assert step.cause == outer.id and step.parent is None
    assert read.parent == step.id
    assert step.thread == handed["thread"] != outer.thread
    for s in by.values():
        assert s.start <= s.end
    assert outer.start <= step.start and step.end <= outer.end
    assert step.counts == read.counts == {"decode.host_reads": 16}
    assert outer.counts == {"decode.joins": 2}
    assert trace.counters() == {"decode.joins": 2, "decode.host_reads": 16}
    assert sorted(annotations, key=str) == sorted(
        [(s.name, s.ids) for s in by.values()], key=str)


def test_reset_drops_spans_and_counts(recorder):
    with trace.span("sched.poll"):
        trace.count("decode.joins")
    assert len(trace.spans()) == 1
    trace.reset()
    assert trace.spans() == [] and trace.counters() == {}


# ---------------------------------------------------------------------------
# Named programs
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_moe():
    cfg = get_arch("granite-moe-1b-a400m", reduced=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def paged_spec(tiny_moe):
    cfg, params = tiny_moe
    return EngineSpec(cfg, params, max_len=MAX_LEN, max_batch=2,
                      block_size=BLOCK)


PROGRAMS = {
    "jit_prefill_chunk": "prefill_chunk",
    "jit_decode": "decode_step",
    "jit_join": "cache_join",
    "jit_paged_decode": "paged_decode_step",
    "jit_paged_join": "paged_cache_join",
    "jit_paged_prefill": "paged_prefill_step",
    "jit_gather_blocks": "paged_gather_blocks",
    "jit_adopt_blocks": "paged_adopt_blocks",
    "jit_copy_block": "paged_copy_block",
    "jit_clear_rows": "paged_clear_rows",
    "jit_mixed": "mixed_step",
}


def _args(spec, attr):
    """Arguments each program is called with on the serving path."""
    p, nbt = spec.params, spec.nbt
    ids = jnp.ones((1, BLOCK), jnp.int32)
    tab = jnp.zeros((nbt,), jnp.int32)
    paged = spec.paged_cache()
    rows = jnp.zeros((spec.paged_slots, 1), jnp.int32)
    slot = jnp.int32(0)
    if attr == "jit_prefill_chunk":
        return p, ids, spec.request_cache()
    if attr == "jit_decode":
        return p, jnp.zeros((spec.max_batch, 1), jnp.int32), \
            spec.batch_cache()
    if attr == "jit_join":
        return spec.batch_cache(), spec.request_cache(), slot
    if attr == "jit_paged_decode":
        return p, rows, paged
    if attr == "jit_paged_join":
        return paged, spec.request_cache(), slot, tab
    if attr == "jit_paged_prefill":
        return p, ids, paged, slot
    if attr == "jit_gather_blocks":
        return paged, tab
    if attr == "jit_adopt_blocks":
        pay = jax.eval_shape(spec.jit_gather_blocks, paged, tab)
        pay = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), pay)
        mask = jnp.zeros((nbt,), bool)
        return paged, pay, slot, tab, mask, mask, jnp.int32(BLOCK)
    if attr == "jit_copy_block":
        return paged, jnp.int32(1), jnp.int32(2)
    if attr == "jit_clear_rows":
        return paged, tab
    if attr == "jit_mixed":
        return (p, rows, paged, ((ids, slot),),
                jnp.ones((spec.paged_slots,), bool))
    raise KeyError(attr)


@pytest.mark.parametrize("attr", sorted(PROGRAMS))
def test_every_engine_program_is_named(paged_spec, attr):
    fn = getattr(paged_spec, attr)
    assert fn.__name__ == PROGRAMS[attr]
    text = fn.lower(*_args(paged_spec, attr)).as_text()
    assert f"module @jit_{PROGRAMS[attr]} " in text.splitlines()[0]
    assert "jit__lambda" not in text


def test_decode_step_names_its_attention_and_moe_scopes(paged_spec):
    args = _args(paged_spec, "jit_paged_decode")
    text = paged_spec.jit_paged_decode.lower(*args).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    assert any("/attention/" in n for n in names)
    assert any("/moe/" in n for n in names)


# ---------------------------------------------------------------------------
# What a serve records
# ---------------------------------------------------------------------------

def _requests(cfg, n=6, seed=0):
    rng = random.Random(seed)
    out = []
    for i in range(n):
        L = rng.randrange(16, 64)
        out.append(Request(
            rid=i, arrival_time=0.01 * i, input_len=L, output_len=5,
            tokens=tuple(rng.randrange(cfg.vocab_size) for _ in range(L))))
    return out


@pytest.fixture(scope="module")
def served(tiny_moe):
    """A paged P/D serve (2 prefill instances, 2 decode DP units) with the
    recorder on: the server, its requests and the spans."""
    cfg, params = tiny_moe
    scfg = ServingConfig(num_prefill_instances=2, prefill_dp_per_instance=1,
                         num_decode_instances=1, decode_dp_per_instance=2,
                         chunk_size=32, max_batch_per_dp=2,
                         kv_budget_tokens=2 * MAX_LEN, block_size=BLOCK,
                         decode_slots_per_dp=4)
    srv = RealSBSServer(cfg, params, serving_cfg=scfg, scheduler="sbs-la",
                        max_len=MAX_LEN, max_new=5)
    reqs = _requests(cfg)
    trace.reset()
    trace.enable()
    try:
        srv.serve(reqs, timeout=300)
    finally:
        trace.disable()
    spans, counts = trace.spans(), trace.counters()
    trace.reset()
    return srv, reqs, spans, counts


def test_one_decode_step_span_per_step_sample(served):
    srv, _reqs, spans, _counts = served
    eng = srv.decode_engines[0]
    steps = sorted((s for s in spans if s.name == "decode.step"),
                   key=lambda s: s.start)
    assert len(steps) == len(eng.step_samples) > 0
    for s, (dur, active, _rows) in zip(steps, eng.step_samples):
        assert abs(s.seconds - dur) < 1e-3
        assert s.ids["rows"] == active
    assert [s.ids["step"] for s in steps] == list(
        range(1, len(steps) + 1))


def test_serve_records_every_layer_boundary(served):
    srv, reqs, spans, counts = served
    names = {s.name for s in spans}
    assert {"runtime.arrival", "runtime.pass_end", "runtime.kv_arrived",
            "runtime.step_end", "sched.poll", "prefill.pass",
            "prefill.chunk", "prefill.first_token", "decode.joins",
            "decode.step", "decode.launch", "decode.readout",
            "decode.finish"} <= names
    by_id = {s.id: s for s in spans}
    runtime = {s.id for s in spans if s.name.startswith("runtime.")}
    for s in spans:
        if s.name in ("decode.step", "prefill.pass"):
            # started by the runtime thread's span that submitted it
            assert s.cause in runtime
            assert s.thread != by_id[s.cause].thread
        if s.name in ("decode.launch", "decode.readout"):
            assert by_id[s.parent].name == "decode.step"
        if s.name in ("prefill.chunk", "prefill.first_token"):
            assert by_id[s.parent].name == "prefill.pass"
        if s.name in ("decode.joins", "decode.finish", "sched.poll"):
            assert s.parent in runtime
    # one first token per request, after its first chunk
    firsts = [s.ids["rid"] for s in spans if s.name == "prefill.first_token"]
    assert sorted(firsts) == sorted(r.rid for r in reqs)
    chunks = [s for s in spans if s.name == "prefill.chunk"]
    assert sum(s.ids["tokens"] for s in chunks) == sum(
        r.input_len for r in reqs)
    # each readout brings every row of its DP job to the host in one
    # transfer; a step carries its jobs' reads and transfers
    slots = srv.spec.paged_slots
    for s in spans:
        if s.name == "decode.readout":
            assert s.counts == {"decode.host_reads": slots,
                                "decode.host_transfers": 1}
        if s.name == "decode.step":
            jobs = sum(1 for c in spans if c.parent == s.id
                       and c.name == "decode.readout")
            assert s.counts["decode.host_reads"] == jobs * slots
            assert s.counts["decode.host_transfers"] == jobs
    for name in ("decode.host_reads", "decode.host_transfers"):
        assert counts[name] == sum(s.counts[name] for s in spans
                                   if s.name == "decode.step")
    assert counts["decode.joins"] == len(reqs)

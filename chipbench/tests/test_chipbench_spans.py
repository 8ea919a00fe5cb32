"""chipbench/spans.py on the CPU: the program's own spans of a tiny run
split each request's TTFT into parts that add up to it, and the decode
step's host numbers read the window; a small trace recorded on a TPU
v5e reduces to its named programs, its spans and the three-way split of
each decode step."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [REPO, os.path.join(REPO, "src"), HERE]

import tiny  # noqa: E402

SECONDS = 2.0


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    import jax
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_default_matmul_precision",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    yield tiny.make_root(str(tmp_path_factory.mktemp("bench")))
    from jax.experimental.compilation_cache import compilation_cache
    for k, v in keep.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module", params=sorted(tiny.CELLS))
def traced(root, request):
    """A tiny cell served with the program's recorder on from the
    window's serve only: the run record, its served tokens, the spans
    and the decode engines' step samples."""
    from chipbench import harness
    from repro.serving import trace
    ses = harness.open_session(root, request.param, require_chip=False)
    params, srv = harness.build_server(ses.cell, 13)
    harness.warm_up(ses.cell, srv)
    warm = [len(eng.step_samples) for eng in srv.decode_engines]
    trace.reset()
    trace.enable()
    try:
        run, tokens, _rec = harness.serve_window(
            ses, srv, 13, SECONDS, harness.tree_bytes(params))
    finally:
        trace.disable()
    spans = trace.spans()
    trace.reset()
    samples = [s for eng, n in zip(srv.decode_engines, warm)
               for s in eng.step_samples[n:]]
    return run, tokens, spans, samples


def test_ttft_parts_add_up_to_the_ttft(traced):
    from chipbench import harness, spans as sp
    run, tokens, spans, _samples = traced
    parts = sp.ttft_parts(run, spans)
    done = harness.finished(run, tokens)
    assert done and {r.rid for r in done} <= set(parts)
    for r in done:
        wait, queue, execute, handoff = parts[r.rid]
        assert wait == pytest.approx(r.dispatch_time - r.arrival_time)
        assert min(wait, queue, execute, handoff) >= 0.0, parts[r.rid]
        assert abs(wait + queue + execute + handoff - r.ttft) < 1e-6


def test_decode_numbers_read_the_window(traced):
    from chipbench import spans as sp
    run, _tokens, spans, samples = traced
    steps = [s for s in spans if s.name == "decode.step"]
    assert len(steps) == len(samples)
    in_window = [s for s in steps if run.in_window(s.end - run.loop_t0)]
    assert 0 < len(in_window) < len(steps)
    slots = run.cell.conf["deployment"]["decode_slots_per_dp"]
    reads = sp.host_reads_per_step(run, spans)
    # one read per row of each DP job; a step runs one or two jobs
    assert slots <= reads <= 2 * slots
    assert reads == sum(s.counts["decode.host_reads"]
                        for s in in_window) / len(in_window)
    assert 0.0 <= sp.decode_turnaround_ms(run, spans) < 1e3
    for read in (sp.prefill_queue_ms_p50, sp.prefill_exec_ms_p50):
        assert 0.0 <= read(run, spans) < 1e3


def test_spans_of_an_earlier_serve_are_left_out(traced):
    """Spans that started before the window's serve (warm-up, an earlier
    run) reuse its rids, and must not count."""
    from chipbench import spans as sp
    from repro.serving.trace import Span
    run, _tokens, spans, _samples = traced
    stale = []
    for r in run.due_in_window:
        s = Span("prefill.chunk", {"rid": r.rid, "tokens": 1}, None)
        s.start = s.end = run.loop_t0 - 1.0
        stale.append(s)
    assert stale
    assert sp.ttft_parts(run, stale + spans) == sp.ttft_parts(run, spans)
    assert sp.prefill_queue_ms_p50(run, stale) is None


# ---------------------------------------------------------------------------
# A trace recorded on a TPU v5e
# ---------------------------------------------------------------------------

TRACE = os.path.join(HERE, "spans_trace")


def test_reduces_the_recorded_spans_trace():
    """`spans_trace/` holds a trace recorded on a TPU v5e with the
    program's recorder on: four `decode.step` spans, each launching a
    `paged_decode_step` program (its softmax under an `attention` scope)
    and a `prefill_chunk` program and reading one value back, each step
    followed by a `prefill.chunk` span running one more `prefill_chunk`;
    and the decode program's compiled HLO text."""
    from chipbench import spans as sp, trace_reduce
    red = sp.reduce(TRACE)
    # the device clock runs behind the host's by about a millisecond
    assert 0.0 < red["clock_offset_s"] < 2e-3
    names = [m[0] for m in red["modules"]]
    assert names.count("jit_paged_decode_step") == 4
    assert names.count("jit_prefill_chunk") == 8
    # device time by program adds up to the executions, and holds the ops
    secs = sp.module_seconds(red)
    assert sum(secs.values()) == pytest.approx(
        sum(b - a for _n, a, b in red["modules"]))
    busy = trace_reduce.reduce(TRACE, 1)["busy_s"]
    assert busy <= sum(secs.values())
    for i, (_n, a, b) in enumerate(red["modules"]):
        assert sum(d for j, _o, d in red["ops"] if j == i) <= b - a
    # the program's spans, with their ids
    by = {}
    for name, a, b, ids in red["spans"]:
        by.setdefault(name, []).append((a, b, ids))
    assert [ids for _a, _b, ids in by["decode.step"]] == [
        {"instance": 0, "step": i, "rows": 512} for i in range(4)]
    assert [ids["rid"] for _a, _b, ids in by["prefill.chunk"]] == [0, 1, 2,
                                                                   3]
    assert len(by["decode.launch"]) == len(by["decode.readout"]) == 4
    steps = by["decode.step"]
    for name in ("decode.launch", "decode.readout"):
        for (a, b, _i), (sa, sb, _s) in zip(by[name], steps):
            assert sa <= a <= b <= sb
    # every program ran inside the span that launched it
    for name, a, b in red["modules"]:
        if name == "jit_paged_decode_step":
            holders = steps
        elif name == "jit_prefill_chunk":
            holders = steps + by["prefill.chunk"]
        else:
            continue
        assert any(sa <= a and b <= sb for sa, sb, _i in holders), name
    # each step splits three ways, and the scoped ops lie in its decode
    scoped = sp.scope_ops(open(os.path.join(TRACE, "spans.hlo.txt")).read(),
                          "attention")
    decode_ops = {o for i, o, _d in red["ops"]
                  if red["modules"][i][0] == "jit_paged_decode_step"}
    assert scoped & decode_ops and not scoped >= decode_ops
    parts = sp.split_steps(red, scoped)
    assert len(parts) == 4
    decode = [b - a for n, a, b in red["modules"]
              if n == "jit_paged_decode_step"]
    for p, (sa, sb, _i), dev in zip(parts, steps, decode):
        assert p["span_s"] == pytest.approx(sb - sa)
        assert p["device_s"] == pytest.approx(dev)
        assert p["blocked_s"] > 0.0 and p["rest_s"] > 0.0
        assert p["device_s"] + p["blocked_s"] + p["rest_s"] == \
            pytest.approx(p["span_s"])
        assert 0.0 < p["scoped_s"] < p["device_s"]
    assert sp.mean_ms(parts, "device_s") == pytest.approx(
        sum(decode) / 4 * 1e3)
    assert sp.mean_ms([], "blocked_s") is None


def test_scope_ops_reads_op_name_metadata():
    from chipbench import spans as sp
    hlo = "\n".join([
        '  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, calls=%f3, '
        'metadata={op_name="jit(paged_decode_step)/while/body/attention/'
        'exp" source_file="blocks.py"}',
        '  ROOT %fusion.9 = f32[4]{0} fusion(%q), kind=kLoop, '
        'metadata={op_name="jit(paged_decode_step)/while/body/moe/dot"}',
        '  %dot.1 = f32[4]{0} dot(%a, %b), metadata={op_name="jit(f)/'
        'attentions/dot"}',
        '  %copy.2 = f32[4]{0} copy(%a)'])
    assert sp.scope_ops(hlo, "attention") == {"%fusion.3"}
    assert sp.scope_ops(hlo, "moe") == {"%fusion.9"}
    assert sp.module_name("jit_prefill_chunk(1234)") == "jit_prefill_chunk"

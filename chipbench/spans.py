"""The program's own spans (`repro.serving.trace`) and a profiler trace's
named programs, reduced to the numbers that split TTFT and the decode
step.

From the spans of a whole run (`Span` objects, `time.monotonic()`; the
server's clock is that minus `loop_t0`), over the requests or steps of
the window:

    ttft_parts            per request due in the window: scheduler wait
                          (due -> dispatch), prefill queue (dispatch ->
                          start of its first `prefill.chunk`), prefill
                          execution (-> end of its `prefill.first_token`,
                          device contention included) and handoff (-> the
                          first token stamped on the runtime thread);
                          they add up to its TTFT
    decode_turnaround_ms  mean gap between consecutive `decode.step` spans
                          of one instance: the step's post, the runtime
                          thread's finish and joins, the worker's pickup
    host_reads_per_step   `decode.host_reads` counted inside each
                          `decode.step` span, per step

From a trace of a traced window whose host plane holds the spans as
`TraceAnnotation` events (`reduce`): every execution of every program of
the first chip, by name (its `XLA Modules` line), the program's spans,
and the device ops inside each execution.  `split_steps` divides each
`decode.step` span three ways: the decode program's own device time,
prefill programs' device time (on one chip, P/D interference), and the
rest (other small programs and device idle).  An execution belongs to
the step span that holds its midpoint.  `scope_ops` names the ops of a
compiled program that lie under a `jax.named_scope`, from its HLO text.

The device's clock in a trace runs behind the host's by about a
millisecond (0.7-1.4 ms in the traces recorded on a TPU v5e): read
plainly, an execution starts before the host enqueued it.  `reduce`
moves the device's times by the least offset that puts every execution's
start after the start of its host `DoEnqueueProgram` event (matched by
`run_id`); its result says by how much (`clock_offset_s`).
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from chipbench import trace_reduce
from chipbench.stats import percentile

MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"
DECODE_PROGRAMS = ("jit_paged_decode_step", "jit_decode_step",
                   "jit_mixed_step")
PREFILL_PROGRAMS = ("jit_prefill_chunk", "jit_paged_prefill_step")
SPAN_PREFIXES = ("runtime.", "sched.", "prefill.", "decode.")
_MODULE = re.compile(r"^(.*)\(\d+\)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


# ---------------------------------------------------------------------------
# Host spans of a run
# ---------------------------------------------------------------------------

def _of_run(run, spans) -> list:
    """Spans of the window's serve: those started once its loop had."""
    return [s for s in spans if s.start >= run.loop_t0]


def ttft_parts(run, spans) -> Dict[int, Tuple[float, float, float, float]]:
    """Seconds of (scheduler wait, prefill queue, prefill execution,
    handoff) of each request due in the window with a first token and
    its spans."""
    t0 = run.loop_t0
    first_chunk: Dict[int, float] = {}
    first_token: Dict[int, float] = {}
    for s in _of_run(run, spans):
        rid = s.ids.get("rid")
        if s.name == "prefill.chunk":
            first_chunk[rid] = min(s.start, first_chunk.get(rid, s.start))
        elif s.name == "prefill.first_token":
            first_token[rid] = s.end
    out = {}
    for r in run.due_in_window:
        if (r.first_token_time is None or r.dispatch_time is None
                or r.rid not in first_chunk or r.rid not in first_token):
            continue
        chunk, token = first_chunk[r.rid] - t0, first_token[r.rid] - t0
        out[r.rid] = (r.dispatch_time - r.arrival_time,
                      chunk - r.dispatch_time, token - chunk,
                      r.first_token_time - token)
    return out


def prefill_queue_ms_p50(run, spans) -> Optional[float]:
    xs = [q * 1e3 for _w, q, _e, _h in ttft_parts(run, spans).values()]
    return percentile(xs, 50) if xs else None


def prefill_exec_ms_p50(run, spans) -> Optional[float]:
    xs = [e * 1e3 for _w, _q, e, _h in ttft_parts(run, spans).values()]
    return percentile(xs, 50) if xs else None


def _steps_in_window(run, spans) -> list:
    return [s for s in _of_run(run, spans) if s.name == "decode.step"
            and run.in_window(s.end - run.loop_t0)]


def decode_turnaround_ms(run, spans) -> Optional[float]:
    by_inst: Dict[int, list] = {}
    for s in _of_run(run, spans):
        if s.name == "decode.step":
            by_inst.setdefault(s.ids.get("instance"), []).append(s)
    gaps = []
    for steps in by_inst.values():
        steps.sort(key=lambda s: s.start)
        gaps += [(b.start - a.end) * 1e3 for a, b in zip(steps, steps[1:])
                 if run.in_window(a.end - run.loop_t0)]
    return sum(gaps) / len(gaps) if gaps else None


def host_reads_per_step(run, spans) -> Optional[float]:
    steps = _steps_in_window(run, spans)
    if not steps:
        return None
    return sum(s.counts.get("decode.host_reads", 0)
               for s in steps) / len(steps)


# ---------------------------------------------------------------------------
# A trace's programs and spans
# ---------------------------------------------------------------------------

def module_name(event_name: str) -> str:
    """`jit_paged_decode_step` from `jit_paged_decode_step(1234)`."""
    m = _MODULE.match(event_name)
    return m.group(1) if m else event_name


def reduce(trace_dir: str) -> Dict:
    """modules [[name, start_s, end_s]] (each execution on the first
    chip), ops [[execution index, op name, seconds]] (the ops inside
    each execution), spans [[name, start_s, end_s, {id: value}]] (the
    program's host-plane spans), clock_offset_s; times in seconds on the
    host's clock of the trace."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(trace_reduce.find_xplane(trace_dir))
    dev_planes = [p for p in data.planes
                  if p.name.startswith("/device:TPU:")]
    if not dev_planes:
        raise ValueError("the trace holds no TPU device plane")
    first = min(dev_planes, key=lambda p: int(p.name.rsplit(":", 1)[1]))
    modules: List[Tuple[str, float, float, Optional[int]]] = []
    raw_ops: List[Tuple[float, float, str]] = []
    for line in first.lines:
        if line.name == MODULES_LINE:
            modules += [(module_name(ev.name), ev.start_ns / 1e9,
                         (ev.start_ns + ev.duration_ns) / 1e9,
                         dict(ev.stats).get("run_id"))
                        for ev in line.events]
        elif line.name == trace_reduce.OPS_LINE:
            raw_ops += [(ev.start_ns / 1e9, ev.duration_ns / 1e9,
                         ev.name.partition(" = ")[0])
                        for ev in line.events]
    modules.sort(key=lambda m: m[1])
    starts = [m[1] for m in modules]
    ops = []
    for a, dur, name in raw_ops:
        i = bisect.bisect_right(starts, a) - 1
        if i >= 0 and a < modules[i][2]:
            ops.append([i, name, dur])
    spans, enqueued = [], {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIXES):
                    spans.append([ev.name, ev.start_ns / 1e9,
                                  (ev.start_ns + ev.duration_ns) / 1e9,
                                  {k: v for k, v in ev.stats}])
                elif ev.name == ENQUEUE:
                    run = dict(ev.stats).get("run_id")
                    t = ev.start_ns / 1e9
                    enqueued[run] = min(t, enqueued.get(run, t))
    spans.sort(key=lambda s: s[1])
    late = [enqueued[r] - a for _n, a, _b, r in modules if r in enqueued]
    offset = max(late) if late else 0.0
    return {"modules": [[n, a + offset, b + offset]
                        for n, a, b, _r in modules],
            "ops": ops, "spans": spans, "clock_offset_s": offset}


def module_seconds(red: Dict) -> Dict[str, float]:
    """Device seconds of each program, summed over its executions."""
    out: Dict[str, float] = {}
    for name, a, b in red["modules"]:
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def scope_ops(hlo_text: str, scope: str) -> Set[str]:
    """Names (`%fusion.12`) of the instructions of a compiled program's
    HLO text whose op_name metadata passes through `scope`."""
    mark = f"/{scope}/"
    out = set()
    for line in hlo_text.splitlines():
        m = _OP_NAME.search(line)
        if m and mark in m.group(1) + "/":
            out.add(line.strip().removeprefix("ROOT ").partition(" = ")[0])
    return out


def split_steps(red: Dict, scoped: Iterable[str] = ()
                ) -> List[Dict[str, float]]:
    """Each `decode.step` span of the trace: its seconds, the device
    seconds of the decode programs and of the prefill programs whose
    executions it holds (by midpoint), the rest, and the seconds of the
    decode programs' ops named in `scoped`."""
    steps = [s for s in red["spans"] if s[0] == "decode.step"]
    scoped = set(scoped)
    by_exec: Dict[int, float] = {}
    for i, name, dur in red["ops"]:
        if name in scoped:
            by_exec[i] = by_exec.get(i, 0.0) + dur
    out = [{"span_s": b - a, "device_s": 0.0, "blocked_s": 0.0,
            "scoped_s": 0.0} for _n, a, b, _ids in steps]
    ends = [s[2] for s in steps]
    for i, (name, a, b) in enumerate(red["modules"]):
        mid = (a + b) / 2
        k = bisect.bisect_right(ends, mid)
        if k == len(steps) or steps[k][1] > mid:
            continue
        if name in DECODE_PROGRAMS:
            out[k]["device_s"] += b - a
            out[k]["scoped_s"] += by_exec.get(i, 0.0)
        elif name in PREFILL_PROGRAMS:
            out[k]["blocked_s"] += b - a
    for o in out:
        o["rest_s"] = o["span_s"] - o["device_s"] - o["blocked_s"]
    return out


def mean_ms(parts: List[Dict[str, float]], key: str) -> Optional[float]:
    """Per-step mean of one of `split_steps`' parts, in ms:
    `device_s` (decode_device_ms), `blocked_s` (decode_blocked_ms),
    `scoped_s` (decode_attention_ms, with the attention scope's ops)."""
    return (sum(p[key] for p in parts) / len(parts) * 1e3) if parts \
        else None

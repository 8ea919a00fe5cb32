"""One run of one cell: set up, serve open-loop, measure, check, report.

Everything particular to a configuration, a traffic mix or a metric is a
file found by name:

    BENCHMARK.json                      cells and metrics
    chipbench/configs/<config>.json     a configuration: published values,
                                        deployment, check limits
    chipbench/models/<model_type>.py    its program mapping, weights and
                                        plain reference
    chipbench/traffic/<mix>.json        a traffic mix (traffic.py reads it)
    chipbench/metrics/<metric>.py       `read(run)` -> number or None

A run builds the weights on the device from the seed, builds the server
through its public constructor, warms every shape the cell's traffic
uses, serves a lead-in of the traffic, then the measured window and a
short tail, and waits until the window's requests have finished (or the
mix's drain limit has passed).  Every request stamp is on the server's
own realtime clock, so a request is timed from when it was due.  After
the window the server is freed and a sample of the window's finished
requests is compared with the plain reference (check.py).
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback
from typing import Callable, Dict, List, Optional, Tuple

from chipbench import check, flops, traffic

TRACE_S = 3.0           # length of the profiler's window in a traced run
STALL_S = 0.25          # an arrival this overdue marks the runtime thread
STALL_POLL_S = 0.05     # as held; how often the watch looks
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


class NoChip(RuntimeError):
    """The cell's chips are not there; the run prints no result."""


# ---------------------------------------------------------------------------
# Finding things by name
# ---------------------------------------------------------------------------

def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """A cell of BENCHMARK.json with its files read."""
    name: str
    root: str
    conf: Dict
    mix: Dict
    chips: int
    end_to_end: List[Dict]
    per_layer: List[Dict]
    model: object          # chipbench/models/<model_type>.py, loaded


def model_module(root: str, model_type: str):
    return _load_module(os.path.join(root, "chipbench", "models",
                                     f"{model_type}.py"),
                        f"chipbench_model_{model_type}")


def metric_reader(root: str, name: str) -> Callable:
    return _load_module(os.path.join(root, "chipbench", "metrics",
                                     f"{name}.py"),
                        f"chipbench_metric_{name}").read


def load_cell(root: str, workload: str) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf_entry = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    with open(os.path.join(root, conf_entry["file"])) as f:
        conf = json.load(f)
    mix = traffic.load(os.path.join(root, "chipbench", "traffic",
                                    f"{w['traffic']}.json"))

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return Cell(workload, root, conf, mix, int(w["chips"]),
                mine(manifest["end_to_end"]), mine(manifest["per_layer"]),
                model_module(root, conf["model_type"]))


# ---------------------------------------------------------------------------
# What a run records
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What the metric readers see.  Times are seconds on the server's
    clock; `window` is [lead-in, lead-in + seconds)."""
    cell: Cell
    window: Tuple[float, float]
    requests: list                       # every Request offered
    emissions: List[Tuple[float, int, int]]   # (time, rid, context) per
    #                                     decode token
    steps: List[Tuple[float, float, List[int]]]  # (end, seconds, context
    #                                     of each live row) per decode step
    loop_t0: float                       # the server clock's zero
    chips: int
    peaks: Dict                          # peaks.json entry of the device
    param_bytes: int                     # the served weights' bytes
    mix: Dict
    setup_s: float = float("nan")
    trace: Optional[Dict] = None         # trace_reduce.reduce output

    @property
    def seconds(self) -> float:
        return self.window[1] - self.window[0]

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t < self.window[1]

    @property
    def due_in_window(self) -> list:
        return [r for r in self.requests if self.in_window(r.arrival_time)]

    def decode_flops(self, ctx: int) -> float:
        return flops.per_token(self.cell.model, self.cell.conf, ctx)

    def step_least_s(self, ctxs: List[int]) -> float:
        return flops.least_step_s(self.cell.model, self.cell.conf,
                                  self.param_bytes, ctxs, self.peaks)


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------

class CompileCounter:
    """Programs compiled and programs loaded from the persistent cache,
    through JAX's monitoring events: every program's build fires the
    backend-compile duration event, and a cache hit fires its own event
    first, on the same thread."""

    def __init__(self):
        import jax
        self.compiled: List[float] = []     # host-clock time of each
        self.loaded: List[float] = []
        self.seconds = 0.0
        self._tls = threading.local()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_kw):
        if event != "/jax/core/compile/backend_compile_duration":
            return
        self.seconds += duration
        hit = getattr(self._tls, "hit", False)
        self._tls.hit = False
        (self.loaded if hit else self.compiled).append(time.monotonic())

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self._tls.hit = True


def check_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def peaks_of(root: str, device_kind: str) -> Dict:
    with open(os.path.join(root, "chipbench", "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise NoChip(f"no peaks for device kind {device_kind!r} in "
                     f"chipbench/peaks.json")
    return table[device_kind]


def make_key(seed: int):
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                              seed // 2 ** 32)


def build_server(cell: Cell, seed: int):
    """Weights from the seed (one jitted program, the model file's
    `serve_weights` folding what the serving stack lacks) and the server
    built from the cell's deployment through the public constructor."""
    import jax
    from repro.config.base import ServingConfig
    from repro.serving.real_engine import EngineSpec
    from repro.serving.server import RealSBSServer
    conf, dep, model = cell.conf, cell.conf["deployment"], cell.model
    cfg = model.program_config(conf)
    params = jax.jit(lambda k: model.serve_weights(
        conf, model.init_weights(conf, k)))(make_key(seed))
    jax.block_until_ready(params)
    scfg = ServingConfig(
        num_prefill_instances=dep["prefill_instances"],
        prefill_dp_per_instance=dep["prefill_dp"],
        num_decode_instances=1, decode_dp_per_instance=dep["decode_dp"],
        chunk_size=dep["chunk"], t_default=dep["t_default_s"],
        l_net=dep["l_net_s"], max_batch_per_dp=dep["max_batch_per_dp"],
        kv_budget_tokens=dep["max_batch_per_dp"] * dep["max_len"],
        block_size=dep["block_size"],
        decode_slots_per_dp=dep["decode_slots_per_dp"])
    spec = EngineSpec(cfg, params, max_len=dep["max_len"],
                      max_batch=scfg.max_batch_per_dp, max_new=0,
                      block_size=scfg.block_size,
                      decode_slots=scfg.resolved_decode_slots)
    srv = RealSBSServer(cfg, spec.params, serving_cfg=scfg,
                        scheduler=cell.mix["scheduler"],
                        max_len=dep["max_len"], max_new=0, spec=spec)
    return params, srv


def reference_weights(cell: Cell, seed: int):
    """The weights from the seed as the plain reference reads them, made
    anew (after the server is gone) by one jitted program."""
    import jax
    return jax.jit(lambda k: cell.model.init_weights(cell.conf, k))(
        make_key(seed))


def warm_up(cell: Cell, srv) -> None:
    """Compile every shape the window uses: each prefill grant length
    (multiples of the prompt quantum up to the chunk), then a short
    serve of two requests through the same server, which compiles the
    decode step, the P/D join, the logit capture and the small eager
    programs around them."""
    import jax
    import jax.numpy as jnp
    from repro.core.types import Request
    spec, dep = srv.spec, cell.conf["deployment"]
    q = int(cell.mix["prompt_len"].get("quantum", 1))
    lens = list(range(q, dep["chunk"] + 1, q))
    for n in lens:
        # token ids reach the engine as a host list, converted as the
        # prefill engine converts them (a program per length)
        ids = jnp.asarray([list(range(1, n + 1))], jnp.int32)
        out = spec.jit_prefill_chunk(spec.params, ids, spec.request_cache())
        jax.block_until_ready(out)
    reqs = [Request(rid=i, arrival_time=0.01 * i, input_len=n, output_len=4,
                    tokens=tuple(range(1, n + 1)))
            for i, n in enumerate((lens[0], lens[-1] + q))]
    srv.serve(reqs, timeout=600.0)


# ---------------------------------------------------------------------------
# Instrumentation from the benchmark's side
# ---------------------------------------------------------------------------

class Recorder:
    """Wraps the engines' own hooks for one serve: the event loop's start
    (so the window can be placed on the server's clock), every arrival as
    the scheduler takes it (how late the open-loop generator ran), every
    decode token as the runtime thread emits it, and every decode step's
    host-clock duration with the context of each live row."""

    HOOKS = ("bind_loop", "_record_emit", "finish_step")

    def __init__(self, srv, requests_by_rid: Dict[int, object]):
        self.loop_t0: Optional[float] = None
        self.started = threading.Event()
        self.emissions: List[Tuple[float, int, int]] = []
        self.steps: List[Tuple[float, float, List[int]]] = []
        self.late: List[float] = []
        self.late_at: List[float] = []       # server time of each arrival
        self.runtime_thread: Optional[int] = None
        self.watch: Optional[StallWatch] = None
        self._saved = []
        for eng in srv.decode_engines:
            self._wrap(eng, requests_by_rid)
        sched = srv.runtime.psched
        if sched is not None:
            self._save(sched, "on_arrival")
            on_arrival = sched.on_arrival

            def arrival(req, now):
                self.runtime_thread = threading.get_ident()
                self.late.append(now - req.arrival_time)
                self.late_at.append(now)
                return on_arrival(req, now)
            sched.on_arrival = arrival

    def _save(self, obj, name):
        self._saved.append((obj, name, obj.__dict__.get(name)))

    def unwrap(self):
        """Restore the hooks and drop every reference to the server."""
        for obj, name, prev in reversed(self._saved):
            if prev is None:
                obj.__dict__.pop(name, None)
            else:
                setattr(obj, name, prev)
        self._saved = []

    def _wrap(self, eng, by_rid):
        for name in self.HOOKS:
            self._save(eng, name)
        bind, emit, finish = eng.bind_loop, eng._record_emit, eng.finish_step

        def bind_loop(loop):
            self.loop_t0 = loop._t0
            self.started.set()
            bind(loop)

        def record_emit(rid, now):
            r = by_rid[rid]
            self.emissions.append((now, rid, r.input_len + r.generated - 1))
            emit(rid, now)

        def finish_step(now, dp_states):
            dur, _active, _rows = eng.step_samples[-1]
            ctxs = [r.input_len + r.generated
                    for parts in eng._participants.values()
                    for r, _slot in parts]
            self.steps.append((now, dur, ctxs))
            return finish(now, dp_states)

        eng.bind_loop, eng._record_emit = bind_loop, record_emit
        eng.finish_step = finish_step


class StallWatch(threading.Thread):
    """Where the runtime thread is while an arrival is overdue: every
    STALL_POLL_S, if the next due arrival is more than STALL_S late, the
    runtime thread's innermost frames are counted by where they are; and
    the length of every garbage collection.  For the diagnostic lines
    only: no metric reads them."""

    def __init__(self, rec: Recorder, due: List[float]):
        super().__init__(daemon=True, name="chipbench-stallwatch")
        self.rec, self.due = rec, sorted(due)
        self.halt = threading.Event()
        self.stacks: collections.Counter = collections.Counter()
        self.worst = (0.0, 0.0)             # (seconds overdue, server time)
        self.gc_s: List[float] = []
        self._gc_t0 = 0.0

    def _gc(self, phase, _info):
        if phase == "start":
            self._gc_t0 = time.monotonic()
        else:
            self.gc_s.append(time.monotonic() - self._gc_t0)

    def run(self):
        gc.callbacks.append(self._gc)
        try:
            self.rec.started.wait()
            while not self.halt.wait(STALL_POLL_S):
                self._look()
        finally:
            gc.callbacks.remove(self._gc)

    def _look(self):
        rec, n = self.rec, len(self.rec.late)
        if n >= len(self.due) or rec.runtime_thread is None:
            return
        now = time.monotonic() - rec.loop_t0
        over = now - self.due[n]
        if over < STALL_S:
            return
        if over > self.worst[0]:
            self.worst = (over, now)
        frame = sys._current_frames().get(rec.runtime_thread)
        if frame is None:
            return
        inner = traceback.extract_stack(frame)[-4:]
        self.stacks[" < ".join(
            f"{os.path.basename(f.filename)}:{f.lineno} {f.name}"
            for f in reversed(inner))] += 1

    def summary(self) -> str:
        gcs = self.gc_s or [0.0]
        top = "; ".join(f"{k} x{v}" for k, v in self.stacks.most_common(4))
        return (f"overdue over {STALL_S * 1e3:.0f} ms in "
                f"{sum(self.stacks.values())} looks, worst "
                f"{self.worst[0] * 1e3:.1f} ms at t {self.worst[1]:.2f} s; "
                f"runtime thread at: {top or 'none'}; garbage collections "
                f"{len(self.gc_s)}, longest {max(gcs) * 1e3:.1f} ms")


class Tracer(threading.Thread):
    """Starts the profiler a little into the window and stops it
    TRACE_S later, from its own thread while the server runs."""

    def __init__(self, rec: Recorder, lead: float, seconds: float,
                 out_dir: str):
        super().__init__(daemon=True, name="chipbench-tracer")
        self.rec, self.out_dir = rec, out_dir
        self.offset = lead + max(seconds - TRACE_S, 0.0) / 2
        self.length = min(TRACE_S, seconds)
        self.error: Optional[BaseException] = None

    def run(self):
        import jax
        try:
            self.rec.started.wait()
            start = self.rec.loop_t0 + self.offset
            time.sleep(max(start - time.monotonic(), 0.0))
            # no Python tracer: it would slow the host it is measuring
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            time.sleep(self.length)
            jax.profiler.stop_trace()
        except BaseException as e:      # reported by the run's thread
            self.error = e


# ---------------------------------------------------------------------------
# One run, in the pieces the tools reuse
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Session:
    """A process's hold on the chips: the cell, its devices and peaks."""
    cell: Cell
    devices: list
    peaks: Dict
    compiles: CompileCounter
    cache_dir: str


def open_session(root: str, workload: str,
                 require_chip: bool = True) -> Session:
    """Check the chips, then set the process-wide JAX options: the
    persistent compilation cache where JAX_COMPILATION_CACHE_DIR points
    (JAX reads it itself), else at a fixed path in the checkout, with
    every program cached, the sub-second ones too; and the
    configuration's matmul precision as the default of every thread (the
    server runs its forwards on worker threads; a precision context is
    thread-local)."""
    import jax
    cell = load_cell(root, workload)
    if require_chip:
        devices = check_devices(cell.chips)
        peaks = peaks_of(root, devices[0].device_kind)
    else:
        devices = jax.devices()[:cell.chips]
        peaks = {"bf16_flops_per_s": float("nan"),
                 "hbm_bytes_per_s": float("nan")}
    cache_dir = os.environ.get(CACHE_ENV)
    if not cache_dir:
        cache_dir = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_default_matmul_precision",
                      cell.conf["precision"]["matmul"])
    return Session(cell, devices, peaks, CompileCounter(), cache_dir)


def tree_bytes(tree) -> int:
    import jax
    return int(sum(x.nbytes for x in jax.tree.leaves(tree)))


def serve_window(ses: Session, srv, seed: int, seconds: float,
                 param_bytes: int, mix: Optional[Dict] = None,
                 trace_dir: Optional[str] = None
                 ) -> Tuple[Run, Dict[int, list], Recorder]:
    """Serve lead-in + window + tail of the cell's traffic (or of `mix`)
    through `srv`, open-loop; returns the run record, the served tokens
    by rid and the recorder.  With `trace_dir` the profiler traces
    TRACE_S seconds from the middle of the window."""
    from repro.core.types import Request
    cell = ses.cell
    mix = mix or cell.mix
    offered = traffic.generate(mix, seed, seconds, cell.conf["vocab_size"])
    reqs = [Request(rid=a.rid, arrival_time=a.due, input_len=len(a.prompt),
                    output_len=a.output_len, tokens=tuple(a.prompt))
            for a in offered]
    rec = Recorder(srv, {r.rid: r for r in reqs})
    lead = float(mix["lead_in_s"])
    window = (lead, lead + seconds)
    tracer = None
    if trace_dir is not None:
        tracer = Tracer(rec, lead, seconds, trace_dir)
        tracer.start()
    rec.watch = StallWatch(rec, [r.arrival_time for r in reqs])
    rec.watch.start()
    try:
        gens = srv.serve(reqs,
                         timeout=window[1] + float(mix["drain_limit_s"]))
    finally:
        rec.unwrap()
        rec.watch.halt.set()
        rec.started.set()
        rec.watch.join()
    if tracer is not None:
        tracer.join()
        if tracer.error is not None:
            raise tracer.error
    run = Run(cell, window, reqs, rec.emissions, rec.steps, rec.loop_t0,
              cell.chips, ses.peaks, param_bytes, mix)
    return run, {g.rid: g.tokens for g in gens}, rec


def finished(run: Run, tokens: Dict[int, list]) -> list:
    """Requests due in the window that finished with all their tokens."""
    return [r for r in run.due_in_window if r.finish_time is not None
            and len(tokens.get(r.rid, ())) == r.output_len]


def memory_peak(devices, key: str = "peak_bytes_in_use") -> int:
    """The fullest chip's reading of `key` in its memory statistics."""
    return int(max((d.memory_stats() or {}).get(key, 0) for d in devices))


def backlog(run: Run, t: float) -> Dict[str, int]:
    """Requests in the system at `t` (server clock): due and unfinished;
    of those, due without a first token, and with a first token but no
    decode token yet (waiting to join the decode pool)."""
    first_by = {}
    for te, rid, _ctx in run.emissions:
        first_by[rid] = min(te, first_by.get(rid, te))

    def before(x):
        return x is not None and x <= t
    due = [r for r in run.requests
           if r.arrival_time <= t and not before(r.finish_time)]
    return {
        "in_system": len(due),
        "no_first_token": sum(1 for r in due
                              if not before(r.first_token_time)),
        "not_joined": sum(1 for r in due if before(r.first_token_time)
                          and not before(first_by.get(r.rid))),
    }


def read_metrics(result: Run, metrics: List[Dict]) -> Dict:
    vals = {}
    for m in metrics:
        v = metric_reader(result.cell.root, m["name"])(result)
        if v is not None:
            vals[m["name"]] = {"value": v, "unit": m["unit"]}
    return vals


def _ms(xs: List[float]) -> str:
    from chipbench.stats import percentile
    if not xs:
        return "none"
    return (f"p50 {percentile(xs, 50) * 1e3:.3f} ms, max "
            f"{max(xs) * 1e3:.3f} ms over {len(xs)}")


def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        t_process: float, require_chip: bool = True,
        tamper: Optional[Callable] = None, log=sys.stderr) -> Dict:
    """Run one cell and return the result line's object.  `tamper`, for
    the harness's own tests, breaks the server's timed path after it is
    built; `require_chip=False` lets those tests run on the CPU."""
    ses = open_session(root, workload, require_chip)
    cell = ses.cell
    phases = [("imports and devices", time.monotonic())]
    params, srv = build_server(cell, seed)
    param_bytes = tree_bytes(params)
    phases.append(("weights and server", time.monotonic()))
    mem = [("server built", memory_peak(ses.devices, "bytes_in_use"),
            memory_peak(ses.devices))]
    if tamper is not None:
        tamper(srv)
    cap = check.LogitCapture(srv)
    n_built = len(ses.compiles.compiled), len(ses.compiles.loaded)
    warm_up(cell, srv)
    cap.reset()
    phases.append(("warm-up", time.monotonic()))
    warm = (len(ses.compiles.compiled) - n_built[0],
            len(ses.compiles.loaded) - n_built[1])
    mem.append(("warmed up", memory_peak(ses.devices, "bytes_in_use"),
                memory_peak(ses.devices)))
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace \
        else None
    result, tokens, rec = serve_window(ses, srv, seed, seconds, param_bytes,
                                       trace_dir=trace_dir)
    t_window = result.loop_t0 + result.window[0]
    result.setup_s = t_window - t_process
    phases.append(("lead-in", t_window))
    t_end = result.loop_t0 + result.window[1]
    in_window = sum(1 for t in ses.compiles.compiled
                    if t_window <= t < t_end)
    loaded_late = (
        sum(1 for t in ses.compiles.loaded if t < t_window
            and t >= result.loop_t0),
        sum(1 for t in ses.compiles.loaded if t_window <= t < t_end))
    peak_mem = memory_peak(ses.devices)
    due = result.due_in_window
    done = finished(result, tokens)
    t_served = time.monotonic()

    # the server, its caches and the served weights go before the
    # reference runs, on weights of its own; only the captured top-K
    # rows of the sampled requests are kept
    chk = cell.conf["check"]
    sample = check.pick_sample(done, chk["sample_requests"], seed)
    prog = check.program_rows(cap, sample, tokens)
    cap.remove()
    del srv, params, cap
    gc.collect()
    t_ref = time.monotonic()
    ref = check.Reference(cell.model, cell.conf,
                          reference_weights(cell, seed))
    reading = check.compare(ref, sample, tokens, prog,
                            cell.model.logit_factor(cell.conf))
    correct, checks = check.decide(chk, reading)
    t_done = time.monotonic()

    devices = ses.devices
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak_mem}
    out = {"correct": bool(correct), "attempted": len(due),
           "failed": len(due) - len(done)}
    breakdown = None
    if trace:
        from chipbench import trace_reduce
        red = trace_reduce.reduce(trace_dir, len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        result.trace = red
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
    out["metrics"] = read_metrics(
        result, cell.per_layer if trace else cell.end_to_end)
    out["device"] = dev
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks

    queue = {f"{k}_{at}": v for at, t in (("start", result.window[0]),
                                          ("end", result.window[1]))
             for k, v in backlog(result, t).items()}
    print(f"[run] {workload} seed {seed}: {len(result.requests)} offered, "
          f"{len(due)} due in the window, {out['failed']} failed; "
          f"compile cache {ses.cache_dir}", file=log)
    print("[setup] " + ", ".join(
        f"{name} {b - a:.3f} s" for (_n, a), (name, b)
        in zip([("start", t_process)] + phases, phases))
        + f"; setup_s {result.setup_s:.3f}; warm-up compiled {warm[0]}, "
        f"loaded {warm[1]} from the cache; process total "
        f"{len(ses.compiles.compiled)} compiled, "
        f"{len(ses.compiles.loaded)} loaded; {in_window} compiled in the "
        f"window; loaded from the cache in the lead-in {loaded_late[0]}, "
        f"in the window {loaded_late[1]}", file=log)
    worst = max(range(len(rec.late)), key=rec.late.__getitem__, default=None)
    print(f"[generator] arrivals taken after their due time: "
          f"{_ms(rec.late)}" + ("" if worst is None else
                                f", the latest taken at t "
                                f"{rec.late_at[worst]:.2f} s (window "
                                f"{result.window[0]:.0f}-"
                                f"{result.window[1]:.0f} s)"), file=log)
    print(f"[stall] {rec.watch.summary()}", file=log)
    print("[mem] bytes in use, peak: " + "; ".join(
        f"{what} {used} {peak}" for what, used, peak in mem)
        + f"; window closed {peak_mem}", file=log)
    print("[backlog] requests at the window's start and end: " + ", ".join(
        f"{k} {v}" for k, v in queue.items()), file=log)
    print(f"[check] drain and readback {t_ref - t_served:.3f} s, "
          f"reference {t_done - t_ref:.3f} s; sample rids "
          f"{[r.rid for r in sample]}; rows not captured for "
          f"{reading['missing']} requests; per-row gap quantiles "
          f"(50, 90, 99, max) {_quantiles(reading['gaps'])}", file=log)
    for name, c in checks.items():
        lim = {k: v for k, v in c.items() if k != "value"}
        print(f"[check] {name} {c['value']} {lim}", file=log)
    return out


def _quantiles(g) -> List[float]:
    import numpy as np
    if not g.size:
        return []
    return [float(np.percentile(g, q)) for q in (50, 90, 99, 100)]

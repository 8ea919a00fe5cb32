"""Reduce a JAX profiler trace (`.xplane.pb`) to device busy time, idle
gaps and the device operations that took the most time.

Device planes are those named `/device:TPU:<n>`; on each, the line of
XLA operations (`XLA Ops`) gives the intervals in which an operation
ran.  Busy time is the length of the union of those intervals inside the
traced window, averaged over the chips.  The window runs from the end of
the host's `start_trace` call to the last event in the trace.  An idle
gap is a stretch of the first chip's window with no operation running;
each is labelled with the host event that overlaps it most (the thread's
line name and the event name), which says what the host was doing.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
TOP = 10
# control-flow ops span the ops of their bodies, which the line also holds
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r"\s([a-z][a-z0-9_-]*)\(")


def op_name(hlo: str) -> str:
    """`%fusion.3 fusion` from an XLA op event's HLO text."""
    name, _, rest = hlo.partition(" = ")
    m = _OPCODE.search(" " + rest)
    return f"{name} {m.group(1)}" if m else name


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce(trace_dir: str, n_devices: int) -> Dict:
    """busy_s, window_s, device_ops [[name, s]], idle_gaps [[label, s]]."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(trace_dir))
    device_ops: Dict[int, List[Tuple[str, float, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    hi = float("-inf")
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            for ev in line.events:
                a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                hi = max(hi, b)
                if is_dev:
                    if line.name == OPS_LINE:
                        dev = int(plane.name.rsplit(":", 1)[1])
                        device_ops.setdefault(dev, []).append(
                            (op_name(ev.name), a, b))
                elif plane.name.startswith("/host:"):
                    host.append((f"{line.name}:{ev.name}", a, b))
    # event times count from the start of the profiling session; where
    # the host's start_trace call was traced, the window opens at its end
    starts = [b for n, _a, b in host if n.endswith("start_trace")]
    lo = max(starts) if starts else 0.0
    if not device_ops:
        raise ValueError("the trace holds no device operation")
    window = (hi - lo) / 1e9
    busy = 0.0
    per_name: Dict[str, float] = {}
    for dev, ops in device_ops.items():
        iv = _union(_clip([(a, b) for _n, a, b in ops], lo, hi))
        busy += sum(b - a for a, b in iv) / 1e9
        for name, a, b in ops:
            if name.rsplit(" ", 1)[-1] in CONTAINERS:
                continue
            c = _clip([(a, b)], lo, hi)
            if c:
                per_name[name] = per_name.get(name, 0.0) + (
                    c[0][1] - c[0][0]) / 1e9
    devs = max(n_devices, 1)
    top = sorted(per_name.items(), key=lambda kv: -kv[1])[:TOP]
    first = min(device_ops)
    iv = _union(_clip([(a, b) for _n, a, b in device_ops[first]], lo, hi))
    edges = [lo] + [x for ab in iv for x in ab] + [hi]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                   for i in range(0, len(edges) - 1, 2)
                   if edges[i + 1] > edges[i]), reverse=True)[:TOP]
    idle = []
    for length, a, b in gaps:
        best, label = 0.0, "no host event"
        for name, ha, hb in host:
            if hb - ha > 10 * length:     # an enclosing long-lived event
                continue
            ov = min(b, hb) - max(a, ha)
            if ov > best:
                best, label = ov, name
        idle.append([label, length / 1e9])
    return {"busy_s": busy / devs, "window_s": window,
            "device_ops": [[n, s / devs] for n, s in top],
            "idle_gaps": idle}

"""Bring-up check on a TPU: serve full-width granite-moe-1b-a400m through
the real P/D-separated SBS server and check its tokens.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded DP+EP deployment

One chip: builds granite-moe-1b-a400m at its published widths (random
fp32 weights from --seed, made on the device), serves 8 requests under
`sbs-la` and then under `immediate` through `RealSBSServer`, and checks
that every request finished with its full token count and that its tokens
equal a single-request greedy decode of the same weights.

`--chips 4`: serves the same requests through the sharded deployment (a
4-rank data mesh, decode DP = 4, the MoE's explicit all-to-all in every
step) and through the same deployment on one device, checks that both
give the same tokens and that the compiled merged decode step holds an
all-to-all.  It runs no other phase.

Lines before the last are bring-up facts, not benchmark numbers.  The last
line is one JSON object naming the device; it reads `"ok": true` only when
every phase passed.  Without a TPU the script exits 1 before building
anything.  Set JAX_COMPILATION_CACHE_DIR to place the compile cache;
otherwise it is kept in `.jax_cache` in the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ARCH = "granite-moe-1b-a400m"
SCHEDULERS = ("sbs-la", "immediate")
N_REQUESTS = 8


class CompileCounter:
    """Counts XLA compiles (persistent-cache hits included, and counted
    apart) and their seconds through JAX's monitoring events."""

    def __init__(self):
        import jax
        self.compiles, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def line(self) -> str:
        return (f"compiles: {self.compiles} in {self.seconds:.1f}s "
                f"({self.cache_hits} from the persistent cache)")


def _report_serve(name, run, sched):
    reqs = run.requests[sched]
    gens = run.generations[sched]
    print(f"[{name}] {sched}: finished {len(gens)}/{len(reqs)} requests")
    for g in gens:
        print(f"[{name}] {sched}: rid {g.rid} ttft {g.ttft * 1000:.1f} ms "
              f"tokens {len(g.tokens)}")
    if len(gens) != len(reqs):
        raise RuntimeError(f"{sched}: {len(reqs) - len(gens)} requests "
                           f"unfinished")


def one_chip(args) -> None:
    import jax
    from repro.launch.serve import greedy_mismatches, serve_real
    t0 = time.monotonic()
    run = serve_real(ARCH, schedulers=SCHEDULERS, n_requests=N_REQUESTS,
                     seed=args.seed)
    cfg, params = run.cfg, run.params
    leaves = jax.tree.leaves(params)
    print(f"config: {cfg.name} (reduced: {cfg.name.endswith('[reduced]')}) "
          f"layers {cfg.num_layers} d_model {cfg.d_model} experts "
          f"{cfg.moe.num_experts} top-{cfg.moe.top_k} vocab {cfg.vocab_size}")
    print(f"params: {sum(x.nbytes for x in leaves)} bytes, dtypes "
          f"{sorted({str(x.dtype) for x in leaves})}")
    for sched in SCHEDULERS:
        _report_serve("serve", run, sched)
    print(f"serve wall: {time.monotonic() - t0:.1f}s")
    for sched in SCHEDULERS:
        bad = greedy_mismatches(cfg, params, run.requests[sched],
                                run.generations[sched])
        for line in bad:
            print(f"[check] {sched}: {line}")
        if bad:
            raise RuntimeError(f"{sched}: {len(bad)} token mismatches vs "
                               f"the greedy reference")
        n = sum(len(g.tokens) for g in run.generations[sched])
        print(f"[check] {sched}: {n} tokens equal the greedy reference")


def four_chips(args) -> None:
    import jax.numpy as jnp
    from repro.launch.mesh import make_engine_mesh
    from repro.launch.serve import serve_real
    mesh = make_engine_mesh(4)
    sharded = serve_real(ARCH, schedulers=("sbs-la",),
                         n_requests=N_REQUESTS, seed=args.seed, mesh=mesh)
    _report_serve("sharded", sharded, "sbs-la")
    # the same deployment (decode DP = the mesh's data size) on one device
    local = serve_real(ARCH, schedulers=("sbs-la",),
                       n_requests=N_REQUESTS, seed=args.seed,
                       params=sharded.params,
                       decode_dp=sharded.spec.n_dp)
    _report_serve("one-device", local, "sbs-la")
    ts = {g.rid: g.tokens for g in sharded.generations["sbs-la"]}
    t1 = {g.rid: g.tokens for g in local.generations["sbs-la"]}
    diff = sorted(r for r in t1 if ts.get(r) != t1[r])
    if diff or set(ts) != set(t1):
        raise RuntimeError(f"sharded tokens differ from one device for "
                           f"rids {diff or sorted(set(ts) ^ set(t1))}")
    print(f"[check] sharded == one-device tokens for {len(ts)} requests")
    spec = sharded.spec
    cache = spec.merged_paged_cache()
    toks = jnp.zeros((cache["cur"].shape[0], 1), jnp.int32)
    hlo = spec.jit_paged_decode.lower(spec.params, toks, cache).compile(
    ).as_text()
    if "all-to-all" not in hlo:
        raise RuntimeError("merged decode step has no all-to-all")
    print(f"[check] merged decode step: {hlo.count('all-to-all')} "
          f"all-to-all mentions in the compiled HLO; cache sharding "
          f"{cache['cur'].sharding.spec}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX's first device is {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, have "
              f"{len(devices)}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch import compile_cache
    print(f"device: {dev.device_kind} x{len(devices)}; compile cache "
          f"{compile_cache.enable()}")
    # The server runs its forwards on worker threads, and
    # jax.default_matmul_precision is a thread-local context: set the
    # process-wide default so the server and the reference both run
    # fp32 matmuls at full precision.
    jax.config.update("jax_default_matmul_precision", "highest")
    counter = CompileCounter()
    (four_chips if args.chips == 4 else one_chip)(args)
    print(counter.line())
    for i, d in enumerate(devices[:args.chips]):
        stats = d.memory_stats() or {}
        print(f"device {i}: peak_bytes_in_use "
              f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Serving launcher: run the SBS control plane.

Two modes:
  --mode sim   discrete-event cluster simulation at production scale
               (reproduces the paper's §5 numbers; default)
  --mode real  real JAX execution behind the SBS scheduler: the P/D
               deployment of `RealSBSServer` (threaded prefill/decode
               engines, chunked prefill, paged decode cache) at the
               model's published widths; `--reduced` swaps in the small
               preset for CPU runs

    PYTHONPATH=src python -m repro.launch.serve --mode sim \
        --arch deepseek-v3-671b --scheduler sbs --qps 100 --duration 20
    PYTHONPATH=src python -m repro.launch.serve --mode real \
        --arch granite-moe-1b-a400m --scheduler sbs-la --requests 8
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import random
from typing import Dict, List, Sequence

SIM_SCHEDULERS = ("sbs", "immediate-rr", "immediate-lt")
REAL_SCHEDULERS = ("sbs", "sbs-la", "immediate")

# Prompt lengths are multiples of 32 and the prefill chunk is 64 tokens, so
# every chunk a pass runs holds 32 or 64 tokens: two prefill shapes compile,
# however the scheduler splits the prompts.
PROMPT_LENS = (32, 64, 96, 128)
CHUNK = 64
BLOCK_SIZE = 16
MAX_LEN = 256


@dataclasses.dataclass
class RealRun:
    """What `serve_real` built and served: the model, the shared engine
    spec, and per scheduler the server, its requests and its
    generations (sorted by rid; unfinished requests are absent)."""
    cfg: object
    params: object
    spec: object
    servers: Dict[str, object]
    requests: Dict[str, List[object]]
    generations: Dict[str, List[object]]


def make_requests(cfg, n: int, seed: int, output_len: int):
    """`n` requests 50 ms apart, with prompt lengths cycling through
    PROMPT_LENS and prompt tokens drawn from `seed`.  Fresh objects per
    call: a serve mutates its requests."""
    from repro.core.types import Request
    rng = random.Random(seed)
    out = []
    for i in range(n):
        L = PROMPT_LENS[i % len(PROMPT_LENS)]
        out.append(Request(
            rid=i, arrival_time=i * 0.05, input_len=L,
            output_len=output_len,
            tokens=tuple(rng.randrange(cfg.vocab_size) for _ in range(L))))
    return out


def serve_real(arch: str, *, reduced: bool = False,
               schedulers: Sequence[str] = ("sbs-la",), n_requests: int = 8,
               seed: int = 0, output_len: int = 16, params=None, mesh=None,
               decode_dp: int | None = None,
               timeout: float = 600.0) -> RealRun:
    """Serve the same `n_requests` through `RealSBSServer` once per
    scheduler, all over one model and one `EngineSpec` (each jitted
    shape compiles once).  With `mesh`, the deployment is the sharded
    DP+EP one: its decode instance has one DP unit per rank of the
    mesh's data axis.  `decode_dp` sets the decode instance's DP units
    (default: the mesh's data size, else the server's default), so the
    same deployment can be served with and without a mesh.  Without
    `params`, random fp32 weights are made from `seed` by one jitted
    program on the default device."""
    import jax
    from repro.config.base import get_arch
    from repro.models import init_params
    from repro.serving.real_engine import EngineSpec
    from repro.serving.server import RealSBSServer, _default_serving_config
    for s in schedulers:
        if s not in REAL_SCHEDULERS:
            raise ValueError(f"scheduler {s!r} not in {REAL_SCHEDULERS}")
    cfg = get_arch(arch, reduced=reduced)
    if params is None:
        params = jax.jit(functools.partial(init_params, cfg))(
            jax.random.PRNGKey(seed))
    # the server's default P/D topology, with the paged cache
    scfg = dataclasses.replace(_default_serving_config(),
                               block_size=BLOCK_SIZE, chunk_size=CHUNK)
    if decode_dp is None and mesh is not None:
        decode_dp = dict(mesh.shape)["data"]
    if decode_dp is not None:
        scfg = dataclasses.replace(scfg, decode_dp_per_instance=decode_dp)
    spec = EngineSpec(cfg, params, max_len=MAX_LEN,
                      max_batch=scfg.max_batch_per_dp, max_new=output_len,
                      block_size=scfg.block_size,
                      decode_slots=scfg.resolved_decode_slots, mesh=mesh)
    run = RealRun(cfg, params, spec, {}, {}, {})
    for s in schedulers:
        srv = RealSBSServer(cfg, params, serving_cfg=scfg, scheduler=s,
                            max_len=MAX_LEN, max_new=output_len, spec=spec)
        reqs = make_requests(cfg, n_requests, seed, output_len)
        run.servers[s] = srv
        run.requests[s] = reqs
        run.generations[s] = srv.serve(reqs, timeout=timeout)
    return run


def greedy_mismatches(cfg, params, requests, generations,
                      max_len: int = MAX_LEN) -> List[str]:
    """Check served tokens against a plain single-request greedy decode
    (`prefill` + `decode_step`) of the same params.  The reference is fed
    the served tokens, so every position is checked even after a first
    mismatch; a served token equal to the reference argmax everywhere is
    exactly the reference's own greedy output.  Returns one line per
    mismatching position (empty = all equal); a missing or short
    generation is a mismatch too."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import decode_step, prefill
    jit_prefill = jax.jit(
        lambda p, t: prefill(cfg, p, t, max_len=max_len))
    jit_decode = jax.jit(lambda p, t, c: decode_step(cfg, p, t, c))
    got = {g.rid: g.tokens for g in generations}
    bad: List[str] = []
    for r in requests:
        toks = got.get(r.rid)
        if toks is None or len(toks) != r.output_len:
            bad.append(f"rid {r.rid}: {0 if toks is None else len(toks)} "
                       f"of {r.output_len} tokens")
            continue
        logits, cache = jit_prefill(
            params, jnp.asarray([r.tokens[:r.input_len]], jnp.int32))
        for i, tok in enumerate(toks):
            if i:
                logits, cache = jit_decode(
                    params, jnp.asarray([[toks[i - 1]]], jnp.int32), cache)
            row = np.asarray(logits[0])
            ref = int(row.argmax())
            if ref != tok:
                top2 = np.sort(row)[-2:]
                bad.append(f"rid {r.rid} pos {i}: served {tok}, reference "
                           f"{ref} (reference top-2 margin "
                           f"{float(top2[1] - top2[0]):.3g}, served-token "
                           f"gap {float(row[ref] - row[tok]):.3g})")
    return bad


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="sim", choices=["sim", "real"])
    ap.add_argument("--arch", default=None,
                    help="default: deepseek-v3-671b (sim), "
                         "granite-moe-1b-a400m (real)")
    ap.add_argument("--scheduler", default="sbs",
                    choices=sorted(set(SIM_SCHEDULERS + REAL_SCHEDULERS)))
    ap.add_argument("--reduced", action="store_true",
                    help="real mode: the small preset of --arch (CPU runs)")
    ap.add_argument("--workload", default="short",
                    choices=["short", "long", "decode"])
    ap.add_argument("--qps", type=float, default=100.0)
    ap.add_argument("--duration", type=float, default=20.0)
    ap.add_argument("--chunk", type=int, default=3072)
    ap.add_argument("--prefill-instances", type=int, default=3)
    ap.add_argument("--dp-per-instance", type=int, default=8)
    ap.add_argument("--cache-aware", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    valid = SIM_SCHEDULERS if args.mode == "sim" else REAL_SCHEDULERS
    if args.scheduler not in valid:
        ap.error(f"--mode {args.mode} takes --scheduler in {valid}")

    if args.mode == "sim":
        from repro.config.base import ServingConfig, get_arch
        from repro.serving.cluster import PrefillClusterSim
        from repro.serving.workload import SPECS, generate
        cfg = get_arch(args.arch or "deepseek-v3-671b")
        scfg = ServingConfig(
            num_prefill_instances=args.prefill_instances,
            prefill_dp_per_instance=args.dp_per_instance,
            chunk_size=args.chunk, cache_aware=args.cache_aware,
            t_default=0.1)
        reqs = generate(SPECS[args.workload], qps=args.qps,
                        duration=args.duration, seed=args.seed,
                        with_tokens=args.cache_aware,
                        shared_prefix_prob=0.5 if args.cache_aware else 0.0)
        sim = PrefillClusterSim(cfg, scfg, scheduler=args.scheduler)
        rep = sim.run(reqs, args.duration)
        print(f"{args.scheduler} @ {args.qps} qps: {rep.row()}")
        return

    from repro.launch import compile_cache
    compile_cache.enable()
    run = serve_real(args.arch or "granite-moe-1b-a400m",
                     reduced=args.reduced, schedulers=(args.scheduler,),
                     n_requests=args.requests, seed=args.seed)
    gens = run.generations[args.scheduler]
    for g in gens:
        print(f"rid={g.rid} ttft={g.ttft*1000:7.1f}ms tokens={g.tokens}")
    srv = run.servers[args.scheduler]
    print(f"{run.cfg.name}: served {len(gens)}/{args.requests}; "
          f"adapted I_opt={srv.state.interval.interval*1000:.1f}ms")


if __name__ == "__main__":
    main()

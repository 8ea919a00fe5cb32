"""Real JAX engine backends for the EnginePlane contract.

`RealPrefillEngine` / `RealDecodeEngine` plug into `ClusterRuntime`
exactly where `SimPrefillInstance` / `SimDecodeInstance` do — same
scheduler feedback, same DecodeDPState accounting — but every pass/step
is an actual jitted model forward executed on the engine's worker
thread.  The runtime runs in realtime mode (wall clock): `start_pass` /
`start_step` return the ASYNC sentinel, and the worker posts the
matching `pass_end` / `step_end` completion to the runtime's event loop.

Prefill is TRUE chunked prefill with two cache backends:

  dense (default)        each granted (request, tokens) slice extends the
      request's private batch-1 KV cache via `prefill_chunk`; completion
      publishes the whole cache on the `KVHandoffBus`.
  page-native (opt-in)   chunks write DIRECTLY into `BlockPool` pages via
      `paged_prefill_step` — no batch-1 staging cache exists.  With
      `share_prefix`, a `PagePrefixBinder` resolves each new prompt's
      longest cached prefix to live pages at enqueue time, so those
      chunks are never computed (an exact full-prompt hit skips prefill
      entirely and replays the stored first token).  Completion gathers
      only the pages the request holds (`paged_gather_blocks`) into a
      `PageHandoff` — the handoff-realization copy of the dense path is
      gone, and the payload is sized by the prompt, not max_len.

When the prompt completes, the first output token (argmax of the
last-chunk logits) plus the cache/handoff are published on the
`KVHandoffBus` — the paper's P/D KV-cache transfer, priced by
`transfer_time` on the runtime heap and physically realised at join
time.

Decode is CONTINUOUS BATCHED decode with two cache backends behind one
engine:

  padded (block_size=0)  each DP owns a `max_batch`-slot dense cache
      (`init_cache`); a free SLOT is the admission token.
  paged  (block_size>0)  each DP owns a shared `BlockPool` + block-table
      cache (`init_paged_cache`); admission is by free-BLOCK count — a
      request's lifetime pages are reserved at join and returned at
      leave/drain, so the same KV memory budget sustains far more
      concurrent short requests than max_len-padded slots would.

Handed-off requests JOIN by `cache_join`/`paged_cache_join` into a free
slot (a `PageHandoff` joins by `paged_adopt_blocks`: shared prefix pages
already resident on the DP are pointed at, not copied), every step runs
one batched `decode_step`/`paged_decode_step` per occupied DP behind the
instance sync barrier, and finished requests LEAVE by freeing their slot
(paged: also dropping their table row and returning their blocks).  A
decode DP with `share_prefix` publishes each joined prompt's pages into
its own binder and COPY-ON-WRITES the partial tail block EAGERLY at join
— the request's very first decode write would land in the now-shared
block, so the divergence point is known and the copy happens while no
step is in flight.  All scheduler state mutation happens on the runtime
thread (enqueue/finish_pass/start_step/finish_step); worker threads only
execute JAX computations on snapshots — device caches are never mutated
while a pass/step is in flight.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import queue
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig
from repro.core.types import DispatchCommand, Request, RequestPhase
from repro.models.model import (
    _require_pageable_prefill, cache_join, cache_take, decode_step,
    init_cache, init_paged_cache, mixed_step, paged_adopt_blocks,
    paged_cache_clear_slot, paged_cache_join, paged_cache_take,
    paged_clear_rows, paged_copy_block, paged_decode_step,
    paged_gather_blocks, paged_layout, paged_prefill_step, prefill_chunk,
)
from repro.serving import trace
from repro.serving.engine import SimDecodeInstance, SimPrefillInstance
from repro.serving.kv_pool import BlockPool, pad_block_table
from repro.serving.page_share import PagePrefixBinder
from repro.serving.plane import ASYNC, PassResult, StartResult, UnifiedEngine


# ---------------------------------------------------------------------------
# Shared engine spec + KV handoff bus
# ---------------------------------------------------------------------------


def _with_cfg(fn, cfg):
    """`fn` with the model config bound as its first argument, under
    `fn`'s own name: `jax.jit` names the program after it.  (A
    `functools.partial` would be lowered as `jit__unknown`.)"""
    def call(*args):
        return fn(cfg, *args)
    call.__name__ = call.__qualname__ = fn.__name__
    return call


class _LockedJit:
    """A jitted callable serialized behind the deployment's mesh lock.

    One mesh is ONE shared device set, and XLA's CPU collective
    rendezvous deadlocks when two multi-device programs interleave their
    per-device participant launches (e.g. the prefill worker's chunk
    all-reduce racing the decode worker's step all-to-all — both wait
    forever for participants the other program's threads are holding).
    So on the sharded plane every program runs exclusively: take the
    lock, launch, block until the result is materialized, release.  This
    is also physically honest — concurrent engines CONTEND for the one
    mesh the way they would for one accelerator.

    `lower()` forwards to the underlying jit so HLO probes
    (`spec.jit_paged_decode.lower(...).compile().as_text()`) still work.
    """

    def __init__(self, fn, lock):
        self._fn, self._lock = fn, lock

    def __call__(self, *args, **kwargs):
        with self._lock:
            out = self._fn(*args, **kwargs)
            jax.block_until_ready(out)
            return out

    def lower(self, *args, **kwargs):
        return self._fn.lower(*args, **kwargs)


@dataclasses.dataclass
class EngineSpec:
    """Model + jit context shared by every engine of one deployment, so
    each (chunk-shape, batch-shape) compiles exactly once per process
    instead of once per engine instance.

    `max_batch` doubles as the decode-plane MEMORY budget: the padded
    plane allocates max_batch slots of max_len tokens per DP; the paged
    plane (block_size > 0) spends the SAME token budget on a shared
    `BlockPool` of max_batch·max_len/block_size blocks, with
    `decode_slots` (default 2×max_batch) cheap batch rows on top — so a
    paged DP admits by free-block count and sustains more concurrent
    requests than the padded DP at equal memory.

    With a `mesh` (sharded plane, paged only) the spec becomes
    MESH-NATIVE: params are device_put with `distributed.sharding`
    pspecs, every paged step jit is wrapped in
    `annotate.activate(mesh, axis_map, ep_shard_map=True)` so MoE layers
    take the explicit all-to-all EP path of `models.moe_ep`, and output
    caches are pinned to `paged_cache_pspecs` layouts.  A decode
    instance then merges its DP units' rows into ONE cache sharded over
    the mesh's "data" axis — each step is a genuine cross-DP
    synchronized program, which is where the paper's sync barrier
    physically lives on this plane."""
    cfg: ModelConfig
    params: Any
    max_len: int = 256
    max_batch: int = 8          # decode slots per DP unit (= memory budget)
    max_new: int = 0            # 0 = no cap on generated tokens
    block_size: int = 0         # paged KV block size (0 = padded slots)
    decode_slots: int = 0       # paged batch rows per DP (0 = 2×max_batch)
    pool_blocks: int = 0        # physical blocks per DP (0 = equal-memory)
    prefill_slots: int = 0      # page-native prefill rows (0 = auto)
    prefill_pool_blocks: int = 0  # page-native prefill pool (0 = auto)
    mesh: Any = None            # jax.sharding.Mesh -> sharded engines
    parallel: Any = None        # ParallelConfig (None = EP over the mesh)

    def __post_init__(self):
        cfg = self.cfg
        # every program is jitted under its model function's name, so a
        # profiler trace names it (`jit_paged_decode_step`, ...)
        self.jit_prefill_chunk = jax.jit(_with_cfg(prefill_chunk, cfg))
        self.jit_decode = jax.jit(_with_cfg(decode_step, cfg))
        self.jit_join = jax.jit(cache_join)
        if self.block_size:
            self.nbt, _ = paged_layout(cfg, self.max_len, self.block_size)
            self.jit_paged_decode = jax.jit(
                _with_cfg(paged_decode_step, cfg))
            self.jit_paged_join = jax.jit(_with_cfg(paged_cache_join, cfg))
            # page-native prefill + block-granular handoff (one jitted
            # shape each: tables/masks are padded to nbt width, slot and
            # block ids are traced scalars)
            self.jit_paged_prefill = jax.jit(
                _with_cfg(paged_prefill_step, cfg))
            self.jit_gather_blocks = jax.jit(
                _with_cfg(paged_gather_blocks, cfg))
            self.jit_adopt_blocks = jax.jit(
                _with_cfg(paged_adopt_blocks, cfg))
            self.jit_copy_block = jax.jit(_with_cfg(paged_copy_block, cfg))
            self.jit_clear_rows = jax.jit(paged_clear_rows)
            # unified mixed-batch step (decode rows + piggybacked prefill
            # chunks in one XLA program): retraces per (n_chunks, chunk
            # lengths) combination — slots and masks are traced
            self.jit_mixed = jax.jit(_with_cfg(mixed_step, cfg))
        self.n_dp = 1
        self.axis_map = None
        self._mesh_lock = threading.RLock()
        self._cache_builders: Dict = {}   # (slots, blocks) -> jitted init
        if self.mesh is not None:
            self._init_sharded()

    def _init_sharded(self) -> None:
        """Turn the paged step jits into MESH programs.

        Parameters are committed once with `param_pspecs` layouts; each
        step fn is re-wrapped so (a) `annotate.activate(..,
        ep_shard_map=True)` is live at trace time — MoE layers route
        through `moe_block_ep`'s explicit all-to-all whenever the token
        count divides the device count — and (b) the output cache is
        pinned to its `paged_cache_pspecs` layout, computed from the
        TRACED shapes so the same wrapper serves the merged decode
        cache, the prefill cache, and any dry-run geometry."""
        if not self.block_size:
            raise ValueError(
                "sharded engines are paged-only (set block_size > 0)")
        import numpy as np
        from repro.config.base import ParallelConfig
        from repro.distributed import annotate
        from repro.distributed.sharding import (
            data_axes_of, named, paged_cache_pspecs, param_pspecs)
        cfg, mesh = self.cfg, self.mesh
        if self.parallel is None:
            # EP over the WHOLE mesh when the expert count divides it
            # (launch/dryrun's default_parallel rule) — on a data×1
            # engine mesh this is what makes every decode step carry a
            # cross-DP all-to-all
            par = ParallelConfig()
            mc = getattr(cfg, "moe", None)
            E = mc.num_experts if mc is not None else 0
            for cand in (("data", "model"), ("model",)):
                n = int(np.prod([dict(mesh.shape).get(a, 1) for a in cand]))
                if E and E % n == 0:
                    par = dataclasses.replace(par, expert_axes=cand)
                    break
            self.parallel = par
        par = self.parallel
        self.n_dp = int(dict(mesh.shape)["data"])
        model_size = int(dict(mesh.shape).get(par.model_axis, 1))
        heads_ok = cfg.num_heads == 0 or cfg.num_heads % model_size == 0
        self.axis_map = {
            "tokens": data_axes_of(mesh, par),
            "experts": tuple(a for a in par.expert_axes
                             if a in mesh.axis_names),
            "model": par.model_axis,
            "attn_seq": None if heads_ok else par.model_axis,
        }
        self.params = jax.device_put(
            self.params, named(mesh, param_pspecs(cfg, mesh, par,
                                                  self.params)))

        def sharded(fn):
            @functools.wraps(fn)
            def wrapped(p, t, c, *rest):
                with annotate.activate(mesh, self.axis_map,
                                       ep_shard_map=True):
                    out = fn(p, t, c, *rest)
                cspec = named(mesh, paged_cache_pspecs(cfg, mesh, par,
                                                       out[-1]))
                return out[:-1] + (
                    jax.lax.with_sharding_constraint(out[-1], cspec),)
            return jax.jit(wrapped)

        self.jit_paged_decode = sharded(_with_cfg(paged_decode_step, cfg))
        self.jit_paged_prefill = sharded(_with_cfg(paged_prefill_step, cfg))
        self.jit_mixed = sharded(_with_cfg(mixed_step, cfg))
        # EVERY jit becomes a mesh program once params are sharded (the
        # dense-path prefill chunk carries an all-reduce over the
        # data-sharded expert weights, joins/gathers reshard sharded
        # caches) — funnel them all through the mesh lock so no two
        # multi-device programs ever interleave (see _LockedJit)
        for name in ("jit_prefill_chunk", "jit_decode", "jit_join",
                     "jit_paged_decode", "jit_paged_join",
                     "jit_paged_prefill", "jit_gather_blocks",
                     "jit_adopt_blocks", "jit_copy_block",
                     "jit_clear_rows", "jit_mixed"):
            setattr(self, name,
                    _LockedJit(getattr(self, name), self._mesh_lock))

    @property
    def sharded(self) -> bool:
        return self.mesh is not None

    def device_lock(self):
        """The deployment's mesh lock (a no-op context when unsharded).
        Engine code holding it may nest jitted calls freely (RLock)."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return self._mesh_lock

    def run_eager(self, fn, *args):
        """Run an EAGER (unjitted) op over sharded arrays under the mesh
        lock, blocking until the result is materialized — eager dispatch
        is async too, so without the barrier its device program could
        still be in flight when the next step's collectives launch.
        Plain passthrough on unsharded specs."""
        if self.mesh is None:
            return fn(*args)
        with self._mesh_lock:
            out = fn(*args)
            jax.block_until_ready(out)
        return out

    def new_paged_cache(self, slots: int, num_blocks: int) -> Dict:
        """A fresh paged cache; on a mesh it is built by one jitted
        program straight into its `paged_cache_pspecs` layout, so no
        device ever holds the whole of it.  That program is jitted once
        per cache shape and reused."""
        make = functools.partial(init_paged_cache, self.cfg, slots,
                                 num_blocks, self.max_len, self.block_size)
        if self.mesh is None:
            return make()
        build = self._cache_builders.get((slots, num_blocks))
        if build is None:
            from repro.distributed.sharding import named, paged_cache_pspecs
            layout = named(self.mesh, paged_cache_pspecs(
                self.cfg, self.mesh, self.parallel, jax.eval_shape(make)))
            build = jax.jit(make, out_shardings=layout)
            self._cache_builders[(slots, num_blocks)] = build
        return build()

    def merged_paged_cache(self) -> Dict:
        """ONE instance-wide decode cache holding every DP unit's rows
        (sharded plane): slot s belongs to DP s // paged_slots, physical
        block b to DP b // paged_pool_blocks — matching the per-DP
        `BlockPool(base=...)` allocators — so both pool dims shard over
        the mesh's data axis and DP d's rows live on mesh rank d."""
        n = self.n_dp
        return self.new_paged_cache(n * self.paged_slots,
                                    n * self.paged_pool_blocks)

    @property
    def paged(self) -> bool:
        return self.block_size > 0

    @property
    def paged_slots(self) -> int:
        from repro.config.base import PAGED_SLOTS_FACTOR
        return self.decode_slots or self.max_batch * PAGED_SLOTS_FACTOR

    @property
    def paged_pool_blocks(self) -> int:
        """Physical blocks per DP; default matches the padded plane's
        token capacity exactly (+1 for the reserved null block)."""
        if self.pool_blocks:
            return self.pool_blocks
        return self.max_batch * self.max_len // self.block_size + 1

    @property
    def prefix_sharable(self) -> bool:
        """Page sharing needs every cached layer to live in pool pages —
        attention-only decoder-only configs (SSM/encoder state has no
        page representation)."""
        if not self.paged:
            return False
        try:
            _require_pageable_prefill(self.cfg)
        except ValueError:
            return False
        return True

    @property
    def paged_prefill_slots(self) -> int:
        """Concurrent in-flight prompts per page-native prefill engine."""
        return self.prefill_slots or max(8, 2 * self.max_batch)

    @property
    def paged_prefill_blocks(self) -> int:
        """Prefill-pool size: 2× the slot working set, so completed pages
        can stay resident in the prefix cache while fresh prompts stage."""
        if self.prefill_pool_blocks:
            return self.prefill_pool_blocks
        per_slot = self.max_len // self.block_size
        return 2 * self.paged_prefill_slots * per_slot + 1

    def request_cache(self) -> Dict:
        return init_cache(self.cfg, 1, self.max_len)

    def batch_cache(self) -> Dict:
        return init_cache(self.cfg, self.max_batch, self.max_len)

    def paged_cache(self) -> Dict:
        return self.new_paged_cache(self.paged_slots, self.paged_pool_blocks)

    def prefill_paged_cache(self) -> Dict:
        return self.new_paged_cache(self.paged_prefill_slots,
                                    self.paged_prefill_blocks)

    def target_len(self, req: Request) -> int:
        if self.max_new:
            return min(req.output_len, self.max_new)
        return req.output_len

    def lifetime_tokens(self, req: Request) -> int:
        """KV tokens resident when `req` finishes: the prompt plus one
        written KV entry per decode step (the final sampled token never
        enters the cache)."""
        return req.input_len + max(self.target_len(req) - 1, 0)


@dataclasses.dataclass
class PageHandoff:
    """Block-granular prefill→decode KV payload (`paged_gather_blocks`
    output, nbt-padded): only the pages the prompt actually occupies
    travel, not a max_len dense cache.  `n_tokens` is the prompt length
    the payload covers (payload row i holds tokens [i·bs, (i+1)·bs))."""
    payload: Dict
    n_tokens: int


@dataclasses.dataclass
class GenState:
    """Per-request generation context carried across the P/D handoff.
    `cache` is a dense batch-1 cache (dense prefill / drain re-park) or a
    `PageHandoff` (page-native prefill); None while resident."""
    rid: int
    cache: Optional[Any]        # parked KV payload (None while resident)
    tokens: List[int]


class KVHandoffBus:
    """Prefill → decode KV-cache handoff registry (one per deployment).

    The prefill plane publishes a finished request's cache + first token;
    the decode plane takes the cache at join time.  A drained (watchdog)
    decode instance re-parks its residents' caches here so re-dispatch
    lands them on a healthy instance with generation state intact.  All
    access happens on the runtime thread."""

    def __init__(self):
        self._gens: Dict[int, GenState] = {}

    def publish(self, rid: int, cache: Any, first_token: int) -> GenState:
        gen = GenState(rid=rid, cache=cache, tokens=[first_token])
        self._gens[rid] = gen
        return gen

    def gen(self, rid: int) -> GenState:
        return self._gens[rid]

    def get(self, rid: int) -> Optional[GenState]:
        return self._gens.get(rid)


class _Worker(threading.Thread):
    """One serial job executor per engine (the engine's 'device')."""

    def __init__(self, name: str):
        super().__init__(daemon=True, name=name)
        self.jobs: "queue.Queue[Optional[Any]]" = queue.Queue()

    def submit(self, job) -> None:
        self.jobs.put(job)

    def stop(self) -> None:
        self.jobs.put(None)

    def run(self) -> None:
        while True:
            job = self.jobs.get()
            if job is None:
                return
            job()


class _WorkerOwner:
    """start/stop lifecycle shared by the real engines.  Each start()
    spawns a fresh worker thread, so a server can serve() repeatedly
    after a COMPLETED run (after a timeout the deployment may hold
    in-flight passes and is not reusable).  A worker-thread exception is
    parked in `_error` and re-raised on the runtime thread by the next
    start/finish call, so a failed forward surfaces immediately instead
    of blocking the loop until its horizon."""

    def __init__(self, tag: str):
        self._tag = tag
        self._worker: Optional[_Worker] = None
        self._error: Optional[BaseException] = None

    def start(self) -> None:
        self._worker = _Worker(self._tag)
        self._worker.start()

    def stop(self) -> None:
        if self._worker is not None:
            self._worker.stop()

    def join_worker(self, timeout: float = 10.0) -> None:
        if self._worker is not None:
            self._worker.join(timeout=timeout)
            self._worker = None

    def _raise_worker_error(self) -> None:
        if self._error is not None:
            err, self._error = self._error, None
            raise err


# ---------------------------------------------------------------------------
# Real prefill
# ---------------------------------------------------------------------------


class _PrefillCtx:
    """Model-side state of one in-flight prefill (batch-1 chunked cache)."""

    def __init__(self, spec: EngineSpec):
        self.cache = spec.request_cache()
        self.consumed = 0
        self.first_token: Optional[int] = None


class _PagedPrefillCtx:
    """Model-side state of one PAGE-NATIVE prefill: a row of the
    engine-shared paged cache plus the request's block table.  `slot` is
    None until the engine stages the request (allocates its fresh blocks
    and installs its table row); a full-prefix cache hit never stages —
    `consumed` starts at `input_len` and the stored first token replays."""

    def __init__(self, table: List[int], claimed: int,
                 first_token: Optional[int] = None):
        self.slot: Optional[int] = None
        self.table = table          # physical blocks (claimed prefix first)
        self.claimed = claimed      # prefix tokens resolved from cache
        self.consumed = claimed     # prompt tokens whose KV is in pages
        self.first_token = first_token


class RealPrefillEngine(SimPrefillInstance, _WorkerOwner):
    """Chunked-prefill engine: scheduler-side queueing/batch-forming and
    EndForward bookkeeping are inherited from the simulated instance —
    only the pass execution differs (jitted `prefill_chunk` on the worker
    thread instead of a cost-model duration).

    With `page_native=True` chunks write straight into `BlockPool` pages
    (`paged_prefill_step`), and `share_prefix=True` adds a
    `PagePrefixBinder`: at enqueue time a new prompt's longest cached
    prefix is CLAIMED (refcounted pages, no copy) so its chunks are never
    computed.  The claim must equal the hit the scheduler credited — both
    sides resolve against the same binder in the same runtime-thread tick
    (see `page_share.EngineBackedPrefixIndex`), so a mismatch is a wiring
    bug and raises."""

    def __init__(self, instance_id: int, dp_ids: Sequence[int], chunk: int,
                 spec: EngineSpec, bus: KVHandoffBus,
                 page_native: bool = False, share_prefix: bool = False,
                 cache_budget_tokens: Optional[int] = None):
        super().__init__(instance_id, dp_ids, chunk, cost=None)
        _WorkerOwner.__init__(self, f"prefill-{instance_id}")
        self.spec = spec
        self.bus = bus
        self._post = None
        self._ctx: Dict[int, _PrefillCtx] = {}
        self.page_native = bool(page_native)
        self.binder: Optional[PagePrefixBinder] = None
        if self.page_native:
            if not spec.prefix_sharable:
                raise ValueError(
                    "page_native prefill needs block_size > 0 and an "
                    "attention-only decoder-only config")
            self.pool = BlockPool(spec.paged_prefill_blocks, spec.block_size)
            self.cache = spec.prefill_paged_cache()
            if share_prefix:
                self.binder = PagePrefixBinder(
                    self.pool, budget_tokens=cache_budget_tokens)
            self._free_slots: List[int] = list(
                range(spec.paged_prefill_slots))
            self._pctx: Dict[int, _PagedPrefillCtx] = {}
        elif share_prefix:
            raise ValueError("share_prefix requires page_native=True")
        # page-native stats (read after the run; only the worker writes
        # chunks_run, only the runtime thread writes the claim counters)
        self.chunks_run = 0
        self.full_hits = 0

    # -- lifecycle -------------------------------------------------------
    def bind_loop(self, loop) -> None:
        self._post = loop.post

    # -- EnginePlane -----------------------------------------------------
    def enqueue(self, cmd: DispatchCommand, now: float) -> None:
        if self.page_native:
            for dp_id, lst in cmd.assignments.items():
                for req, tok in lst:
                    if req.rid not in self._pctx:
                        self._claim_prefix(req, tok)
        super().enqueue(cmd, now)

    def _claim_prefix(self, req: Request, tok: int) -> None:
        """First sight of a request: resolve its cached prefix to pages.
        The scheduler already credited `expected` hit tokens (it granted
        `tok` now and debited `remaining_prefill` by grant + hit), so the
        engine-side claim must match exactly — the claimed chunks will
        never be granted again."""
        expected = req.input_len - req.remaining_prefill - tok
        toks = (req.tokens or ())[:req.input_len]
        if self.binder is not None and toks:
            claim, blocks, first = self.binder.claim(toks)
            self.binder.record(claim, req.input_len)
        else:
            claim, blocks, first = 0, [], None
        if claim != expected:
            raise RuntimeError(
                f"request {req.rid}: scheduler credited a {expected}-token "
                f"prefix hit but the engine binder resolved {claim} — "
                f"cache-aware dispatch on the real plane must match "
                f"through EngineBackedPrefixIndex")
        if claim >= req.input_len:
            self.full_hits += 1
        self._pctx[req.rid] = _PagedPrefillCtx(list(blocks), claim, first)

    def _stage(self, req: Request, ctx: _PagedPrefillCtx) -> bool:
        """Give an unstaged request a cache row + its fresh blocks, and
        install its table/cursor device-side.  Runs on the runtime thread
        with no pass in flight, so the cache mutation cannot race the
        worker.  Returns False (leaving ctx untouched) under slot/page
        exhaustion — the caller requeues the request's chunks."""
        if not self._free_slots:
            return False
        need = self.pool.blocks_for(req.input_len) - len(ctx.table)
        if need > self.pool.free_count and self.binder is not None:
            self.binder.ensure_free(need)
        if need > self.pool.free_count:
            return False
        fresh = self.pool.alloc(need)
        ctx.table = ctx.table + fresh
        ctx.slot = self._free_slots.pop()
        nbt = self.spec.nbt
        if fresh:
            # reused pages keep their previous tenant's kv_pos; any stale
            # pos <= the reader's cursor would alias as valid history
            ids = jnp.asarray(pad_block_table(fresh, nbt), jnp.int32)
            self.cache = self.spec.jit_clear_rows(self.cache, ids)
        tab = jnp.asarray(pad_block_table(ctx.table, nbt), jnp.int32)
        self.cache = self.spec.run_eager(
            lambda c: dict(c, block_tab=c["block_tab"].at[ctx.slot].set(tab),
                           cur=c["cur"].at[ctx.slot].set(ctx.claimed)),
            self.cache)
        return True

    def start_pass(self, now: float) -> StartResult:
        self._raise_worker_error()
        if self.page_native and not self.busy:
            # stage before batch-forming, in queue order, so _begin_pass
            # only hands the worker requests with a live cache row
            staged = set()
            for d in self.dp_ids:
                for req, _tok in self.queues[d]:
                    ctx = self._pctx.get(req.rid)
                    if (ctx is None or ctx.slot is not None
                            or ctx.consumed >= req.input_len
                            or req.rid in staged):
                        continue
                    if not self._stage(req, ctx):
                        break       # exhausted: later arrivals wait too
                    staged.add(req.rid)
        batch = self._begin_pass(now)
        if batch is None:
            return None
        if self.page_native:
            batch = self._strip_unstaged(batch)
            if batch is None:
                return None
        # the loop's post is bound per run: an abandoned job cannot post
        # into a later run's loop
        post, cause = self._post, trace.current()
        self._worker.submit(lambda: self._exec_pass(batch, post, cause))
        return ASYNC

    def _strip_unstaged(self, batch: Dict[int, List[Tuple[Request, int]]]
                        ) -> Optional[Dict[int, List[Tuple[Request, int]]]]:
        """Drop batch items whose request has no cache row (page/slot
        exhaustion) and requeue them at the FRONT of their queue; roll
        the pass back entirely if nothing runnable remains.  Full-hit
        requests (consumed == input_len, slot None) always stay — their
        zero-token markers complete without touching the device."""
        kept: Dict[int, List[Tuple[Request, int]]] = {}
        dropped = 0
        for d, taken in batch.items():
            keep: List[Tuple[Request, int]] = []
            back: List[Tuple[Request, int]] = []
            for req, tok in taken:
                ctx = self._pctx[req.rid]
                if ctx.slot is None and ctx.consumed < req.input_len:
                    back.append((req, tok))
                else:
                    keep.append((req, tok))
            if keep:
                kept[d] = keep
            for item in reversed(back):
                self.queues[d].appendleft(item)
            dropped += len(back)
        if not kept:
            # nothing runnable: undo _begin_pass bookkeeping and idle
            self._current = None
            self.busy = False
            self.passes -= 1
            self.capacity_offered -= len(self.dp_ids) * self.chunk
            return None
        if dropped:
            self._current = kept
        return kept

    def _exec_pass(self, batch: Dict[int, List[Tuple[Request, int]]],
                   post, cause: Optional[int] = None) -> None:
        # worker thread: pure model execution on engine-private contexts
        with trace.span("prefill.pass", instance=self.instance_id,
                        cause=cause):
            try:
                for taken in batch.values():
                    for req, tok in taken:
                        self._run_chunk(req, tok)
            except BaseException as e:      # surface on the runtime thread
                self._error = e
        post("pass_end", self)

    def _run_chunk(self, req: Request, tok: int) -> None:
        if self.page_native:
            self._run_chunk_paged(req, tok)
            return
        ctx = self._ctx.get(req.rid)
        if ctx is None:
            ctx = self._ctx[req.rid] = _PrefillCtx(self.spec)
        ids = (req.tokens or ())[ctx.consumed: ctx.consumed + tok]
        if ids:
            with self.spec.device_lock():
                with trace.span("prefill.chunk", rid=req.rid,
                                tokens=len(ids)):
                    arr = jnp.asarray([ids], jnp.int32)
                    logits, ctx.cache = self.spec.jit_prefill_chunk(
                        self.spec.params, arr, ctx.cache)
                ctx.consumed += len(ids)
                if ctx.consumed >= req.input_len and ctx.first_token is None:
                    with trace.span("prefill.first_token", rid=req.rid):
                        ctx.first_token = int(jnp.argmax(logits[0]))

    def _run_chunk_paged(self, req: Request, tok: int) -> None:
        # worker thread: extend the request's cache row in place; the
        # engine-shared cache is only rebound here and in the (mutually
        # exclusive) staging path on the runtime thread
        ctx = self._pctx[req.rid]
        ids = (req.tokens or ())[ctx.consumed: ctx.consumed + tok]
        if not ids:
            return
        with self.spec.device_lock():
            with trace.span("prefill.chunk", rid=req.rid, tokens=len(ids)):
                arr = jnp.asarray([ids], jnp.int32)
                logits, self.cache = self.spec.jit_paged_prefill(
                    self.spec.params, arr, self.cache, ctx.slot)
            self.chunks_run += 1
            ctx.consumed += len(ids)
            if ctx.consumed >= req.input_len and ctx.first_token is None:
                with trace.span("prefill.first_token", rid=req.rid):
                    ctx.first_token = int(jnp.argmax(logits[0]))

    def finish_pass(self, now: float) -> PassResult:
        self._raise_worker_error()
        res = super().finish_pass(now)
        for req in res.completed:
            if self.page_native:
                self._complete_paged(req)
                continue
            ctx = self._ctx.pop(req.rid, None)
            if ctx is None or ctx.first_token is None:
                raise RuntimeError(
                    f"request {req.rid} completed prefill without model "
                    f"state (tokens shorter than input_len?)")
            # the paper's KV transfer: park cache + first token on the bus;
            # the first output token is the argmax of the last-chunk logits
            self.bus.publish(req.rid, ctx.cache, ctx.first_token)
            req.generated = 1
        return res

    def _complete_paged(self, req: Request) -> None:
        """Page-native completion: gather ONLY the prompt's pages as the
        handoff payload, publish the pages into the prefix cache, then
        release the engine's row and references.  Ordering matters: the
        gather snapshots page contents before any free; `binder.insert`
        increfs newly bound pages before the engine's own references are
        dropped, so published pages never transit refcount 0."""
        ctx = self._pctx.pop(req.rid, None)
        if ctx is None or ctx.first_token is None:
            raise RuntimeError(
                f"request {req.rid} completed prefill without model "
                f"state (tokens shorter than input_len?)")
        ids = jnp.asarray(pad_block_table(ctx.table, self.spec.nbt),
                          jnp.int32)
        payload = self.spec.jit_gather_blocks(self.cache, ids)
        self.bus.publish(req.rid, PageHandoff(payload, req.input_len),
                         ctx.first_token)
        req.generated = 1
        if self.binder is not None and req.tokens:
            # a prompt's pages are frozen from here on (prefill never
            # writes past input_len), so the partial tail is publishable
            # together with its first-token payload
            self.binder.insert(req.tokens[:req.input_len], ctx.table,
                               first_token=ctx.first_token)
        if ctx.slot is not None:
            self.cache = self.spec.run_eager(
                paged_cache_clear_slot, self.cache, ctx.slot)
            self._free_slots.append(ctx.slot)
        self.pool.free(ctx.table)


# ---------------------------------------------------------------------------
# Real decode
# ---------------------------------------------------------------------------


class _DPDecodeState:
    """One DP unit's padded continuous batch (lazily allocated)."""

    def __init__(self, spec: EngineSpec, n_slots: Optional[int] = None):
        self.spec = spec
        self.cache: Optional[Dict] = None
        n = n_slots if n_slots is not None else spec.max_batch
        self.slots: List[Optional[Request]] = [None] * n
        self.next_tok: List[int] = [0] * n

    def free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.slots):
            if r is None:
                return i
        return None

    def occupied(self) -> bool:
        return any(r is not None for r in self.slots)

    # padded plane: a free slot IS the admission token
    def can_admit(self, need_tokens: int, extra_blocks: int = 0,
                  shared_blocks: int = 0) -> bool:
        return self.free_slot() is not None


class _DPPagedState(_DPDecodeState):
    """One DP unit's paged continuous batch: `paged_slots` cheap batch
    rows over a shared `BlockPool`.  Admission is by free-BLOCK count —
    a request's lifetime blocks are reserved at join (so a resident
    request can never strand mid-generation waiting for a page) and
    returned at leave/drain.

    With `share_prefix`, the unit also owns a `PagePrefixBinder`: joined
    prompts publish their pages, and later prompts with a matching prefix
    point at the resident pages instead of re-copying their handoff
    payload rows.  Pool pressure evicts cache entries before refusing an
    admission (`binder.ensure_free`)."""

    def __init__(self, spec: EngineSpec, share_prefix: bool = False):
        super().__init__(spec, n_slots=spec.paged_slots)
        self.pool = BlockPool(spec.paged_pool_blocks, spec.block_size)
        self.held: Dict[int, List[int]] = {}       # rid -> block ids
        self.binder: Optional[PagePrefixBinder] = (
            PagePrefixBinder(self.pool) if share_prefix else None)

    def can_admit(self, need_tokens: int, extra_blocks: int = 0,
                  shared_blocks: int = 0) -> bool:
        need = self.pool.blocks_for(need_tokens)
        if need > self.pool.num_blocks - 1:
            raise ValueError(
                f"request needs {need} blocks, pool holds only "
                f"{self.pool.num_blocks - 1} — raise max_len/pool_blocks")
        # `shared_blocks` prefix pages are already claimed (refs held):
        # they will be pointed at, never allocated, so only the remainder
        # must come out of the free store
        need -= shared_blocks
        if self.binder is not None:
            self.binder.ensure_free(need + extra_blocks)
        return (self.free_slot() is not None
                and need + extra_blocks <= self.pool.free_count)


class _ShardedGroup:
    """Instance-wide device state of the SHARDED decode plane: ONE merged
    paged cache (slot s ↔ DP s // paged_slots, physical block b ↔ DP
    b // paged_pool_blocks) whose pool dims are sharded over the mesh's
    data axis.  A step is a single cross-DP jitted program — the paper's
    DP sync barrier is the program's own collectives (the EP all-to-all
    and the data-axis layout transfers), not the worker's serial per-DP
    job loop the single-device plane approximates it with."""

    def __init__(self, spec: EngineSpec):
        n = spec.n_dp
        self.cache: Dict = spec.merged_paged_cache()
        self.slots: List[Optional[Request]] = [None] * (n * spec.paged_slots)
        self.next_tok: List[int] = [0] * (n * spec.paged_slots)


class _DPShardedState(_DPPagedState):
    """One DP unit's VIEW of the merged sharded cache.  Admission control
    stays strictly per-DP — the `BlockPool` hands out GLOBAL block ids
    from this DP's base-offset range, `free_slot` scans this DP's global
    slot range, the optional prefix binder is private — while `cache`
    reads/writes through to the shared group, so every jitted join /
    clear / step mutation lands in the one merged device cache and the
    inherited `_apply_joins`/`finish_step` machinery works unchanged."""

    def __init__(self, spec: EngineSpec, group: _ShardedGroup, k: int,
                 share_prefix: bool = False):
        self.spec = spec
        self.group = group
        S = spec.paged_slots
        self.lo, self.hi = k * S, (k + 1) * S
        self.slots = group.slots            # SHARED global slot list
        self.next_tok = group.next_tok      # SHARED global feed tokens
        self.pool = BlockPool(spec.paged_pool_blocks, spec.block_size,
                              base=k * spec.paged_pool_blocks)
        self.held: Dict[int, List[int]] = {}
        self.binder: Optional[PagePrefixBinder] = (
            PagePrefixBinder(self.pool) if share_prefix else None)

    @property
    def cache(self) -> Dict:
        return self.group.cache

    @cache.setter
    def cache(self, value: Dict) -> None:
        self.group.cache = value

    def free_slot(self) -> Optional[int]:
        for i in range(self.lo, self.hi):
            if self.slots[i] is None:
                return i
        return None

    def occupied(self) -> bool:
        return any(r is not None for r in self.slots[self.lo:self.hi])


class RealDecodeEngine(SimDecodeInstance, _WorkerOwner):
    """Continuous batched decode: join-on-handoff / leave-on-finish per
    step.  Request/DPState bookkeeping (token counts, first-token stamps,
    KV accounting, drain/epoch) is inherited from the simulated instance;
    this class adds the physical batch caches and the jitted step."""

    def __init__(self, instance_id: int, dp_ids: Sequence[int],
                 spec: EngineSpec, bus: KVHandoffBus,
                 share_prefix: bool = False):
        super().__init__(instance_id, dp_ids, cost=None)
        _WorkerOwner.__init__(self, f"decode-{instance_id}")
        self.spec = spec
        self.bus = bus
        self._post = None
        if share_prefix and not spec.prefix_sharable:
            raise ValueError(
                "share_prefix requires a paged attention-only config")
        if spec.sharded:
            if len(dp_ids) != spec.n_dp:
                raise ValueError(
                    f"a sharded decode instance must own exactly the "
                    f"mesh's data axis: {len(dp_ids)} dp_ids vs "
                    f"data={spec.n_dp}")
            self._group = _ShardedGroup(spec)
            self._dp: Dict[int, _DPDecodeState] = {
                d: _DPShardedState(spec, self._group, k,
                                   share_prefix=share_prefix)
                for k, d in enumerate(dp_ids)}
        elif spec.paged:
            self._dp = {
                d: _DPPagedState(spec, share_prefix=share_prefix)
                for d in dp_ids}
        else:
            self._dp = {d: _DPDecodeState(spec) for d in dp_ids}
        self._pending: List[Tuple[int, Request]] = []
        self._deferred: set = set()   # rids whose join failed can_admit
        self._slot_of: Dict[int, Tuple[int, int]] = {}   # rid -> (dp, slot)
        self._participants: Dict[int, List[Tuple[Request, int]]] = {}
        self._result: Optional[Dict[int, Tuple[Dict, List[int]]]] = None
        self._join_finished: List[Request] = []
        self.peak_resident = 0      # max concurrent resident requests
        self.cow_copies = 0         # eager tail copy-on-writes at join
        self.blocks_shared = 0      # payload rows skipped via shared pages
        # per-step occupancy samples (worker appends, read after the run):
        # (wall seconds, active decode rows, cache rows stepped) — the
        # sharded bench derives sync-stall = Σ dur·(1 − active/rows) from
        # these, i.e. time the cross-DP program spent advancing idle rows
        self.step_samples: List[Tuple[float, int, int]] = []
        self._step_active = 0
        self._step_rows = 0

    # -- lifecycle -------------------------------------------------------
    def bind_loop(self, loop) -> None:
        self._post = loop.post

    # -- EnginePlane -----------------------------------------------------
    def free_kv_tokens(self, dp_id: int,
                       tokens: Optional[Sequence[int]] = None
                       ) -> Optional[int]:
        st = self._dp[dp_id]
        if self.spec.paged:
            free = st.pool.free_count * self.spec.block_size
            if tokens and getattr(st, "binder", None) is not None:
                # credit the claimable block-aligned prefix already
                # resident in this DP's binder: those pages will be
                # POINTED AT, not allocated, so they are headroom for
                # this prompt even though the pool holds them (the same
                # credit EngineBackedPrefixIndex grants at dispatch)
                claim, _full = st.binder.peek(tokens)
                free += st.pool.blocks_for(claim) * self.spec.block_size
            return free
        free_slots = sum(1 for r in st.slots if r is None)
        return free_slots * self.spec.max_len

    def admit(self, dp_id: int, req: Request) -> None:
        # buffered: joins are applied between steps (start_step), never
        # while a worker-thread step is in flight
        self._pending.append((dp_id, req))

    def pending_waits(self) -> List[Request]:
        """Joins deferred by device-side capacity: admitted by the
        scheduler, but can_admit has refused them at least once.  This is
        the real plane's overload signal — the preemption driver swaps
        lower-priority residents out to let these in."""
        return [r for _, r in self._pending if r.rid in self._deferred]

    def preempt(self, rid: int) -> Optional[Request]:
        """Page-level preemption: the drain() mechanics at request
        granularity.  Parks the victim's KV (+ generation state, already
        on the bus) as a dense batch-1 cache, clears its slot/table row,
        returns its pages to the pool.  Re-admission goes through the
        normal join path — a re-parked cache is NOT a PageHandoff, so it
        re-joins via the dense-paged branch with its generated KV intact.
        Refused (None) while a worker step is in flight."""
        if self.busy:
            return None
        loc = self._slot_of.get(rid)
        if loc is None:
            return None
        dp_id, slot = loc
        req = next((r for r in self.running[dp_id] if r.rid == rid), None)
        if req is None:
            return None
        st = self._dp[dp_id]
        if self.spec.paged:
            # eager (unjitted), like drain(): swaps are rare and per-slot
            # jit specialisation would compile mid-overload
            self.bus.gen(rid).cache = self.spec.run_eager(
                paged_cache_take, self.spec.cfg, st.cache, slot)
            st.cache = self.spec.run_eager(
                paged_cache_clear_slot, st.cache, slot)
            st.pool.free(st.held.pop(rid))
        else:
            self.bus.gen(rid).cache = cache_take(st.cache, slot)
        st.slots[slot] = None
        del self._slot_of[rid]
        self.running[dp_id].remove(req)
        return req

    def has_work(self) -> bool:
        return bool(self._pending) or super().has_work()

    def _target_len(self, req: Request) -> int:
        return self.spec.target_len(req)

    def _apply_joins(self, now: float, dp_states) -> None:
        by_id = {s.dp_id: s for s in dp_states}
        still: List[Tuple[int, Request]] = []
        for dp_id, req in self._pending:
            st = self._dp[dp_id]
            gen = self.bus.gen(req.rid)
            if req.generated >= self._target_len(req):
                # the prefill-emitted token already satisfied the request
                # (output_len == 1): finish at join, never occupy a slot.
                # Stash it so the next finish_step reports it to the
                # runtime (closed-loop refill); when no step ever runs the
                # request still settles — the realtime loop's
                # _all_settled early-exit covers the open-loop path.
                # KNOWN LIMIT: if NO step ever runs on this instance the
                # stash is never reported — closed_loop refill and the
                # watchdog's on_step_end ack miss it (an idle instance may
                # be spuriously drained once, harmlessly, under
                # watchdog_multiplier > 0).  Fixing this needs a
                # completion channel besides finish_step on the
                # EnginePlane contract.
                if req.first_token_time is None:
                    req.first_token_time = now
                req.finish_time = now
                req.phase = RequestPhase.FINISHED
                gen.cache = None
                by_id[dp_id].release(
                    req.input_len + req.generated,
                    reserve_len=req.input_len + req.output_len)
                self._deferred.discard(req.rid)
                self._join_finished.append(req)
                continue
            # padded: admission token = a free slot; paged: a free slot
            # AND the request's lifetime blocks (reserved up front so a
            # resident request never stalls mid-generation on a page;
            # share_prefix holds one block of slack for the eager tail
            # copy-on-write in _join_pages)
            life = self.spec.lifetime_tokens(req)
            use_binder = (self.spec.paged and st.binder is not None
                          and st.pool.blocks_for(life)
                          < st.pool.num_blocks - 1)
            # CLAIM FIRST: take the refs on the resident prefix before
            # the admission check so (a) the check credits pages that
            # will be pointed at rather than allocated — a prefix-heavy
            # request must not defer behind blocks it doesn't need — and
            # (b) ensure_free's LRU eviction can never free the pages
            # between the check and the join
            claim, shared = 0, []
            toks = (req.tokens or ())[:req.input_len]
            if (use_binder and toks
                    and isinstance(gen.cache, PageHandoff)):
                claim, shared, _first = st.binder.claim(toks)
            if not st.can_admit(life, extra_blocks=1 if use_binder else 0,
                                shared_blocks=len(shared)):
                if shared:
                    st.pool.free(shared)     # drop the claim's refs
                self._deferred.add(req.rid)
                still.append((dp_id, req))   # retry after this step
                continue
            self._deferred.discard(req.rid)
            slot = st.free_slot()
            if st.cache is None:
                st.cache = (self.spec.paged_cache() if self.spec.paged
                            else self.spec.batch_cache())
            if self.spec.paged and isinstance(gen.cache, PageHandoff):
                self._join_pages(st, gen, req, slot, use_binder,
                                 claim, shared)
            elif self.spec.paged:
                ids = st.pool.alloc(st.pool.blocks_for(life))
                st.held[req.rid] = ids
                tab = jnp.asarray(pad_block_table(ids, self.spec.nbt),
                                  jnp.int32)
                st.cache = self.spec.jit_paged_join(st.cache, gen.cache,
                                                    slot, tab)
            else:
                st.cache = self.spec.jit_join(st.cache, gen.cache, slot)
            gen.cache = None        # resident now; parked copy released
            st.slots[slot] = req
            st.next_tok[slot] = gen.tokens[-1]
            self._slot_of[req.rid] = (dp_id, slot)
            self.running[dp_id].append(req)
            self.peak_resident = max(self.peak_resident, len(self._slot_of))
        self._pending = still

    def _join_pages(self, st: "_DPPagedState", gen: GenState, req: Request,
                    slot: int, use_binder: bool,
                    claim: int = 0, shared: Sequence[int] = ()) -> None:
        """Adopt a `PageHandoff` into this DP: prefix blocks already
        resident (binder claim — taken by the CALLER before the admission
        check, refs held) are POINTED AT, the rest of the payload is
        copied into fresh blocks, growth blocks get their stale kv_pos
        cleared.  Then the prompt's pages are published into the DP's own
        prefix cache; binding makes the partial tail block shared, and
        the request's first decode write lands exactly there — so the
        copy-on-write divergence is handled EAGERLY, now, while no step
        is in flight, leaving the cached tail frozen at input_len."""
        ph: PageHandoff = gen.cache
        bs = self.spec.block_size
        toks = (req.tokens or ())[:req.input_len]
        n_all = st.pool.blocks_for(self.spec.lifetime_tokens(req))
        n_payload = st.pool.blocks_for(req.input_len)
        shared = list(shared)
        if use_binder and toks:
            # hit stats recorded only on a successful join — a deferred
            # admission must not double-count its retries
            st.binder.record(claim, req.input_len)
        n_shared = len(shared)
        self.blocks_shared += n_shared
        table = list(shared) + st.pool.alloc(n_all - n_shared)
        st.held[req.rid] = table
        idx = jnp.arange(self.spec.nbt)
        copy_mask = (idx >= n_shared) & (idx < n_payload)
        clear_mask = (idx >= n_payload) & (idx < n_all)
        tab = jnp.asarray(pad_block_table(table, self.spec.nbt), jnp.int32)
        st.cache = self.spec.jit_adopt_blocks(
            st.cache, ph.payload, slot, tab, copy_mask, clear_mask,
            req.input_len)
        if not use_binder or not toks:
            return
        st.binder.insert(toks, table[:n_payload],
                         first_token=gen.tokens[0])
        lw = req.input_len // bs
        if req.input_len % bs and st.pool.is_shared(table[lw]):
            # eager COW: the admission slack block becomes the private
            # tail; the cached copy stays frozen for future exact hits
            new = st.pool.alloc(1)[0]
            old = table[lw]
            st.cache = self.spec.jit_copy_block(st.cache, old, new)
            st.cache = self.spec.run_eager(
                lambda c: dict(c, block_tab=c["block_tab"]
                               .at[slot, lw].set(new)),
                st.cache)
            table[lw] = new
            st.pool.free([old])
            self.cow_copies += 1

    def _joins(self, now: float, dp_states) -> None:
        """Apply the buffered joins, as a `decode.joins` span counting the
        requests taken off the pending list."""
        with trace.span("decode.joins", instance=self.instance_id):
            n = len(self._pending)
            self._apply_joins(now, dp_states)
            trace.count("decode.joins", n - len(self._pending))

    def start_step(self, dp_states, now: Optional[float] = None
                   ) -> StartResult:
        self._raise_worker_error()
        if self.busy:
            return None
        if self._pending:
            self._joins(now if now is not None else 0.0, dp_states)
        if not super().has_work():
            return None
        self.busy = True
        self.steps += 1
        jobs: List[Tuple[int, Dict, jnp.ndarray]] = []
        self._participants = {}
        for d in self.dp_ids:
            st = self._dp[d]
            if not self.running[d]:
                continue
            self._participants[d] = [
                (r, self._slot_of[r.rid][1]) for r in self.running[d]]
            if self.spec.sharded:
                continue            # ONE merged cross-DP job, built below
            toks = jnp.asarray([[t] for t in st.next_tok], jnp.int32)
            jobs.append((d, st.cache, toks))
        if self.spec.sharded and self._participants:
            # the instance sync barrier now lives INSIDE the program: one
            # step over the merged cache advances every DP's rows under
            # the same mesh collectives (dp_id -1 marks the merged job)
            g = self._group
            toks = jnp.asarray([[t] for t in g.next_tok], jnp.int32)
            jobs.append((-1, g.cache, toks))
        self._step_active = sum(len(v) for v in self._participants.values())
        self._step_rows = (len(self._group.slots) if self.spec.sharded
                           else sum(len(self._dp[d].slots)
                                    for d in self._participants))
        epoch, post, cause = self.epoch, self._post, trace.current()
        self._worker.submit(lambda: self._exec_step(jobs, epoch, post, cause))
        return ASYNC

    def _step_span(self, cause: Optional[int]):
        return trace.span("decode.step", instance=self.instance_id,
                          step=self.steps, rows=self._step_active,
                          cause=cause)

    @staticmethod
    def _readout(logits, dp_id: int, firsts=()) -> Tuple[List[int],
                                                          List[int]]:
        """The host's next tokens of a DP job: each row's argmax, then
        each chunk's first token, taken on the device and brought to the
        host in one `device_get`: one blocking round trip whatever the
        rows, where iterating the device array would run a program and
        block once per row, with the chip idle."""
        with trace.span("decode.readout", dp=dp_id):
            rows = [] if logits is None else [jnp.argmax(logits, axis=-1)]
            host = jax.device_get(rows + [jnp.argmax(lg[0]) for lg in firsts])
            nxt = host[0].tolist() if rows else []
            first = [int(x) for x in host[len(rows):]]
            trace.count("decode.host_reads", len(nxt) + len(first))
            trace.count("decode.host_transfers")
        return nxt, first

    def _exec_step(self, jobs, epoch: int, post,
                   cause: Optional[int] = None) -> None:
        # worker thread: one batched decode_step per occupied DP (the
        # instance-level sync barrier = all DPs in one serial job)
        with self._step_span(cause):
            t0 = time.monotonic()
            step = (self.spec.jit_paged_decode if self.spec.paged
                    else self.spec.jit_decode)
            try:
                res: Dict[int, Tuple[Dict, List[int]]] = {}
                for dp_id, cache, toks in jobs:
                    with self.spec.device_lock():
                        with trace.span("decode.launch", dp=dp_id):
                            logits, new_cache = step(self.spec.params, toks,
                                                     cache)
                        nxt, _ = self._readout(logits, dp_id)
                    if dp_id < 0:
                        # merged cross-DP job: slots are global, so the
                        # same (cache, next-token) pair fans back to every
                        # participating DP — finish_step indexes nxt by
                        # the participant's global slot unchanged
                        for d in self._participants:
                            res[d] = (new_cache, nxt)
                    else:
                        res[dp_id] = (new_cache, nxt)
                self._result = res
            except BaseException as e:      # surface on the runtime thread
                self._error = e
            dur = time.monotonic() - t0
            self.step_samples.append((dur, self._step_active,
                                      self._step_rows))
        post("step_end", (self, epoch, dur))

    def finish_step(self, now: float, dp_states) -> List[Request]:
        self._raise_worker_error()
        with trace.span("decode.finish", instance=self.instance_id,
                        step=self.steps):
            return self._finish_step(now, dp_states)

    def _finish_step(self, now: float, dp_states) -> List[Request]:
        """Append each row's token, leave finished requests' slots."""
        res, self._result = self._result, None
        parts, self._participants = self._participants, {}
        assert res is not None
        for dp_id, (new_cache, nxt) in res.items():
            st = self._dp[dp_id]
            st.cache = new_cache
            for req, slot in parts.get(dp_id, []):
                tok = nxt[slot]
                self.bus.gen(req.rid).tokens.append(tok)
                st.next_tok[slot] = tok
        finished = super().finish_step(now, dp_states)
        for req in finished:
            dp_id, slot = self._slot_of.pop(req.rid)
            st = self._dp[dp_id]
            st.slots[slot] = None                    # leave-on-finish
            if self.spec.paged:
                # drop the table row FIRST: the now-inactive slot keeps
                # stepping on garbage, and its writes must route to the
                # null block, never to pages the pool re-issues
                st.cache = self.spec.run_eager(
                    paged_cache_clear_slot, st.cache, slot)
                st.pool.free(st.held.pop(req.rid))
        if self._join_finished:
            # requests satisfied at join time (never occupied a slot):
            # report them with this step's completions so the runtime's
            # closed-loop refill sees every finish
            finished = self._join_finished + finished
            self._join_finished = []
        return finished

    def drain(self) -> Dict[int, List[Request]]:
        out = super().drain()   # clears running, bumps epoch, unlocks
        # migrate resident KV back to the bus so re-dispatch can re-join
        # the requests (with their generation state) on a healthy instance
        for rid, (dp_id, slot) in list(self._slot_of.items()):
            st = self._dp[dp_id]
            if self.spec.paged:
                # eager (unjitted), like the padded cache_take branch: the
                # drain path is rare and per-slot jit specialisation would
                # compile a fresh gather program mid-recovery
                self.bus.gen(rid).cache = self.spec.run_eager(
                    paged_cache_take, self.spec.cfg, st.cache, slot)
                st.cache = self.spec.run_eager(
                    paged_cache_clear_slot, st.cache, slot)
                st.pool.free(st.held.pop(rid))
            else:
                self.bus.gen(rid).cache = cache_take(st.cache, slot)
            st.slots[slot] = None
        self._slot_of.clear()
        for dp_id, req in self._pending:
            out.setdefault(dp_id, []).append(req)
        self._pending = []
        self._deferred.clear()
        self._participants = {}
        self._result = None
        return out

# ---------------------------------------------------------------------------
# Real unified mixed-batch engine
# ---------------------------------------------------------------------------


class RealUnifiedEngine(RealDecodeEngine, UnifiedEngine):
    """Unified mixed-batch engine (paged only): one pool, one step loop.

    Raw requests (no published generation state) are staged as
    PREFILLING RESIDENTS at join time: their lifetime pages are reserved
    and their table row installed with `cur = 0`, exactly like
    `RealPrefillEngine._stage` — but into the DECODE pool, so no KV
    handoff ever happens.  Each step then runs `mixed_step`: the decode
    rows' batched forward plus as many pending prefill-chunk tokens as
    fit the leftover budget (`chunk − decode_rows`), in ONE XLA program.
    The decode half is MASKED to the actively-decoding slots — a
    prefilling resident's table row is live, so an unmasked decode would
    scribble a garbage token into its pages and bump its cursor.

    Chunk grants are quantized to `block_size` multiples (except a
    prompt's final chunk) to bound jit retraces; the starvation bound
    (`starve_limit`) forces a minimum grant when decode rows hog the
    budget.  `piggyback=False` is the DISJOINT ablation (the
    prefill-prioritizing chunked loop Sarathi measures against): a step
    with pending prefill runs ONLY the prefill chunk while the decode
    rows stall — the ITL bubble the unified plane exists to remove."""

    def __init__(self, instance_id: int, dp_ids: Sequence[int],
                 spec: EngineSpec, bus: KVHandoffBus, chunk: int = 256,
                 starve_limit: int = 4, piggyback: bool = True,
                 share_prefix: bool = False):
        if not spec.paged:
            raise ValueError(
                "the unified mixed-batch engine requires block_size > 0 "
                "(prefill chunks ride paged_prefill_step into the pool)")
        _require_pageable_prefill(spec.cfg)
        super().__init__(instance_id, dp_ids, spec, bus,
                         share_prefix=share_prefix)
        self.chunk = max(int(chunk), 1)
        self.starve_limit = max(int(starve_limit), 1)
        self.piggyback = piggyback
        self.prefilling: Dict[int, "collections.deque[Request]"] = {
            d: collections.deque() for d in dp_ids}
        self._consumed: Dict[int, int] = {}       # rid -> prompt tokens done
        self._starve: Dict[int, int] = {d: 0 for d in dp_ids}
        self._grants: Dict[int, List[Tuple[Request, int]]] = {}
        self._chunk_result: Optional[Dict[int, List[int]]] = None
        self._stalled: set = set()
        self.prefill_tokens = 0
        self.forced_grants = 0      # starvation-bound activations
        self.mixed_steps = 0        # steps that ran decode+prefill fused

    # -- EnginePlane -----------------------------------------------------
    def has_work(self) -> bool:
        return (super().has_work()
                or any(self.prefilling[d] for d in self.dp_ids))

    def prefill_backlog(self) -> int:
        return sum(r.input_len - self._consumed[r.rid]
                   for d in self.dp_ids for r in self.prefilling[d])

    def _apply_joins(self, now: float, dp_states) -> None:
        # handed-off requests (drain re-parks, preemption re-admits) ride
        # the parent join path; RAW requests — no transferred KV on the
        # bus — stage as prefilling residents.  A bus entry WITHOUT a
        # cache is one this plane published itself (unified prefill
        # completions set gen.cache = None), e.g. a re-served rid from a
        # previous run on the same deployment: still raw
        raw: List[Tuple[int, Request]] = []
        rest: List[Tuple[int, Request]] = []
        for item in self._pending:
            gen = self.bus.get(item[1].rid)
            (raw if gen is None or gen.cache is None else rest).append(item)
        self._pending = rest
        super()._apply_joins(now, dp_states)
        still: List[Tuple[int, Request]] = []
        for dp_id, req in raw:
            st = self._dp[dp_id]
            life = self.spec.lifetime_tokens(req)
            if not st.can_admit(life):
                self._deferred.add(req.rid)
                still.append((dp_id, req))
                continue
            self._deferred.discard(req.rid)
            slot = st.free_slot()
            if st.cache is None:
                st.cache = self.spec.paged_cache()
            ids = st.pool.alloc(st.pool.blocks_for(life))
            st.held[req.rid] = ids
            arr = jnp.asarray(pad_block_table(ids, self.spec.nbt), jnp.int32)
            # reused pages keep their previous tenant's kv_pos; stale
            # pos <= the reader's cursor would alias as valid history
            st.cache = self.spec.jit_clear_rows(st.cache, arr)
            st.cache = self.spec.run_eager(
                lambda c: dict(c, block_tab=c["block_tab"].at[slot].set(arr),
                               cur=c["cur"].at[slot].set(0)),
                st.cache)
            st.slots[slot] = req
            self._slot_of[req.rid] = (dp_id, slot)
            self._consumed[req.rid] = 0
            self.prefilling[dp_id].append(req)
            self.peak_resident = max(self.peak_resident, len(self._slot_of))
        self._pending.extend(still)

    # -- budget split ----------------------------------------------------
    def _form_grants(self, d: int, n_decode: int, now: float
                     ) -> List[Tuple[Request, int]]:
        q = self.prefilling[d]
        if not q:
            self._starve[d] = 0
            return []
        # disjoint ablation: prefill-prioritizing baseline — the full
        # chunk budget every step, decode rows stall while it runs
        budget = self.chunk - n_decode if self.piggyback else self.chunk
        if budget <= 0:
            self._starve[d] += 1
            if self._starve[d] < self.starve_limit:
                return []
            budget = max(1, self.chunk // 4)    # forced minimum grant
            self.forced_grants += 1
        bs = self.spec.block_size
        grants: List[Tuple[Request, int]] = []
        for req in q:
            if budget <= 0:
                break
            remaining = req.input_len - self._consumed[req.rid]
            use = min(remaining, budget)
            if use < remaining:
                # partial chunks land on block boundaries: bounds jit
                # retraces to block-multiple shapes + final-chunk shapes
                use = (use // bs) * bs
                if use <= 0:
                    break
            if req.prefill_start is None:
                req.prefill_start = now
            grants.append((req, use))
            budget -= use
            # one chunk per DP per step: each extra chunk in the tuple
            # multiplies the jit_mixed shape lattice (every combination
            # of chunk lengths is a fresh trace), and a single grant
            # keeps prefill FIFO anyway — leftover budget just waits a
            # step
            break
        if grants:
            self._starve[d] = 0
        return grants

    def start_step(self, dp_states, now: Optional[float] = None
                   ) -> StartResult:
        self._raise_worker_error()
        if self.busy:
            return None
        if self._pending:
            self._joins(now if now is not None else 0.0, dp_states)
        if not (SimDecodeInstance.has_work(self)
                or any(self.prefilling[d] for d in self.dp_ids)):
            return None
        tnow = now if now is not None else 0.0
        jobs: List[Tuple[int, Dict, Optional[jnp.ndarray], tuple,
                         Optional[jnp.ndarray]]] = []
        self._participants = {}
        self._grants = {}
        self._stalled = set()
        for d in self.dp_ids:
            st = self._dp[d]
            rows = self.running[d]
            grants = self._form_grants(d, len(rows), tnow)
            if grants:
                self._grants[d] = grants
            stall = bool(grants) and not self.piggyback and bool(rows)
            if stall:
                self._stalled.add(d)
            decode_rows = [] if stall else rows
            if not decode_rows and not grants:
                continue
            chunks = []
            for req, use in grants:
                c0 = self._consumed[req.rid]
                ids = list((req.tokens or ())[c0: c0 + use])
                chunks.append((jnp.asarray([ids], jnp.int32),
                               jnp.int32(self._slot_of[req.rid][1])))
            toks = mask = None
            if decode_rows:
                self._participants[d] = [
                    (r, self._slot_of[r.rid][1]) for r in decode_rows]
                toks = jnp.asarray([[t] for t in st.next_tok], jnp.int32)
                if chunks or self.prefilling[d]:
                    # prefilling residents have LIVE table rows: mask the
                    # decode half to the actively-decoding slots
                    m = [False] * len(st.slots)
                    for _r, s in self._participants[d]:
                        m[s] = True
                    mask = jnp.asarray(m)
            jobs.append((d, st.cache, toks, tuple(chunks), mask))
        if not jobs:
            return None
        if self.spec.sharded:
            jobs = self._merge_sharded_jobs(jobs)
        self.busy = True
        self.steps += 1
        self._step_active = sum(len(v) for v in self._participants.values())
        self._step_rows = (
            len(self._group.slots) if self.spec.sharded
            else sum(len(self._dp[d].slots)
                     for d, _c, toks, _ch, _m in jobs if toks is not None))
        epoch, post, cause = self.epoch, self._post, trace.current()
        self._worker.submit(
            lambda: self._exec_mixed(jobs, epoch, post, cause))
        return ASYNC

    def _merge_sharded_jobs(self, jobs):
        """Collapse the per-DP mixed jobs into ONE cross-DP program over
        the merged cache: global decode-token rows, every DP's chunk
        grant in one tuple (slot ids are already global — grant order
        matches `self._grants` iteration order, which the fan-back in
        `_exec_mixed` relies on), one decode mask over the merged slot
        axis.  The mask is unconditional whenever anything decodes: all
        DPs share the one cache, so another DP's prefilling (or
        disjoint-stalled) resident rows must never see a decode write."""
        g = self._group
        chunks = tuple(c for _d, _c, _t, cs, _m in jobs for c in cs)
        if not self._participants:
            return [(-1, g.cache, None, chunks, None)]
        toks = jnp.asarray([[t] for t in g.next_tok], jnp.int32)
        m = [False] * len(g.slots)
        for lst in self._participants.values():
            for _r, s in lst:
                m[s] = True
        return [(-1, g.cache, toks, chunks, jnp.asarray(m))]

    def _exec_mixed(self, jobs, epoch: int, post,
                    cause: Optional[int] = None) -> None:
        # worker thread: one fused mixed step per DP with decode rows
        # (masked when prefilling residents share the cache), a plain
        # paged decode when nothing is prefilling, a serial chunk loop
        # when nothing is decoding
        spec = self.spec
        with self._step_span(cause):
            t0 = time.monotonic()
            try:
                res: Dict[int, Tuple[Dict, List[int]]] = {}
                cres: Dict[int, List[int]] = {}
                for dp_id, cache, toks, chunks, mask in jobs:
                    with spec.device_lock():
                        with trace.span("decode.launch", dp=dp_id):
                            logits = None
                            if toks is None:
                                new_cache = cache
                                clogits = []
                                for ctoks, slot in chunks:
                                    lg, new_cache = spec.jit_paged_prefill(
                                        spec.params, ctoks, new_cache, slot)
                                    clogits.append(lg)
                            elif mask is not None:
                                logits, clogits, new_cache = spec.jit_mixed(
                                    spec.params, toks, cache, chunks, mask)
                                if chunks:
                                    self.mixed_steps += 1
                            else:
                                logits, new_cache = spec.jit_paged_decode(
                                    spec.params, toks, cache)
                                clogits = ()
                        nxt, firsts = self._readout(logits, dp_id, clogits)
                    if dp_id < 0:
                        # merged cross-DP job: one cache/next-token pair
                        # fans back to every decoding DP (slots are
                        # global); the flat chunk firsts split by per-DP
                        # grant counts in the same order
                        # _merge_sharded_jobs flattened them
                        i = 0
                        for d, lst in self._grants.items():
                            cres[d] = firsts[i:i + len(lst)]
                            i += len(lst)
                        if self._participants:
                            for d in self._participants:
                                res[d] = (new_cache, nxt)
                        else:
                            res[self.dp_ids[0]] = (new_cache, [])
                        continue
                    res[dp_id] = (new_cache, nxt)
                    cres[dp_id] = firsts
                self._result = res
                self._chunk_result = cres
            except BaseException as e:      # surface on the runtime thread
                self._error = e
            dur = time.monotonic() - t0
            self.step_samples.append((dur, self._step_active,
                                      self._step_rows))
        post("step_end", (self, epoch, dur))

    def finish_step(self, now: float, dp_states) -> List[Request]:
        cres = self._chunk_result or {}
        self._chunk_result = None
        grants, self._grants = self._grants, {}
        stalled, self._stalled = self._stalled, set()
        by_id = {s.dp_id: s for s in dp_states}
        # disjoint-stall steps: detach the stalled DPs' rows so the
        # parent pass emits nothing for them (that stall IS the ablation)
        saved = {d: self.running[d] for d in stalled}
        for d in stalled:
            self.running[d] = []
        finished = super().finish_step(now, dp_states)
        for d, rows in saved.items():
            self.running[d] = rows + self.running[d]
        # prefill half: account granted tokens; a completed prompt
        # publishes its first token (argmax of the chunk's last position)
        # and graduates to the decode rows — no handoff, same pool
        for d, lst in grants.items():
            st = self._dp[d]
            sched = by_id[d]
            firsts = cres.get(d, [])
            q = self.prefilling[d]
            for i, (req, use) in enumerate(lst):
                self._consumed[req.rid] += use
                req.remaining_prefill = max(
                    req.input_len - self._consumed[req.rid], 0)
                self.prefill_tokens += use
                if self._consumed[req.rid] < req.input_len:
                    continue
                first = firsts[i]
                q.remove(req)
                del self._consumed[req.rid]
                gen = self.bus.publish(req.rid, None, first)
                gen.cache = None            # resident already — no payload
                sched.step(1)               # the emitted token's KV entry
                req.generated += 1
                if req.first_token_time is None:
                    req.first_token_time = now
                self._record_emit(req.rid, now)
                slot = self._slot_of[req.rid][1]
                if req.generated >= self._target_len(req):
                    req.finish_time = now
                    sched.release(req.input_len + req.generated,
                                  reserve_len=req.input_len + req.output_len)
                    self._last_emit.pop(req.rid, None)
                    self._slot_of.pop(req.rid)
                    st.cache = self.spec.run_eager(
                        paged_cache_clear_slot, st.cache, slot)
                    st.slots[slot] = None
                    st.pool.free(st.held.pop(req.rid))
                    finished.append(req)
                else:
                    st.next_tok[slot] = first
                    self.running[d].append(req)
        return finished

    def drain(self) -> Dict[int, List[Request]]:
        # prefilling residents have no parked generation state: drop
        # their partial KV (pages back to the pool) and restart prefill
        # wherever re-dispatch lands them
        pre: Dict[int, List[Request]] = {}
        for d in self.dp_ids:
            q = self.prefilling[d]
            if not q:
                self._starve[d] = 0
                continue
            pre[d] = list(q)
            q.clear()
            st = self._dp[d]
            for req in pre[d]:
                _dp, slot = self._slot_of.pop(req.rid)
                st.cache = self.spec.run_eager(
                    paged_cache_clear_slot, st.cache, slot)
                st.slots[slot] = None
                st.pool.free(st.held.pop(req.rid))
                del self._consumed[req.rid]
                req.remaining_prefill = req.input_len
            self._starve[d] = 0
        out = super().drain()
        for d, reqs in pre.items():
            out.setdefault(d, []).extend(reqs)
        self._grants = {}
        self._chunk_result = None
        self._stalled = set()
        return out

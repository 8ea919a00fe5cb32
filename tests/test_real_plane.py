"""The real engine plane behind the unified ClusterRuntime:

  * token-level equivalence of continuous batched decode (padded batch
    cache + join/leave AND the paged block-table cache) against the seed
    per-request serial decode
  * cache_take/cache_join round trip (the watchdog migration path), for
    both backends
  * paged admission: a DP admits by free-BLOCK count, sustaining more
    concurrent requests than the padded plane at equal KV memory
  * conservation + completion invariants of the real P/D handoff under
    `sbs` and `sbs-la`, including the satellite regressions:
      - prefill_start stamped when the first chunk STARTS (not at
        prefill completion)
      - serve() leaves caller-owned Request.arrival_time untouched
      - a failing worker forward (prefill OR decode) surfaces within one
        scheduling window, not at the timeout horizon
  * the cross-plane equivalence sweep (sim/real × padded/paged, @slow)
"""
import random
import time

import jax
import jax.numpy as jnp
import pytest

from repro.config import ServingConfig, get_arch
from repro.core.types import DecodeDPState, Request
from repro.models import (
    cache_join, cache_take, decode_step, init_cache, init_paged_cache,
    init_params, paged_cache_clear_slot, paged_cache_join, paged_cache_take,
    paged_decode_step, prefill_chunk,
)
from repro.serving.kv_pool import BlockPool, pad_block_table
from repro.serving.real_engine import (
    EngineSpec, KVHandoffBus, RealDecodeEngine,
)
from repro.serving.runtime import ClusterRuntime
from repro.serving.server import RealSBSServer

MAX_LEN = 96
BLOCK = 16
N_NEW = 5


@pytest.fixture(scope="module")
def tiny_dense():
    cfg = get_arch("deepseek-7b", reduced=True)   # dense: exact equivalence
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _chunked_prefill(cfg, params, ids, chunk=16):
    """The seed server's prefill algorithm: batch-1 chunked KV build."""
    cache = init_cache(cfg, 1, MAX_LEN)
    logits = None
    for i in range(0, len(ids), chunk):
        arr = jnp.asarray([ids[i:i + chunk]], jnp.int32)
        logits, cache = prefill_chunk(cfg, params, arr, cache)
    return int(jnp.argmax(logits[0])), cache


def _serial_decode(cfg, params, t0, cache, n):
    """The seed server's decode loop: batch-of-1, token by token."""
    toks = [t0]
    for _ in range(n - 1):
        lg, cache = decode_step(cfg, params,
                                jnp.asarray([[toks[-1]]], jnp.int32), cache)
        toks.append(int(jnp.argmax(lg[0])))
    return toks, cache


# ---------------------------------------------------------------------------
# Batched continuous decode == seed serial decode
# ---------------------------------------------------------------------------

def test_batched_continuous_decode_matches_serial(tiny_dense):
    """Requests joining a padded batch cache at different steps (continuous
    batching) must generate exactly the tokens of the seed per-request
    serial decode."""
    cfg, params = tiny_dense
    rng = random.Random(0)
    prompts = [[rng.randrange(cfg.vocab_size) for _ in range(L)]
               for L in (23, 37, 11)]
    serial, handoffs = [], []
    for ids in prompts:
        t0, cache = _chunked_prefill(cfg, params, ids)
        serial.append(_serial_decode(cfg, params, t0, cache, N_NEW)[0])
        handoffs.append((t0, cache))

    B = 4
    bc = init_cache(cfg, B, MAX_LEN)
    # join-on-handoff: r0/r1 up front, r2 joins two steps later
    toks = {}
    next_tok = [0] * B
    for slot, ridx in ((0, 0), (2, 1)):
        t0, cache = handoffs[ridx]
        bc = cache_join(bc, cache, slot)
        toks[slot] = [t0]
        next_tok[slot] = t0
    slot_of = {0: 0, 1: 2}                       # request idx -> slot
    for step in range(N_NEW + 2):
        if step == 2:
            t0, cache = handoffs[2]
            bc = cache_join(bc, cache, 1)        # late join into a free slot
            toks[1] = [t0]
            next_tok[1] = t0
            slot_of[2] = 1
        active = [s for s in toks if len(toks[s]) < N_NEW]
        if not active:
            break
        lg, bc = decode_step(cfg, params,
                             jnp.asarray([[t] for t in next_tok], jnp.int32),
                             bc)
        nxt = jnp.argmax(lg, axis=-1)
        for s in active:                         # leave-on-finish: inactive
            t = int(nxt[s])                      # slots just step on garbage
            toks[s].append(t)
            next_tok[s] = t
    batched = [toks[slot_of[i]] for i in range(3)]
    assert batched == serial


def test_cache_take_roundtrip_continues_serial(tiny_dense):
    """cache_take (watchdog migration) must extract a slot that continues
    generating exactly like the never-batched serial cache."""
    cfg, params = tiny_dense
    rng = random.Random(1)
    ids = [rng.randrange(cfg.vocab_size) for _ in range(29)]
    t0, cache = _chunked_prefill(cfg, params, ids)
    serial, _ = _serial_decode(cfg, params, t0, cache, 6)

    bc = init_cache(cfg, 3, MAX_LEN)
    bc = cache_join(bc, cache, 1)
    toks = [t0]
    next_tok = [0, t0, 0]
    for _ in range(2):                           # two batched steps...
        lg, bc = decode_step(cfg, params,
                             jnp.asarray([[t] for t in next_tok], jnp.int32),
                             bc)
        t = int(jnp.argmax(lg[1]))
        toks.append(t)
        next_tok[1] = t
    taken = cache_take(bc, 1)                    # ...then migrate out
    rest, _ = _serial_decode(cfg, params, toks[-1], taken, 4)
    assert toks + rest[1:] == serial


# ---------------------------------------------------------------------------
# Paged (block-table) continuous decode == seed serial decode
# ---------------------------------------------------------------------------

NBT = MAX_LEN // BLOCK


@pytest.mark.paged
def test_paged_batched_continuous_decode_matches_serial(tiny_dense):
    """Requests joining a PAGED batch cache at different steps must
    generate exactly the tokens of the seed per-request serial decode —
    the paged mirror of the padded test above."""
    cfg, params = tiny_dense
    rng = random.Random(0)
    prompts = [[rng.randrange(cfg.vocab_size) for _ in range(L)]
               for L in (23, 37, 11)]
    serial, handoffs = [], []
    for ids in prompts:
        t0, cache = _chunked_prefill(cfg, params, ids)
        serial.append(_serial_decode(cfg, params, t0, cache, N_NEW)[0])
        handoffs.append((t0, cache))

    pool = BlockPool(16, BLOCK)
    pc = init_paged_cache(cfg, 4, 16, MAX_LEN, BLOCK)
    toks = {}
    next_tok = [0] * 4
    slot_of = {}

    def join(ridx, slot):
        nonlocal pc
        t0, cache = handoffs[ridx]
        ids = pool.alloc(pool.blocks_for(len(prompts[ridx]) + N_NEW - 1))
        tab = jnp.asarray(pad_block_table(ids, NBT), jnp.int32)
        pc = paged_cache_join(cfg, pc, cache, slot, tab)
        toks[slot] = [t0]
        next_tok[slot] = t0
        slot_of[ridx] = slot

    join(0, 0)
    join(1, 2)
    for step in range(N_NEW + 2):
        if step == 2:
            join(2, 1)                           # late join into a free slot
        active = [s for s in toks if len(toks[s]) < N_NEW]
        if not active:
            break
        lg, pc = paged_decode_step(
            cfg, params, jnp.asarray([[t] for t in next_tok], jnp.int32), pc)
        nxt = jnp.argmax(lg, axis=-1)
        for s in active:
            t = int(nxt[s])
            toks[s].append(t)
            next_tok[s] = t
    batched = [toks[slot_of[i]] for i in range(3)]
    assert batched == serial


@pytest.mark.paged
def test_paged_take_roundtrip_continues_serial(tiny_dense):
    """paged_cache_take (watchdog migration) must extract a dense batch-1
    cache that continues generating exactly like the never-paged serial
    cache, and the freed pages must return to the pool."""
    cfg, params = tiny_dense
    rng = random.Random(1)
    ids = [rng.randrange(cfg.vocab_size) for _ in range(29)]
    t0, cache = _chunked_prefill(cfg, params, ids)
    serial, _ = _serial_decode(cfg, params, t0, cache, 6)

    pool = BlockPool(12, BLOCK)
    pc = init_paged_cache(cfg, 3, 12, MAX_LEN, BLOCK)
    blocks = pool.alloc(pool.blocks_for(29 + 6))
    pc = paged_cache_join(
        cfg, pc, cache, 1,
        jnp.asarray(pad_block_table(blocks, NBT), jnp.int32))
    toks = [t0]
    next_tok = [0, t0, 0]
    for _ in range(2):                           # two paged steps...
        lg, pc = paged_decode_step(
            cfg, params, jnp.asarray([[t] for t in next_tok], jnp.int32), pc)
        t = int(jnp.argmax(lg[1]))
        toks.append(t)
        next_tok[1] = t
    taken = paged_cache_take(cfg, pc, 1)         # ...then migrate out
    pc = paged_cache_clear_slot(pc, 1)
    pool.free(blocks)
    pool.check()
    rest, _ = _serial_decode(cfg, params, toks[-1], taken, 4)
    assert toks + rest[1:] == serial


# ---------------------------------------------------------------------------
# Real P/D handoff through ClusterRuntime
# ---------------------------------------------------------------------------

def _mk_requests(cfg, n=4, out_len=3, seed=0):
    rng = random.Random(seed)
    reqs = []
    for i in range(n):
        L = rng.randrange(16, 48)
        reqs.append(Request(
            rid=i, arrival_time=i * 0.02, input_len=L, output_len=out_len,
            tokens=tuple(rng.randrange(cfg.vocab_size) for _ in range(L))))
    return reqs


@pytest.fixture(scope="module")
def shared_spec(tiny_dense):
    cfg, params = tiny_dense
    return EngineSpec(cfg, params, max_len=MAX_LEN, max_batch=8, max_new=3)


@pytest.mark.parametrize("scheduler", ["sbs", "sbs-la"])
def test_real_pd_handoff_conserves_and_completes(tiny_dense, shared_spec,
                                                 scheduler):
    cfg, params = tiny_dense
    reqs = _mk_requests(cfg)
    arrivals = [r.arrival_time for r in reqs]
    srv = RealSBSServer(cfg, params, scheduler=scheduler, max_len=MAX_LEN,
                        max_new=3, spec=shared_spec)
    assert isinstance(srv.runtime, ClusterRuntime)   # one driver, both planes
    gens = srv.serve(reqs, timeout=120)

    # completion: every request finishes exactly once with its full output
    assert sorted(g.rid for g in gens) == [r.rid for r in reqs]
    for g, r in zip(gens, reqs):
        assert len(g.tokens) == r.output_len
        assert r.generated == r.output_len
    # timestamps: dispatch -> first chunk start -> first token -> finish,
    # with prefill_start stamped at chunk START (satellite regression)
    for r in reqs:
        assert r.prefill_start is not None
        assert r.dispatch_time <= r.prefill_start <= r.first_token_time
        assert r.arrival_time <= r.first_token_time <= r.finish_time
    # caller-owned arrival times are never rewritten (satellite regression)
    assert [r.arrival_time for r in reqs] == arrivals
    # conservation: decode accounting fully drained, tokens additive
    assert sum(d.kv_tokens for d in srv.state.decode_dps) == 0
    assert sum(d.batch for d in srv.state.decode_dps) == 0
    decoded = sum(e.tokens_generated for e in srv.decode_engines)
    # the first token of each request is emitted by the prefill plane
    assert decoded == sum(r.output_len - 1 for r in reqs)
    prefilled = sum(e.tokens_processed for e in srv.engines)
    assert prefilled == sum(r.input_len for r in reqs)


def test_worker_error_surfaces_promptly(tiny_dense):
    """A failing forward on an engine worker thread must raise out of
    serve() immediately, not leave the runtime blocked until the
    timeout horizon."""
    cfg, params = tiny_dense
    spec = EngineSpec(cfg, params, max_len=MAX_LEN, max_batch=4, max_new=3)

    def boom(p, t, c):
        raise RuntimeError("boom")

    spec.jit_prefill_chunk = boom
    srv = RealSBSServer(cfg, params, scheduler="sbs", max_len=MAX_LEN,
                        max_new=3, spec=spec)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="boom"):
        srv.serve(_mk_requests(cfg, n=2), timeout=60)
    assert time.monotonic() - t0 < 30


def test_real_ttft_stamped_at_prefill_completion(tiny_dense, shared_spec):
    """On the real plane the first token is produced by the prefill
    engine: its stamp must survive the handoff (TTFT is NOT deferred to
    the first batched decode step, which emits token #2)."""
    cfg, params = tiny_dense
    reqs = _mk_requests(cfg, n=3, seed=3)
    srv = RealSBSServer(cfg, params, scheduler="sbs", max_len=MAX_LEN,
                        max_new=3, spec=shared_spec)
    step_times = []
    for eng in srv.decode_engines:          # record decode step completions
        orig = eng.finish_step
        eng.finish_step = (lambda now, dps, _o=orig:
                           (step_times.append(now), _o(now, dps))[1])
    gens = srv.serve(reqs, timeout=120)
    assert len(gens) == 3
    for r in reqs:
        # the stamp is a prefill pass_end, never a decode step_end (the
        # old behavior re-stamped TTFT at a decode step completion)
        assert r.first_token_time not in step_times
        assert r.first_token_time < r.finish_time
        # and it precedes every decode step this request participated in
        assert any(r.first_token_time < t for t in step_times)


def test_real_immediate_baseline_completes(tiny_dense, shared_spec):
    """The immediate baseline runs over the same plane unchanged."""
    cfg, params = tiny_dense
    reqs = _mk_requests(cfg, n=3, seed=2)
    srv = RealSBSServer(cfg, params, scheduler="immediate", max_len=MAX_LEN,
                        max_new=3, spec=shared_spec)
    gens = srv.serve(reqs, timeout=120)
    assert len(gens) == 3
    assert all(len(g.tokens) == 3 for g in gens)


def test_repeated_serve_completes_without_timeline_stall(tiny_dense,
                                                         shared_spec):
    """serve() may be called repeatedly on one server: the runtime resets
    time-gated scheduler stamps (reset_clock) so a second run is not
    stalled by the previous run's timeline.  The adaptive T_fwd estimate
    deliberately persists (warm start), so run 2 is only required to be
    correct and no slower than run 1 — not instant."""
    cfg, params = tiny_dense
    srv = RealSBSServer(cfg, params, scheduler="sbs", max_len=MAX_LEN,
                        max_new=3, spec=shared_spec)
    t0 = time.monotonic()
    g1 = srv.serve(_mk_requests(cfg, seed=4), timeout=120)
    d1 = time.monotonic() - t0
    t0 = time.monotonic()
    g2 = srv.serve(_mk_requests(cfg, seed=4), timeout=120)
    d2 = time.monotonic() - t0
    assert len(g1) == len(g2) == 4
    assert [g.tokens for g in g1] == [g.tokens for g in g2]
    # the regression guarded here is a STALL (run 2 sleeping out the old
    # timeline — tens of seconds); allow generous wall-clock noise, this
    # is not a perf assertion
    assert d2 <= d1 * 2 + 2.0


# ---------------------------------------------------------------------------
# Paged admission: free blocks, not free slots
# ---------------------------------------------------------------------------

def _publish_handoffs(cfg, params, bus, reqs):
    """Stage every request on the handoff bus the way the prefill plane
    would (batch-1 cache + first token), marking generated=1."""
    cache_by_len = {}
    for r in reqs:
        if r.input_len not in cache_by_len:
            cache_by_len[r.input_len] = _chunked_prefill(
                cfg, params, list(r.tokens))
        t0, cache = cache_by_len[r.input_len]
        bus.publish(r.rid, cache, t0)
        r.generated = 1


@pytest.mark.paged
def test_paged_admission_by_free_blocks_not_slots(tiny_dense):
    """At EQUAL KV memory (max_batch × max_len tokens per DP) the paged
    engine must admit strictly more concurrent short requests than the
    padded engine, whose limit is its slot count."""
    cfg, params = tiny_dense
    rng = random.Random(5)
    reqs = [Request(rid=i, arrival_time=0.0, input_len=20, output_len=3,
                    tokens=tuple(rng.randrange(cfg.vocab_size)
                                 for _ in range(20)))
            for i in range(6)]

    def resident_after_joins(spec):
        bus = KVHandoffBus()
        _publish_handoffs(cfg, params, bus, reqs)
        eng = RealDecodeEngine(0, [0], spec, bus)
        st = DecodeDPState(dp_id=0, instance_id=0,
                           block_size=spec.block_size)
        free0 = eng.free_kv_tokens(0)
        assert free0 == 2 * MAX_LEN       # equal budget on both backends
        for r in reqs:
            r.generated = 1
            eng.admit(0, r)
        eng._apply_joins(0.0, [st])
        # the headroom probe tracks what admission consumed: slots×max_len
        # (padded) or reserved pages×block_size (paged)
        consumed = (len(eng._slot_of) * MAX_LEN if not spec.block_size
                    else sum(len(s.held[r.rid]) for s in eng._dp.values()
                             for r in reqs if r.rid in s.held) * BLOCK)
        assert eng.free_kv_tokens(0) == free0 - consumed
        return len(eng._slot_of)

    padded = EngineSpec(cfg, params, max_len=MAX_LEN, max_batch=2,
                        max_new=3)
    paged = EngineSpec(cfg, params, max_len=MAX_LEN, max_batch=2,
                       max_new=3, block_size=BLOCK, decode_slots=8)
    # same memory budget: pool holds exactly the padded plane's tokens
    assert (paged.paged_pool_blocks - 1) * BLOCK == 2 * MAX_LEN
    n_padded = resident_after_joins(padded)
    n_paged = resident_after_joins(paged)
    assert n_padded == 2                      # slot-bound
    # block-bound: ceil((20+3-1)/16) = 2 blocks per request, 12 usable
    assert n_paged == 6
    assert n_paged > n_padded


@pytest.mark.paged
def test_paged_join_defers_when_pool_exhausted(tiny_dense):
    """Over-admitted requests wait on the pending list (retried after
    each step) instead of corrupting live pages."""
    cfg, params = tiny_dense
    rng = random.Random(6)
    reqs = [Request(rid=i, arrival_time=0.0, input_len=40, output_len=3,
                    tokens=tuple(rng.randrange(cfg.vocab_size)
                                 for _ in range(40)))
            for i in range(4)]
    spec = EngineSpec(cfg, params, max_len=MAX_LEN, max_batch=1,
                      max_new=3, block_size=BLOCK, decode_slots=8)
    bus = KVHandoffBus()
    _publish_handoffs(cfg, params, bus, reqs)
    eng = RealDecodeEngine(0, [0], spec, bus)
    st = DecodeDPState(dp_id=0, instance_id=0, block_size=BLOCK)
    for r in reqs:
        eng.admit(0, r)
    eng._apply_joins(0.0, [st])
    # pool = 1*96/16 = 6 usable blocks; each request needs ceil(42/16)=3
    assert len(eng._slot_of) == 2
    assert len(eng._pending) == 2             # deferred, not dropped
    assert eng.has_work()


@pytest.mark.paged
def test_paged_drain_migrates_and_frees_pages(tiny_dense):
    """Watchdog drain on a paged engine re-parks residents as DENSE
    batch-1 caches on the bus (the cross-plane handoff format), clears
    their table rows, and returns every page to the pool — a drained
    request can re-join (padded or paged) with generation state intact."""
    cfg, params = tiny_dense
    rng = random.Random(8)
    reqs = [Request(rid=i, arrival_time=0.0, input_len=25, output_len=4,
                    tokens=tuple(rng.randrange(cfg.vocab_size)
                                 for _ in range(25)))
            for i in range(2)]
    spec = EngineSpec(cfg, params, max_len=MAX_LEN, max_batch=2,
                      max_new=4, block_size=BLOCK)
    bus = KVHandoffBus()
    _publish_handoffs(cfg, params, bus, reqs)
    eng = RealDecodeEngine(0, [0], spec, bus)
    st = DecodeDPState(dp_id=0, instance_id=0, block_size=BLOCK)
    for r in reqs:
        eng.admit(0, r)
    eng._apply_joins(0.0, [st])
    assert len(eng._slot_of) == 2
    pool = eng._dp[0].pool
    assert pool.used_count > 0
    out = eng.drain()
    assert sorted(r.rid for rs in out.values() for r in rs) == [0, 1]
    pool.check()
    assert pool.used_count == 0                # every page came back
    assert not eng._slot_of and not eng._dp[0].occupied()
    for r in reqs:
        gen = bus.gen(r.rid)
        assert gen.cache is not None           # re-parked, dense format
        assert gen.cache["kv_pos"].shape == (1, MAX_LEN)
        assert int(gen.cache["cur"][0]) == r.input_len


# ---------------------------------------------------------------------------
# Worker-error surfacing (RealtimeEventLoop regression)
# ---------------------------------------------------------------------------

def test_decode_worker_error_surfaces_within_window(tiny_dense):
    """A failing DECODE forward on the engine worker thread must raise
    out of serve() within one scheduling window of the failure — the
    loop may not sleep out the remaining timeout horizon.  (The prefill
    twin lives in test_worker_error_surfaces_promptly; this one covers
    the step_end path, which reaches the runtime via a different
    completion event.)"""
    cfg, params = tiny_dense
    spec = EngineSpec(cfg, params, max_len=MAX_LEN, max_batch=4, max_new=3)

    def boom(p, t, c):
        raise RuntimeError("decode boom")

    spec.jit_decode = boom
    srv = RealSBSServer(cfg, params, scheduler="sbs", max_len=MAX_LEN,
                        max_new=3, spec=spec)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="decode boom"):
        srv.serve(_mk_requests(cfg, n=2), timeout=120)
    elapsed = time.monotonic() - t0
    # prefill (healthy) + one failed step, orders of magnitude below the
    # 120s horizon the old busy-wait would have slept out
    assert elapsed < 30


@pytest.mark.paged
def test_paged_decode_worker_error_surfaces_within_window(tiny_dense):
    """Same regression over the paged step path."""
    cfg, params = tiny_dense
    spec = EngineSpec(cfg, params, max_len=MAX_LEN, max_batch=4, max_new=3,
                      block_size=BLOCK)

    def boom(p, t, c):
        raise RuntimeError("paged boom")

    spec.jit_paged_decode = boom
    scfg = ServingConfig(num_prefill_instances=2, prefill_dp_per_instance=2,
                         num_decode_instances=1, decode_dp_per_instance=2,
                         chunk_size=32, t_default=0.05, l_net=0.001,
                         max_batch_per_dp=4, block_size=BLOCK)
    srv = RealSBSServer(cfg, params, serving_cfg=scfg, scheduler="sbs",
                        max_len=MAX_LEN, max_new=3, spec=spec)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="paged boom"):
        srv.serve(_mk_requests(cfg, n=2), timeout=120)
    assert time.monotonic() - t0 < 30


# ---------------------------------------------------------------------------
# Cross-plane equivalence sweep (sim/real × padded/paged)
# ---------------------------------------------------------------------------

def _oracle_tokens(cfg, params, req, cache_ref):
    """Seed-server reference generation for one request (memoized)."""
    if req.rid not in cache_ref:
        t0, cache = _chunked_prefill(cfg, params, list(req.tokens))
        cache_ref[req.rid] = _serial_decode(cfg, params, t0, cache,
                                            req.output_len)[0]
    return cache_ref[req.rid]


@pytest.mark.paged
@pytest.mark.slow
@pytest.mark.parametrize("plane", ["sim-padded", "sim-paged",
                                   "real-padded", "real-paged"])
def test_cross_plane_equivalence(tiny_dense, plane):
    """One workload, four deployments.  Conservation must hold on every
    plane (requests in == completions; no KV tokens or blocks outlive
    their request) and the real planes must be token-exact against the
    seed serial decode — which also makes real-padded and real-paged
    token-exact against each other."""
    from repro.serving.e2e import PDClusterSim

    cfg, params = tiny_dense
    kind, backend = plane.split("-")
    scfg = ServingConfig(num_prefill_instances=2, prefill_dp_per_instance=2,
                         num_decode_instances=1, decode_dp_per_instance=2,
                         chunk_size=32, t_default=0.05, l_net=0.001,
                         max_batch_per_dp=4,
                         block_size=BLOCK if backend == "paged" else 0)
    reqs = _mk_requests(cfg, n=5, out_len=3, seed=11)

    if kind == "sim":
        sim = PDClusterSim(cfg, scfg, scheduler="sbs")
        sim.run(reqs, duration=2.0)
        state = sim.state
        engines = sim.decode
    else:
        srv = RealSBSServer(cfg, params, serving_cfg=scfg, scheduler="sbs",
                            max_len=MAX_LEN, max_new=3)
        gens = srv.serve(reqs, timeout=120)
        state = srv.state
        engines = srv.decode_engines
        # token-exact vs the seed serial decode
        oracle_cache = {}
        assert sorted(g.rid for g in gens) == [r.rid for r in reqs]
        for g, r in zip(gens, reqs):
            assert g.tokens == _oracle_tokens(cfg, params, r, oracle_cache)
        # device-side pools fully drained
        for e in engines:
            for st in e._dp.values():
                if scfg.block_size:
                    st.pool.check()
                    assert st.pool.used_count == 0
                assert not st.occupied()

    # requests in == completions (every request finished exactly once)
    assert all(r.finish_time is not None for r in reqs)
    assert all(r.generated == r.output_len for r in reqs)
    # no KV tokens (or reserved blocks) outlive their request
    assert sum(d.kv_tokens for d in state.decode_dps) == 0
    assert sum(d.batch for d in state.decode_dps) == 0
    assert sum(d.kv_blocks for d in state.decode_dps) == 0
    # decode plane emitted exactly the non-prefill tokens
    decoded = sum(e.tokens_generated for e in engines)
    first_from_prefill = 1 if kind == "real" else 0
    assert decoded == sum(r.output_len - first_from_prefill for r in reqs)


@pytest.mark.parametrize("scheduler", ["sbs-la", "immediate"])
def test_serve_real_matches_greedy_reference(scheduler):
    """The entry point `chip_smoke.py` drives (`launch.serve.serve_real`),
    at the reduced granite preset: every request finishes with its full
    token count, and its tokens equal a single-request greedy decode of
    the same params."""
    from repro.launch.serve import greedy_mismatches, serve_real
    run = serve_real("granite-moe-1b-a400m", reduced=True,
                     schedulers=(scheduler,), n_requests=4, output_len=4,
                     timeout=120)
    gens = run.generations[scheduler]
    assert [g.rid for g in gens] == [0, 1, 2, 3]
    assert run.spec.paged and run.spec.block_size == 16
    assert greedy_mismatches(run.cfg, run.params, run.requests[scheduler],
                             gens) == []

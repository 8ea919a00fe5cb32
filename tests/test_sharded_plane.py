"""Sharded real plane: mesh-native engines must be TOKEN-EXACT vs the
single-device plane.

Multi-device cases run in subprocesses with
``--xla_force_host_platform_device_count`` forced (the count must be
pinned before jax initializes, and the suite's own process runs on the
normal 1-device platform), mirroring ``tests/test_distributed.py``.
Exactness holds because the engine meshes here are data-only (no tensor
parallelism, so no reduction-order drift) and the MoE capacity factor is
non-binding at these batch sizes — every token keeps its top-k experts
through the EP all-to-all path.
"""
import os
import subprocess
import sys

import pytest

from repro.serving.kv_pool import BlockPool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.sharded


def _sub(code: str, n_dev: int = 4, timeout: int = 420) -> str:
    env = {**os.environ, "PYTHONPATH": "src",
           "XLA_FLAGS": (f"--xla_force_host_platform_device_count={n_dev} "
                         + os.environ.get("XLA_FLAGS", ""))}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


_PRELUDE = """
import jax, random
from repro.config.base import get_arch, ServingConfig
from repro.core.types import Request
from repro.launch.mesh import make_engine_mesh
from repro.models.model import init_params
from repro.serving.server import RealSBSServer

cfg = get_arch("granite-moe-1b-a400m", reduced=True)
params = init_params(cfg, jax.random.PRNGKey(0))

def reqs():
    rng = random.Random(7)
    return [Request(rid=i, input_len=16, output_len=5,
                    arrival_time=0.02 * i,
                    tokens=[rng.randrange(cfg.vocab_size)
                            for _ in range(16)])
            for i in range(6)]

def serve_pair(scfg):
    mesh = make_engine_mesh(4)
    srv_s = RealSBSServer(cfg, params, serving_cfg=scfg, scheduler="sbs",
                          max_len=64, max_new=5, mesh=mesh)
    gens_s = srv_s.serve(reqs(), timeout=120)
    srv_1 = RealSBSServer(cfg, params, serving_cfg=scfg, scheduler="sbs",
                          max_len=64, max_new=5)
    gens_1 = srv_1.serve(reqs(), timeout=120)
    ts = {g.rid: g.tokens for g in gens_s}
    t1 = {g.rid: g.tokens for g in gens_1}
    assert set(ts) == set(t1) == set(range(6)), (set(ts), set(t1))
    assert ts == t1, (ts, t1)
    return srv_s
"""


def test_sharded_pd_plane_token_exact():
    """P/D deployment on a 4-device data mesh (merged decode cache, EP
    all-to-all in every step) generates the SAME tokens as the
    single-device paged plane, end to end through the server."""
    _sub(_PRELUDE + """
scfg = ServingConfig(num_prefill_instances=1, prefill_dp_per_instance=1,
                     num_decode_instances=1, decode_dp_per_instance=4,
                     chunk_size=32, t_default=0.05, l_net=0.001,
                     max_batch_per_dp=2, block_size=8)
srv = serve_pair(scfg)
eng = srv.decode_engines[0]
assert eng.step_samples, "sharded decode never stepped"
# merged plane: every sample covers the whole instance-wide slot axis
assert all(r == len(eng._group.slots) for _d, _a, r in eng.step_samples)
print("PD-EXACT-OK")
""")


def test_sharded_mixed_plane_token_exact():
    """Unified mixed-batch deployment (chunked prefill piggybacked into
    the merged cross-DP step) is token-exact vs single-device, and the
    sharded leg actually exercised fused mixed steps."""
    _sub(_PRELUDE + """
scfg = ServingConfig(num_prefill_instances=1, prefill_dp_per_instance=1,
                     num_decode_instances=1, decode_dp_per_instance=4,
                     chunk_size=32, t_default=0.05, l_net=0.001,
                     max_batch_per_dp=2, block_size=8, mixed_batch=True,
                     mixed_chunk=32)
srv = serve_pair(scfg)
eng = srv.decode_engines[0]
assert eng.mixed_steps > 0, "no fused mixed step ran"
print("MIXED-EXACT-OK")
""")


def test_serve_real_sharded_matches_one_device():
    """`chip_smoke.py --chips 4` at the reduced preset: `serve_real` on a
    4-device mesh and the same deployment (decode DP = 4) on one device
    give the same tokens, and each sharded cache shape is built by one
    jitted program however many caches of it the deployment makes."""
    out = _sub("""
from repro.launch.mesh import make_engine_mesh
from repro.launch.serve import serve_real

kw = dict(reduced=True, n_requests=4, output_len=4, timeout=120)
sh = serve_real("granite-moe-1b-a400m", mesh=make_engine_mesh(4), **kw)
one = serve_real("granite-moe-1b-a400m", params=sh.params,
                 decode_dp=sh.spec.n_dp, **kw)
assert sh.spec.n_dp == 4 and one.spec.mesh is None
for run in (sh, one):
    assert run.servers["sbs-la"].scfg.decode_dp_per_instance == 4
ts = {g.rid: g.tokens for g in sh.generations["sbs-la"]}
t1 = {g.rid: g.tokens for g in one.generations["sbs-la"]}
assert set(ts) == {0, 1, 2, 3} and ts == t1, (ts, t1)
before = dict(sh.spec._cache_builders)
sh.spec.merged_paged_cache()
assert sh.spec._cache_builders == before, "cache builder re-jitted"
print("SERVE-REAL-SHARDED-OK", len(before))
""")
    assert "SERVE-REAL-SHARDED-OK" in out


def test_sharded_step_has_ep_all_to_all():
    """The compiled merged decode step is a genuine mesh program: the
    explicit EP all-to-all appears in its HLO, and the output cache
    stays sharded over the data axis."""
    _sub("""
import jax
import jax.numpy as jnp
from repro.config.base import get_arch
from repro.launch.mesh import make_engine_mesh
from repro.models.model import init_params
from repro.serving.real_engine import EngineSpec

cfg = get_arch("granite-moe-1b-a400m", reduced=True)
params = init_params(cfg, jax.random.PRNGKey(0))
spec = EngineSpec(cfg, params, max_len=64, max_batch=2, block_size=8,
                  mesh=make_engine_mesh(4))
cache = spec.merged_paged_cache()
toks = jnp.zeros((cache["cur"].shape[0], 1), jnp.int32)
hlo = spec.jit_paged_decode.lower(
    spec.params, toks, cache).compile().as_text()
assert "all-to-all" in hlo, "EP shard_map path not active"
_lg, out = spec.jit_paged_decode(spec.params, toks, cache)
assert "data" in str(out["cur"].sharding.spec), out["cur"].sharding
print("EP-HLO-OK")
""")


def test_deepseek_dry_run_shapes():
    """deepseek-v3-671b (reduced geometry, MLA + shared-expert MoE)
    lowers and compiles through the sharded decode step on a 4-device
    data mesh with the EP all-to-all active — the dry-run shape check of
    the production config's engine layout."""
    _sub("""
import jax
import jax.numpy as jnp
from repro.config.base import get_arch
from repro.launch.mesh import make_engine_mesh
from repro.models.model import init_params
from repro.serving.real_engine import EngineSpec

cfg = get_arch("deepseek-v3-671b", reduced=True)
params = init_params(cfg, jax.random.PRNGKey(0))
spec = EngineSpec(cfg, params, max_len=64, max_batch=2, block_size=8,
                  mesh=make_engine_mesh(4))
cache = spec.merged_paged_cache()
toks = jnp.zeros((cache["cur"].shape[0], 1), jnp.int32)
hlo = spec.jit_paged_decode.lower(
    spec.params, toks, cache).compile().as_text()
assert "all-to-all" in hlo, "EP path inactive for deepseek config"
print("DSV3-DRYRUN-OK")
""")


def test_block_pool_base_offsets():
    """Per-DP pools with disjoint base offsets issue GLOBAL block ids
    (the merged-cache contract: DP k owns [k*B, (k+1)*B))."""
    pools = [BlockPool(8, 4, base=k * 8) for k in range(3)]
    seen = set()
    for k, p in enumerate(pools):
        ids = p.alloc(p.free_count)
        assert all(k * 8 < i < (k + 1) * 8 for i in ids), (k, ids)
        assert not (set(ids) & seen)
        seen.update(ids)
        p.free(ids)
        p.check()
    with pytest.raises(ValueError):
        BlockPool(8, 4, base=-1)

"""Ahead-of-time compiles for a TPU v5e chip that is described, not attached.

The TPU compiler is installed with JAX, so these tests catch what the
chip's compiler refuses and interpret mode cannot see: Pallas block shapes
off the (8, 128) tiling, more VMEM than a kernel may use, a serving step
that does not fit the chip's 16 GB.  Nothing runs; no result or time is
checked.  The topology is described only inside the `topo` fixture (never
at import), so every pytest-xdist worker collects the same tests and only
the worker running this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config.base import get_arch
from repro.models import abstract_params, paged_decode_step, paged_prefill_step
from repro.models.model import init_paged_cache, paged_layout

HBM_BYTES = 16 * 10**9          # one TPU v5e chip
H, K, HD, BS = 16, 8, 64, 16    # granite-moe-1b-a400m attention, block 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _specs(one_chip, *shapes):
    return [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]


def _kernel_case(name):
    f32, i32 = jnp.float32, jnp.int32
    if name == "paged_decode":
        from repro.kernels.decode_attention.kernel import (
            paged_decode_attention_pallas as fn)
        B, N, nbt = 16, 513, 32
        return fn, [((B, H, HD), f32), ((N, BS, K, HD), f32),
                    ((N, BS, K, HD), f32), ((N, BS), i32), ((B, nbt), i32),
                    ((B,), i32)]
    if name == "dense_decode":
        from repro.kernels.decode_attention.kernel import (
            decode_attention_pallas as fn)
        B, S = 16, 512
        return fn, [((B, H, HD), f32), ((B, S, K, HD), f32),
                    ((B, S, K, HD), f32), ((B, S), i32), ((B,), i32)]
    if name == "flash_prefill":
        from repro.kernels.flash_prefill.kernel import (
            flash_prefill_pallas as fn)
        S = 512
        return fn, [((1, S, H, HD), f32), ((1, S, K, HD), f32),
                    ((1, S, K, HD), f32)] + [((1, S), i32)] * 4
    # ssd_scan at mamba2-370m widths: 32 heads of 64, state 128, chunk 256
    from repro.kernels.ssd_scan.kernel import ssd_chunk_pallas as fn
    B, nc, Q, nh, hp, ds = 1, 4, 256, 32, 64, 128
    return fn, [((B, nc, Q, nh, hp), f32), ((B, nc, Q, nh), f32),
                ((nh,), f32), ((B, nc, Q, ds), f32), ((B, nc, Q, ds), f32)]


@pytest.mark.parametrize("name", ["paged_decode", "dense_decode",
                                  "flash_prefill", "ssd_scan"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_case(name)
    compiled = jax.jit(fn).lower(*_specs(one_chip, *shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _granite_step_args(one_chip, slots, max_len):
    cfg = get_arch("granite-moe-1b-a400m")
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        abstract_params(cfg, jnp.float32))
    blocks = slots // 2 * max_len // BS + 1      # the engine's pool size
    cache = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_paged_cache(cfg, slots, blocks, max_len,
                                                BS)))
    return cfg, params, cache


def _fits_one_chip(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used


def test_granite_paged_decode_step_fits_v5e(one_chip):
    """The full-width decode step the server runs: 16 slots, max_len 512."""
    cfg, params, cache = _granite_step_args(one_chip, slots=16, max_len=512)
    tok = _specs(one_chip, ((16, 1), jnp.int32))[0]
    compiled = jax.jit(
        lambda p, t, c: paged_decode_step(cfg, p, t, c)).lower(
        params, tok, cache).compile()
    _fits_one_chip(compiled)


def test_granite_paged_prefill_chunk_fits_v5e(one_chip):
    """One 64-token page-native prefill chunk at full width."""
    cfg, params, cache = _granite_step_args(one_chip, slots=16, max_len=512)
    assert paged_layout(cfg, 512, BS)[0] == 512 // BS
    tok, slot = _specs(one_chip, ((1, 64), jnp.int32), ((), jnp.int32))
    compiled = jax.jit(
        lambda p, t, c, s: paged_prefill_step(cfg, p, t, c, s)).lower(
        params, tok, cache, slot).compile()
    _fits_one_chip(compiled)

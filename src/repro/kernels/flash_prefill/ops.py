"""Public jit'd wrapper for the flash_prefill kernel.

On the CPU backend the kernel body executes in interpret mode; on TPU
it lowers through Mosaic with the BlockSpec VMEM tiling in kernel.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_prefill.kernel import flash_prefill_pallas


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_kv", "interpret"))
def flash_prefill(q, k, v, q_pos, kv_pos, q_seg, kv_seg, *,
                  causal: bool = True, window: int = 0,
                  block_q: int = 128, block_kv: int = 256,
                  interpret: bool | None = None):
    if interpret is None:
        interpret = _on_cpu()
    return flash_prefill_pallas(
        q, k, v, q_pos, kv_pos, q_seg, kv_seg,
        causal=causal, window=window, block_q=block_q, block_kv=block_kv,
        interpret=interpret)

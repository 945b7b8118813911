"""Public wrapper for the batched ternary-LoRA matmul.

The fused Pallas kernel runs every 2-D (decode) call on an accelerator; the
XLA reference (gather + two einsums — still packed 2-bit in HBM) covers the
batched-prefill 3-D case and the CPU, where the kernel would only run in
the slow interpreter (tests drive it there directly).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.batched_lora.batched_lora import batched_lora_matmul
from repro.kernels.batched_lora.ref import batched_lora_ref


@functools.partial(jax.jit, static_argnames=("use_kernel", "out_dtype"))
def batched_lora(
    x: jax.Array,          # (B, ..., K)
    a_codes: jax.Array,    # (R, K//4, r)
    b_codes: jax.Array,    # (R, r//4, N)
    scales: jax.Array,     # (R,)
    idx: jax.Array,        # (B,)
    *,
    use_kernel: bool = True,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Per-slot LoRA contribution ``y[b] = (x[b]·A[idx[b]])·B[idx[b]]·s[idx[b]]``."""
    if x.ndim == 3 and x.shape[1] == 1:
        # the decode hot path carries a singleton seq axis ((B, 1, K) from
        # x[:, None] in the attention projections) — squeeze so it takes
        # the fused kernel instead of the 3-D prefill reference
        y = batched_lora(x[:, 0], a_codes, b_codes, scales, idx,
                         use_kernel=use_kernel, out_dtype=out_dtype)
        return y[:, None]
    if use_kernel and x.ndim == 2 and jax.default_backend() != "cpu":
        return batched_lora_matmul(x, a_codes, b_codes, scales, idx,
                                   out_dtype=out_dtype)
    return batched_lora_ref(x, a_codes, b_codes, scales, idx, out_dtype=out_dtype)

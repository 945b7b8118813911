"""Public jit'd wrapper for the flash-decode kernel: padding, GQA folding,
fp8 KV handling and backend dispatch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_decode.flash_decode import flash_decode as _pallas_decode
from repro.kernels.flash_decode.paged import (paged_flash_decode,
                                              paged_flash_decode_ref)
from repro.kernels.flash_decode.ref import flash_decode_ref


@functools.partial(jax.jit, static_argnames=("block_s", "use_kernel", "interpret", "out_dtype"))
def decode_attention(
    q: jax.Array,          # (B, Hq, D)
    k: jax.Array,          # (B, Hkv, S, D)  (fp8 or bf16/f32)
    v: jax.Array,
    length: jax.Array,     # int32 ()
    kv_scale: jax.Array = 1.0,
    *,
    block_s: int = 512,
    use_kernel: bool = True,
    interpret: bool | None = None,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Single-token GQA decode attention over a (padded) KV cache.

    Pads S to a block multiple (masked via `length`), folds query groups so
    the kernel's score matmul has M=G, and widens fp8 KV inside the kernel.
    """
    b, hq, d = q.shape
    _, hkv, s_len, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d)

    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    bs = min(block_s, max(128, s_len))
    pad = (-s_len) % bs
    if pad:
        widths = ((0, 0), (0, 0), (0, pad), (0, 0))
        k = jnp.pad(k.astype(jnp.float32) if k.dtype == jnp.float8_e4m3fn else k, widths)
        v = jnp.pad(v.astype(jnp.float32) if v.dtype == jnp.float8_e4m3fn else v, widths)

    if use_kernel:
        out = _pallas_decode(
            qg, k, v, length, kv_scale,
            block_s=bs, out_dtype=out_dtype, interpret=interpret,
        )
    else:
        out = flash_decode_ref(qg, k, v, length, kv_scale, out_dtype=out_dtype)
    return out.reshape(b, hq, d)


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret", "out_dtype"))
def paged_decode_attention(
    q: jax.Array,          # (B, Hq, D)
    k_pool: jax.Array,     # (L, n_pages, Hkv, page, D)  shared PagePool
    v_pool: jax.Array,
    tables: jax.Array,     # (B, n_p) int32 block tables (pad → scratch page)
    lengths: jax.Array,    # (B,) int32 live context length per sequence
    kv_scale: jax.Array = 1.0,
    *,
    layer: jax.Array,      # int32 () layer of the pool to attend over
    use_kernel: bool = True,
    interpret: bool | None = None,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Single-token GQA decode attention straight off the paged KV pool.

    The serving engine's block tables (`PagePool.batch_tables`) drive the
    kernel's page-shaped context loop via scalar prefetch — no contiguous
    gather. The kernel reads ``layer`` of the whole pool in place (a
    one-layer pool is ``pool[None]`` with layer 0). fp8 pools are widened
    per-tile inside the kernel."""
    b, hq, d = q.shape
    _, _, hkv, _, _ = k_pool.shape
    assert hq % hkv == 0, (hq, hkv)
    qg = q.reshape(b, hkv, hq // hkv, d)

    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    if k_pool.dtype == jnp.float8_e4m3fn:
        # interpret-mode dot_generals reject fp8 inputs; widen outside
        if interpret:
            k_pool = k_pool.astype(jnp.float32)
            v_pool = v_pool.astype(jnp.float32)

    if use_kernel:
        out = paged_flash_decode(qg, k_pool, v_pool, tables, lengths, kv_scale,
                                 layer, out_dtype=out_dtype,
                                 interpret=interpret)
    else:
        out = paged_flash_decode_ref(qg, k_pool[layer], v_pool[layer], tables,
                                     lengths, kv_scale, out_dtype=out_dtype)
    return out.reshape(b, hq, d)

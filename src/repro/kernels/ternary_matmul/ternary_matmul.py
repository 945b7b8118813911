"""Pallas TPU kernel: packed-ternary × activation matmul (paper C1→TPU).

The TPU adaptation of TOM's sparsity-aware ROM: weights live in HBM as 2-bit
codes (4/byte); each grid step streams a packed K-tile into VMEM, decodes it
with bitwise ops ("the combinational logic"), widens to the activation dtype
and feeds the MXU. Weight bytes moved are 8× less than bf16 / 2× less than
int4 — in the memory-bound decode regime this moves the memory-roofline term
by the same factor, which is precisely the paper's density argument.

Two decode layouts (see core/ternary.py):
 - interleaved: stack(4 slots, axis=-2) + reshape — a sublane interleave.
 - strided: concatenate(4 slots, axis=-2) — no interleave; cheaper lowering.

Grid: (M/bm, N/bn, K/bk) with K innermost ('arbitrary'), f32 VMEM accumulator,
scale applied once on the final K step from SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def decode_slot(codes: jax.Array, s: int, dtype) -> jax.Array:
    """Slot ``s`` of integer 2-bit codes → ternary {−1, 0, +1} in ``dtype``
    ('01'→+1, '10'→−1, '00'→0: conditional negation, no multiplier)."""
    c = (codes >> (2 * s)) & 3
    return (c & 1).astype(dtype) - (c >> 1).astype(dtype)


def _decode_tile(codes: jax.Array, layout: str, bk: int, bn: int, dtype) -> jax.Array:
    """uint8 (bk//4, bn) 2-bit codes → (bk, bn) ±1/0 in `dtype`."""
    slots = [decode_slot(codes, s, jnp.int8) for s in range(4)]
    if layout == "interleaved":
        w = jnp.stack(slots, axis=1).reshape(bk, bn)
    else:  # strided: slot s covers rows [s*bk/4, (s+1)*bk/4) of the tile
        w = jnp.concatenate(slots, axis=0)
    return w.astype(dtype)


def _kernel(x_ref, p_ref, scale_ref, o_ref, acc_ref, *, layout: str, bk: int, bn: int,
            n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    w = _decode_tile(p_ref[...], layout, bk, bn, x.dtype)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * scale_ref[0]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("layout", "block_m", "block_n", "block_k", "out_dtype", "interpret"),
)
def ternary_matmul(
    x: jax.Array,
    packed: jax.Array,
    scale: jax.Array,
    *,
    layout: str = "interleaved",
    block_m: int = 128,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """``x (M,K) · W (K,N) * scale`` with W given as packed 2-bit ternary (K//4, N).

    Shapes must be divisible by the block sizes (ops.py pads). For the strided
    layout the pack tile must equal ``block_k``.
    """
    m, kdim = x.shape
    kq, n = packed.shape
    assert kq * 4 == kdim, (kq, kdim)
    n_k = kdim // block_k
    scale = jnp.asarray(scale, jnp.float32).reshape(1)

    grid = (m // block_m, n // block_n, n_k)
    kernel = functools.partial(_kernel, layout=layout, bk=block_k, bn=block_n, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k // 4, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, packed, scale)

"""Pallas TPU kernel: batched multi-adapter ternary-LoRA matmul.

The SGMV analogue for TOM's SRAM adapters: the decode batch mixes slots that
run *different* frozen fine-tunes, so each grid step resolves its row's
adapter through **scalar prefetch** (the same indirection idiom as
`flash_decode/paged.py`'s block tables) — the A/B BlockSpec index maps pick
which adapter's packed 2-bit tile to DMA HBM→VMEM before the body runs. The
tile is decoded in-registers ("the combinational logic"), so adapter weight
bytes moved stay at the 2-bit ROM density even with many tenants resident.

Grid: (B,) — one step per decode slot; both LoRA products are rank-narrow
(r ≤ 64), so one step fuses decode(A) → x·A → decode(B) → z·B → ·s entirely
in VMEM. Per-adapter combined scales ride in SMEM via the second scalar-
prefetch operand.

Layout. Packed row ``j`` of a code matrix holds unpacked rows ``4j+s`` in
its four 2-bit slots ``s``. Interleaving the slots back into rows inside
the kernel is a sublane shape cast the TPU compiler refuses, so the kernel
never forms the unpacked matrices. The wrapper instead splits ``x`` by
slot (``x_t[i] = x[4i+t]``) and transposes A with its rank rows grouped by
B's packing slot; each slot pair is then a broadcast multiply and a sum on
the vector unit (the products are K·r and r·N multiply-adds per row — far
too narrow for the MXU to matter).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ternary_matmul.ternary_matmul import decode_slot


def _kernel(idx_ref, s_ref, x_ref, a_ref, b_ref, o_ref):
    bi = pl.program_id(0)
    b_codes = b_ref[0].astype(jnp.int32)                      # (r/4, N)
    y = jnp.zeros(o_ref.shape[1:], jnp.float32)               # (1, N)
    for s in range(4):          # B row slot s consumes z[4j+s], j < r/4
        a_codes = a_ref[0, s].astype(jnp.int32)               # (r/4, K/4)
        z_s = jnp.zeros((a_codes.shape[0], 1), jnp.float32)   # (r/4, 1)
        for t in range(4):      # A's K slot t meets x[4i+t], i < K/4
            x_t = x_ref[0, t].astype(jnp.float32)             # (1, K/4)
            a_t = decode_slot(a_codes, t, jnp.float32)
            z_s += jnp.sum(a_t * x_t, axis=1, keepdims=True)
        b_s = decode_slot(b_codes, s, jnp.float32)
        y += jnp.sum(b_s * z_s, axis=0, keepdims=True)
    o_ref[0] = (y * s_ref[idx_ref[bi]]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def batched_lora_matmul(
    x: jax.Array,          # (B, K) one activation row per decode slot
    a_codes: jax.Array,    # (R, K//4, r) uint8 packed ternary A stacks
    b_codes: jax.Array,    # (R, r//4, N) uint8 packed ternary B stacks
    scales: jax.Array,     # (R,) f32 combined per-adapter scale
    idx: jax.Array,        # (B,) int32 adapter slot per row (0 = null adapter)
    *,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    bsz, k = x.shape
    n_adapters, kq, r = a_codes.shape
    rq, n = b_codes.shape[-2:]
    assert kq * 4 == k, (kq, k)
    assert rq * 4 == r, (rq, r)

    idx = jnp.asarray(idx, jnp.int32).reshape(bsz)
    scales = jnp.asarray(scales, jnp.float32).reshape(n_adapters)
    # x by K slot: x_slots[b, t, 0, i] = x[b, 4i + t]
    x_slots = x.reshape(bsz, kq, 4).transpose(0, 2, 1).reshape(bsz, 4, 1, kq)
    # Aᵀ by B's rank slot: a_t[a, s, j, :] = Aᵀ row 4j + s (K still packed)
    a_t = (a_codes.transpose(0, 2, 1).reshape(n_adapters, rq, 4, kq)
           .transpose(0, 2, 1, 3))

    # Every block's last two dims equal the array's, which the TPU compiler
    # accepts at any width.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # idx, scales
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((1, 4, 1, kq), lambda b, i, s: (b, 0, 0, 0)),
            # the multi-tenant indirection: this row's adapter tile, not a
            # contiguous adapter axis
            pl.BlockSpec((1, 4, rq, kq), lambda b, i, s: (i[b], 0, 0, 0)),
            pl.BlockSpec((1, rq, n), lambda b, i, s: (i[b], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, n), lambda b, i, s: (b, 0, 0)),
    )
    y = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, 1, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="batched_lora_matmul",
    )(idx, scales, x_slots, a_t, b_codes)
    return y.reshape(bsz, n)

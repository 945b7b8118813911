"""Pallas TPU kernel: paged flash-decode over a block-table-indexed KV pool.

The page-shaped twin of `flash_decode.py`: instead of a contiguous
(B, Hkv, S, D) cache, each sequence owns a *block table* of page ids into a
shared pool (serving/paged_kv.py — vLLM-style paging over the paper's
distributed-SRAM KV). The context grid axis walks the table; the block-table
entry is resolved through **scalar prefetch** (`PrefetchScalarGridSpec`), so
the k/v BlockSpec index maps pick which pool page to DMA HBM→VMEM *before*
the kernel body runs — no host-side gather ever materializes the contiguous
view. `block_s == page`: the kernel's context loop is already page-shaped,
which is exactly the integration point the pool was designed for.

Per-sequence live lengths ride in as the second scalar-prefetch operand and
mask the table's padded tail (pad slots may point at any page — commonly the
pool's scratch page — their scores are masked to -inf, contributing exactly
0 after the online softmax).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tables_ref, len_ref, q_ref, k_ref, v_ref, kvs_ref, o_ref,
            m_ref, d_ref, acc_ref, *, page: int, n_p: int, scale: float):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        d_ref[...] = jnp.zeros_like(d_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                    # (G, D)
    k = k_ref[0, 0].astype(jnp.float32) * kvs_ref[0]       # (page, D)
    v = v_ref[0, 0].astype(jnp.float32) * kvs_ref[0]       # (page, D)

    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                              # (G, page)

    # mask positions beyond this sequence's live length
    pos = p * page + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(pos < len_ref[b], scores, NEG_INF)

    m_prev = m_ref[...]                                    # (G, 128) lane-replicated
    m_cur = jnp.max(scores, axis=-1, keepdims=True)        # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    corr = jnp.exp(m_prev[:, :1] - m_new[:, :1])           # (G, 1)
    pr = jnp.exp(scores - m_new[:, :1])                    # (G, page)

    d_ref[...] = d_ref[...] * corr + jnp.sum(pr, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        pr, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(p == n_p - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(d_ref[:, :1], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "out_dtype", "interpret"),
)
def paged_flash_decode(
    q: jax.Array,         # (B, Hkv, G, D)
    k_pool: jax.Array,    # (n_pages, Hkv, page, D)  shared pool (fp8 or wider)
    v_pool: jax.Array,
    tables: jax.Array,    # (B, n_p) int32 — per-sequence block tables (padded)
    lengths: jax.Array,   # (B,) int32 — live context length per sequence
    kv_scale: jax.Array,  # f32 () — fp8 dequant scale (1.0 when KV is bf16)
    *,
    scale: float | None = None,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    b, hkv, g, d = q.shape
    _, _, page, _ = k_pool.shape
    n_p = tables.shape[1]
    scale = scale if scale is not None else d ** -0.5

    tables = jnp.asarray(tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32).reshape(b)
    kv_scale = jnp.asarray(kv_scale, jnp.float32).reshape(1)

    kernel = functools.partial(_kernel, page=page, n_p=n_p, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # tables, lengths
        grid=(b, hkv, n_p),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b, h, p, t, l: (b, h, 0, 0)),
            # the paged indirection: the context step's block comes from the
            # sequence's block table, not from a contiguous S axis
            pl.BlockSpec((1, 1, page, d), lambda b, h, p, t, l: (t[b, p], h, 0, 0)),
            pl.BlockSpec((1, 1, page, d), lambda b, h, p, t, l: (t[b, p], h, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b, h, p, t, l: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),  # running max (lane-replicated)
            pltpu.VMEM((g, 128), jnp.float32),  # running denom
            pltpu.VMEM((g, d), jnp.float32),    # running output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_flash_decode",
    )(tables, lengths, q, k_pool, v_pool, kv_scale)


def paged_flash_decode_ref(q, k_pool, v_pool, tables, lengths, kv_scale=1.0,
                           *, scale=None, out_dtype=jnp.float32):
    """Oracle: gather the contiguous view per sequence, then dense softmax."""
    b, hkv, g, d = q.shape
    _, _, page, _ = k_pool.shape
    n_p = tables.shape[1]
    scale = scale if scale is not None else d ** -0.5
    # (B, P, H, page, D) → (B, H, P*page, D)
    kf = (k_pool[tables].astype(jnp.float32) * kv_scale
          ).transpose(0, 2, 1, 3, 4).reshape(b, hkv, n_p * page, d)
    vf = (v_pool[tables].astype(jnp.float32) * kv_scale
          ).transpose(0, 2, 1, 3, 4).reshape(b, hkv, n_p * page, d)
    s = jnp.einsum("bhgd,bhsd->bhgs", q.astype(jnp.float32), kf) * scale
    mask = jnp.arange(n_p * page)[None, :] < lengths[:, None]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgs,bhsd->bhgd", p, vf).astype(out_dtype)

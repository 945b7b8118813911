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

The pool is the whole stack of layers, ``(L, n_pages, Hkv, page, D)``, and
the layer to read is the third scalar-prefetch operand: the decode step's
layer scan carries the pool as one buffer, writes each token into it in
place (`paged_kv_append`), and hands the kernel that buffer — no per-layer
slice is ever copied out. A single-layer pool is passed with a leading axis
of 1 and layer 0.

`paged_kv_append` is the write half. An XLA scatter of one token row per
slot makes the TPU compiler pick a pool layout with the heads axis minor
(a token's (Hkv, D) slab then fills whole tiles), and the kernel's operand
needs the default layout: the compiled step then copies the whole pool
between the two layouts in every layer. The append kernel instead reads and
rewrites the 8-row tile group that holds each slot's new row, in the pool's
own layout, and aliases the pool to its output, so the carried buffer is
updated where it lies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tables_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, kvs_ref,
            o_ref, m_ref, d_ref, acc_ref, *, page: int, n_p: int,
            scale: float):
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
    k_pool: jax.Array,    # (L, n_pages, Hkv, page, D)  shared pool (fp8 or wider)
    v_pool: jax.Array,
    tables: jax.Array,    # (B, n_p) int32 — per-sequence block tables (padded)
    lengths: jax.Array,   # (B,) int32 — live context length per sequence
    kv_scale: jax.Array,  # f32 () — fp8 dequant scale (1.0 when KV is bf16)
    layer: jax.Array,     # int32 () — which layer of the pool to read
    *,
    scale: float | None = None,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    b, hkv, g, d = q.shape
    _, _, _, page, _ = k_pool.shape
    n_p = tables.shape[1]
    scale = scale if scale is not None else d ** -0.5

    tables = jnp.asarray(tables, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    lengths = jnp.asarray(lengths, jnp.int32).reshape(b)
    kv_scale = jnp.asarray(kv_scale, jnp.float32).reshape(1)

    kernel = functools.partial(_kernel, page=page, n_p=n_p, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                 # tables, lengths, layer
        grid=(b, hkv, n_p),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b, h, p, t, l, li: (b, h, 0, 0)),
            # the paged indirection: the context step's block comes from the
            # sequence's block table, not from a contiguous S axis; the layer
            # axis is squeezed, so the kernel sees one (1, 1, page, D) page
            pl.BlockSpec((None, 1, 1, page, d),
                         lambda b, h, p, t, l, li: (li[0], t[b, p], h, 0, 0)),
            pl.BlockSpec((None, 1, 1, page, d),
                         lambda b, h, p, t, l, li: (li[0], t[b, p], h, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda b, h, p, t, l, li: (b, h, 0, 0)),
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
    )(tables, lengths, layer, q, k_pool, v_pool, kv_scale)


def _append_kernel(layer_ref, page_ref, off_ref, kn_ref, vn_ref, k_ref, v_ref,
                   ko_ref, vo_ref, *, rows: int):
    # the block holds rows [off - off % rows, +rows) of the slot's page
    hit = (jax.lax.broadcasted_iota(jnp.int32, k_ref.shape, 1)
           == off_ref[pl.program_id(0)] % rows)
    ko_ref[...] = jnp.where(hit, jnp.broadcast_to(kn_ref[...], k_ref.shape),
                            k_ref[...])
    vo_ref[...] = jnp.where(hit, jnp.broadcast_to(vn_ref[...], v_ref.shape),
                            v_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_append(
    k_pool: jax.Array,    # (L, n_pages, Hkv, page, D)  shared pool
    v_pool: jax.Array,
    k_new: jax.Array,     # (B, Hkv, D) in the pool's dtype
    v_new: jax.Array,
    layer: jax.Array,     # int32 () — layer to write
    page_ids: jax.Array,  # (B,) int32 — page each slot's token lands in
    offsets: jax.Array,   # (B,) int32 — row within that page
    *,
    interpret: bool = False,
):
    """Write each slot's new k/v row at ``[layer, page_ids[b], :,
    offsets[b]]`` of the pools; returns the new pools. The outputs alias
    the pool operands, so inside a jitted step that owns (or was donated)
    the pools the write happens in place.

    Slots are written in order, one grid step each. Two slots that name one
    page (inactive slots all name the scratch page) may lose each other's
    row there, as a scatter with repeated indices would; live slots write
    pages of their own."""
    _, _, hkv, page, d = k_pool.shape
    b = k_new.shape[0]
    rows = 8 if page % 8 == 0 else page
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    pool_spec = pl.BlockSpec(
        (None, None, hkv, rows, d),
        lambda i, li, pg, off: (li[0], pg[i], 0, off[i] // rows, 0))
    new_spec = pl.BlockSpec((None, hkv, 1, d),
                            lambda i, li, pg, off: (i, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                 # layer, page_ids, offsets
        grid=(b,),
        in_specs=[new_spec, new_spec, pool_spec, pool_spec],
        out_specs=[pool_spec, pool_spec],
    )
    return pl.pallas_call(
        functools.partial(_append_kernel, rows=rows),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        # operands: layer, page_ids, offsets, k_new, v_new, k_pool, v_pool
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_kv_append",
    )(layer, jnp.asarray(page_ids, jnp.int32), jnp.asarray(offsets, jnp.int32),
      k_new[:, :, None], v_new[:, :, None], k_pool, v_pool)


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

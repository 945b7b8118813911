"""Pallas TPU kernel: tiled online-softmax decode attention (paper C3→TPU).

The lane-local half of TOM's attention dataflow (Fig 7b steps 0 & 3): one new
query token attends over a (possibly fp8) KV cache tile-by-tile with an
online softmax, entirely in VMEM. The cross-lane half (steps 1/2/4 — global
max and the tree reductions) lives in `core/attention.py` as shard_map
collectives; this kernel is what each lane runs on its local context shard.

Layout: queries are grouped GQA-style — ``q (B, Hkv, G, D)`` where G =
Hq/Hkv query heads share one KV head — so the score matmul `(G,D)x(D,bs)`
hits the MXU with a non-trivial M dim even for decode. KV tiles stream
HBM→VMEM along the context grid axis; running (m, d, o) state lives in VMEM
scratch across grid steps.

KV may be fp8 (e4m3): the kernel widens tiles to f32 after load — VMEM/HBM
traffic is halved, which is the paper's "Act./KV Cache Format: FP8" applied
to the memory-roofline term.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, kvs_ref, o_ref,
            m_ref, d_ref, acc_ref, *, block_s: int, n_s: int, scale: float):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        d_ref[...] = jnp.zeros_like(d_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                    # (G, D)
    k = k_ref[0, 0].astype(jnp.float32) * kvs_ref[0]       # (bs, D)
    v = v_ref[0, 0].astype(jnp.float32) * kvs_ref[0]       # (bs, D)

    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                              # (G, bs)

    # mask positions beyond the live context length
    pos = s * block_s + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(pos < len_ref[0], scores, NEG_INF)

    m_prev = m_ref[...]                                    # (G, 128) lane-replicated
    m_cur = jnp.max(scores, axis=-1, keepdims=True)        # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    corr = jnp.exp(m_prev[:, :1] - m_new[:, :1])           # (G, 1)
    p = jnp.exp(scores - m_new[:, :1])                     # (G, bs)

    d_ref[...] = d_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(s == n_s - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(d_ref[:, :1], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_s", "scale", "out_dtype", "interpret"),
)
def flash_decode(
    q: jax.Array,        # (B, Hkv, G, D)
    k: jax.Array,        # (B, Hkv, S, D)   S % block_s == 0 (ops.py pads)
    v: jax.Array,        # (B, Hkv, S, D)
    length: jax.Array,   # int32 () — live context length (masks the padding)
    kv_scale: jax.Array, # f32 () — fp8 dequant scale (1.0 when KV is bf16)
    *,
    block_s: int = 512,
    scale: float | None = None,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    b, hkv, g, d = q.shape
    _, _, s_len, _ = k.shape
    assert s_len % block_s == 0, (s_len, block_s)
    n_s = s_len // block_s
    scale = scale if scale is not None else d ** -0.5

    length = jnp.asarray(length, jnp.int32).reshape(1)
    kv_scale = jnp.asarray(kv_scale, jnp.float32).reshape(1)

    kernel = functools.partial(_kernel, block_s=block_s, n_s=n_s, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(b, hkv, n_s),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g, d), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_s, d), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, block_s, d), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b, h, s: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),  # running max (lane-replicated)
            pltpu.VMEM((g, 128), jnp.float32),  # running denom
            pltpu.VMEM((g, d), jnp.float32),    # running output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(length, q, k, v, kv_scale)

"""Attention blocks: GQA (with qk-norm / RoPE) and MLA (DeepSeek-V2).

Two execution regimes per block:

  * **train/prefill** — full-sequence causal attention, computed as a
    block-banded online-softmax scan (`chunked_causal_attention`): flash
    attention expressed in pure JAX so XLA keeps the live score tile at
    (chunk_q × chunk_k) instead of S². Heads are sharded over ``model``
    by the GSPMD layer (models/sharding.py).

  * **decode** — one token against an fp8 KV cache that is sharded over the
    *context* dimension across lanes (the paper's SRAM tiling). The softmax
    is TOM's two-phase tree dataflow (core/attention.py) inside a shard_map
    over the ``model`` axis.

The KV cache layout is ``k/v: (B, Hkv, S, D)`` (GQA) or the compressed
``latent: (B, S, R+rope)`` (MLA — decode uses the absorbed form so the cache
stays compressed end-to-end).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import attention as core_attn
from repro.core.lanes import tree_max, tree_sum
from repro.models import act_sharding, layers
from repro.models.layers import KV_CACHE_SCALE, Params, apply_linear, init_linear, linear_spec
from repro.models.sharding import paged_pool_spec
from repro.obs import names

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Paged KV decode state (the opaque KVState a `serving.kv.PagedKV` backend
# hands to Model.decode_step — block tables instead of a contiguous cache)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PagedKVState:
    """Block-table view of a shared KV page pool for one decode tick.

    ``k_pool``/``v_pool`` are the whole pool ``(L, n_pages+1, Hkv, page, D)``
    (last page = scratch for inactive slots); ``tables`` (B, P) int32 are the
    per-slot block tables (pad → scratch page); ``write_page``/``write_off``
    (B,) name where this tick's token lands; ``lengths`` (B,) is the live
    context length *including* the new token. The struct is a pytree so it
    crosses jit boundaries; Model.decode_step returns it with updated pools.
    """
    k_pool: jax.Array
    v_pool: jax.Array
    tables: jax.Array
    write_page: jax.Array
    write_off: jax.Array
    lengths: jax.Array


jax.tree_util.register_dataclass(
    PagedKVState,
    data_fields=["k_pool", "v_pool", "tables", "write_page", "write_off",
                 "lengths"],
    meta_fields=[])


def gather_pages(pool: jax.Array, tables: jax.Array) -> jax.Array:
    """pool (L, N, H, page, D) × tables (B, P) → contiguous (L, B, H, P*page, D)."""
    l, _, h, page, d = pool.shape
    b, p = tables.shape
    pages = pool[:, tables]                        # (L, B, P, H, page, D)
    return pages.transpose(0, 1, 3, 2, 4, 5).reshape(l, b, h, p * page, d)


def scatter_tokens(pool: jax.Array, page_ids: jax.Array, offsets: jax.Array,
                   toks: jax.Array) -> jax.Array:
    """Write toks (L, B, H, D) at (page_ids[b], offsets[b]) in pool
    (L, N, H, page, D). The separated advanced indices put the broadcast
    batch dim first, so the value is fed as (B, L, H, D)."""
    return pool.at[:, page_ids, :, offsets].set(
        toks.astype(pool.dtype).transpose(1, 0, 2, 3))


def gqa_decode_paged(p: Params, x: jax.Array, k_pool: jax.Array,
                     v_pool: jax.Array, layer: jax.Array, tables: jax.Array,
                     write_page: jax.Array, write_off: jax.Array,
                     lengths: jax.Array, pos: jax.Array, cfg: ModelConfig,
                     mode: str, *, interpret: bool,
                     **kw) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token GQA decode straight off one layer of the paged KV pool.

    Writes the new token's k/v into its page of ``layer`` with the Pallas
    ``paged_kv_append`` kernel, then runs attention over that layer with the
    Pallas ``paged_flash_decode`` kernel (block tables via scalar prefetch,
    pages stream HBM→VMEM — no contiguous gather). x: (B, D);
    k_pool/v_pool: (L, N+1, Hkv, page, D), the whole pool; layer: int32 ().
    Both kernels take the whole pool, and the append aliases it to its
    output: inside a layer scan that carries the pools, the write updates
    the carried buffer in place and no layer of the pool is ever copied.
    A pool that spans several devices runs the kernels per lane
    (`_append_attend_on_lanes`).
    Returns (out (B, D), new k_pool, new v_pool).
    """
    b, _ = x.shape
    positions = pos[:, None]
    q, k_new, v_new = _project_qkv(p, x[:, None], cfg, mode, positions, **kw)
    q = q[:, 0]                                          # (B, H, D)
    with names.scope(names.KV_APPEND):
        k_new = (k_new[:, 0] / KV_CACHE_SCALE).astype(k_pool.dtype)
        v_new = (v_new[:, 0] / KV_CACHE_SCALE).astype(v_pool.dtype)
    args = (q, k_new, v_new, k_pool, v_pool, layer, tables, write_page,
            write_off, lengths)
    mesh = jax.typeof(k_pool).sharding.mesh
    if mesh.empty or mesh.size == 1:
        out, k_pool, v_pool = _append_attend(*args, interpret=interpret)
    else:
        out, k_pool, v_pool = _append_attend_on_lanes(mesh, *args,
                                                      interpret=interpret)
    with names.scope(names.ATTN):
        out = out.reshape(b, cfg.q_dim).astype(x.dtype)
    return apply_linear(p["o"], out, mode, **kw), k_pool, v_pool


def _append_attend(q, k_new, v_new, k_pool, v_pool, layer, tables,
                   write_page, write_off, lengths, *, interpret: bool):
    """The two Pallas kernels of `gqa_decode_paged` on one device's pool:
    write each slot's row into ``layer``, then attend over that layer."""
    from repro.kernels.flash_decode.ops import paged_decode_attention
    from repro.kernels.flash_decode.paged import paged_kv_append
    with names.scope(names.KV_APPEND):
        k_pool, v_pool = paged_kv_append(k_pool, v_pool, k_new, v_new, layer,
                                         write_page, write_off,
                                         interpret=interpret)
    with names.scope(names.ATTN):
        out = paged_decode_attention(
            q, k_pool, v_pool, tables, lengths, jnp.float32(KV_CACHE_SCALE),
            layer=layer, use_kernel=True, interpret=interpret,
            out_dtype=jnp.float32)
    return out, k_pool, v_pool


def _append_attend_on_lanes(mesh, q, k_new, v_new, k_pool, v_pool, layer,
                            tables, write_page, write_off, lengths, *,
                            interpret: bool):
    """`_append_attend` on a pool that lives on several devices. XLA cannot
    partition a Mosaic kernel, so both kernels run per lane under
    ``shard_map``, with the pool laid out as `paged_pool_spec` places it:

      * replicated: every lane runs them on its own copy of the whole pool
        (the same values on every lane) — the pool never leaves its lane;
      * pages split over the ``model`` lanes: every lane gathers the pages
        of ``layer``, runs the kernels on that one layer, and keeps its own
        pages of the result — one layer crosses the lanes per layer, never
        the whole pool.

    Every other operand is replicated; the output is the same on every
    lane."""
    spec = paged_pool_spec(k_pool.shape, mesh)
    lanes = spec[1]
    run = functools.partial(_append_attend, interpret=interpret)

    def lane(q, k_new, v_new, kp, vp, layer, *rest):
        if lanes is None:
            return run(q, k_new, v_new, kp, vp, layer, *rest)
        n = kp.shape[1]
        k_l, v_l = (jax.lax.all_gather(pool[layer], lanes, axis=0,
                                       tiled=True)[None] for pool in (kp, vp))
        out, k_l, v_l = run(q, k_new, v_new, k_l, v_l, jnp.int32(0), *rest)
        first = jax.lax.axis_index(lanes) * n
        kp, vp = (jax.lax.dynamic_update_index_in_dim(
            pool, jax.lax.dynamic_slice_in_dim(new[0], first, n, axis=0),
            layer, axis=0) for pool, new in ((kp, k_l), (vp, v_l)))
        return out, kp, vp

    rep = P()
    return jax.shard_map(
        lane, mesh=mesh,
        in_specs=(rep, rep, rep, spec, spec, rep, rep, rep, rep, rep),
        out_specs=(rep, spec, spec), check_vma=False,
    )(q, k_new, v_new, k_pool, v_pool, layer, tables, write_page, write_off,
      lengths)


# ---------------------------------------------------------------------------
# Block-banded causal flash attention (train / prefill)
# ---------------------------------------------------------------------------


def chunked_causal_attention(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, S, Hkv, D)
    v: jax.Array,  # (B, S, Hkv, D)
    *,
    chunk_q: int = 512,
    chunk_k: int = 512,
    scale: Optional[float] = None,
    remat_rows: bool = True,
) -> jax.Array:
    """Causal GQA attention with O(chunk_q·chunk_k) live scores.

    Outer scan over query chunks; inner scan over key chunks skips blocks
    strictly above the diagonal (lax.cond → no FLOPs on TPU's sequential
    scan), masking only the diagonal block.

    ``remat_rows`` wraps each q-row in ``jax.checkpoint`` — the flash-
    attention backward policy: the (cq × S) probability row is recomputed
    per q-chunk during the backward instead of being saved for every
    (q-chunk, k-chunk) tile, which would materialize the full S² scores
    (at 123B-scale training that is the difference between ~3 GB and
    ~100+ GB of per-layer backward residuals).
    """
    b, s, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    scale = scale if scale is not None else d ** -0.5
    assert s % chunk_q == 0 and s % chunk_k == 0, (s, chunk_q, chunk_k)
    nq, nk = s // chunk_q, s // chunk_k

    qc = q.reshape(b, nq, chunk_q, hkv, g, d).transpose(1, 0, 3, 4, 2, 5)  # (nq,B,Hkv,G,cq,D)
    kc = k.reshape(b, nk, chunk_k, hkv, d).transpose(1, 0, 3, 2, 4)        # (nk,B,Hkv,ck,D)
    vc = v.reshape(b, nk, chunk_k, hkv, d).transpose(1, 0, 3, 2, 4)

    def q_step(_, iq_qi):
        iq, q_i = iq_qi
        q_i = q_i.astype(jnp.float32)

        def kv_step(carry, ik_kv):
            ik, k_i, v_i = ik_kv
            m_p, d_p, o_p = carry

            def compute(args):
                m_p, d_p, o_p = args
                s_ij = jnp.einsum("bhgqd,bhkd->bhgqk", q_i, k_i.astype(jnp.float32)) * scale
                # mask the diagonal block; earlier blocks are fully visible
                q_pos = iq * chunk_q + jnp.arange(chunk_q)
                k_pos = ik * chunk_k + jnp.arange(chunk_k)
                causal = q_pos[:, None] >= k_pos[None, :]
                s_ij = jnp.where(causal[None, None, None], s_ij, NEG_INF)
                m_n = jnp.maximum(m_p, jnp.max(s_ij, axis=-1))
                corr = jnp.exp(m_p - m_n)
                p_ij = jnp.exp(s_ij - m_n[..., None])
                d_n = d_p * corr + jnp.sum(p_ij, axis=-1)
                o_n = o_p * corr[..., None] + jnp.einsum(
                    "bhgqk,bhkd->bhgqd", p_ij, v_i.astype(jnp.float32))
                return m_n, d_n, o_n

            new = jax.lax.cond(
                ik * chunk_k <= iq * chunk_q + chunk_q - 1,  # block intersects causal band
                compute, lambda a: a, (m_p, d_p, o_p))
            return new, None

        init = (
            jnp.full((b, hkv, g, chunk_q), NEG_INF, jnp.float32),
            jnp.zeros((b, hkv, g, chunk_q), jnp.float32),
            jnp.zeros((b, hkv, g, chunk_q, d), jnp.float32),
        )
        (m_f, d_f, o_f), _ = jax.lax.scan(
            kv_step, init, (jnp.arange(nk), kc, vc))
        out = o_f / jnp.maximum(d_f[..., None], 1e-30)
        return None, out.astype(q.dtype)

    if remat_rows:
        q_step = jax.checkpoint(q_step)
    _, out = jax.lax.scan(q_step, None, (jnp.arange(nq), qc))
    # (nq, B, Hkv, G, cq, D) → (B, S, H, D)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, s, h, d)
    return out


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------


def init_gqa(key: jax.Array, cfg: ModelConfig, mode: str, **kw) -> Params:
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p = {
        "q": init_linear(ks[0], d, cfg.q_dim, mode,
                         lora=layers.lora_for(cfg, "q", mode), **kw),
        "k": init_linear(ks[1], d, cfg.kv_dim, mode,
                         lora=layers.lora_for(cfg, "k", mode), **kw),
        "v": init_linear(ks[2], d, cfg.kv_dim, mode,
                         lora=layers.lora_for(cfg, "v", mode), **kw),
        "o": init_linear(ks[3], cfg.q_dim, d, mode,
                         lora=layers.lora_for(cfg, "o", mode), **kw),
    }
    if cfg.qk_norm:
        p["q_norm"] = layers.init_rms_norm(cfg.head_dim)
        p["k_norm"] = layers.init_rms_norm(cfg.head_dim)
    return p


def gqa_spec(cfg: ModelConfig, mode: str, **kw) -> Params:
    d = cfg.d_model
    p = {
        "q": linear_spec(d, cfg.q_dim, mode, **kw),
        "k": linear_spec(d, cfg.kv_dim, mode, **kw),
        "v": linear_spec(d, cfg.kv_dim, mode, **kw),
        "o": linear_spec(cfg.q_dim, d, mode, **kw),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"w": jax.ShapeDtypeStruct((cfg.head_dim,), jnp.float32)}
        p["k_norm"] = {"w": jax.ShapeDtypeStruct((cfg.head_dim,), jnp.float32)}
    return p


def _project_qkv(p: Params, x: jax.Array, cfg: ModelConfig, mode: str,
                 positions: jax.Array, **kw):
    b = x.shape[:-1]
    if kw.get("fuse") and mode != "qat":
        sub = {kk: v_ for kk, v_ in kw.items() if kk not in ("fuse", "kv_dtype")}
        q, k, v = layers.apply_linear_fused([p["q"], p["k"], p["v"]], x, mode,
                                            **sub)
        q = q.reshape(*b, cfg.num_heads, cfg.head_dim)
        k = k.reshape(*b, cfg.num_kv_heads, cfg.head_dim)
        v = v.reshape(*b, cfg.num_kv_heads, cfg.head_dim)
    else:
        q = apply_linear(p["q"], x, mode, **kw).reshape(*b, cfg.num_heads, cfg.head_dim)
        k = apply_linear(p["k"], x, mode, **kw).reshape(*b, cfg.num_kv_heads, cfg.head_dim)
        v = apply_linear(p["v"], x, mode, **kw).reshape(*b, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = layers.rms_norm(q, p["q_norm"]["w"], cfg.norm_eps)
        k = layers.rms_norm(k, p["k_norm"]["w"], cfg.norm_eps)
    q = layers.apply_rope(q, positions, cfg.rope_theta)
    k = layers.apply_rope(k, positions, cfg.rope_theta)
    # pin head sharding so chunked-attention tiles stay lane-local (§Perf A);
    # no-op when the head count doesn't divide the lane axis (yi, starcoder)
    q = act_sharding.constrain(q, "heads")
    k = act_sharding.constrain(k, "heads")
    v = act_sharding.constrain(v, "heads")
    return q, k, v


def gqa_train(p: Params, x: jax.Array, cfg: ModelConfig, mode: str,
              chunk: int = 512, **kw) -> jax.Array:
    """Full-sequence causal GQA. x: (B, S, D)."""
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    q, k, v = _project_qkv(p, x, cfg, mode, positions, **kw)
    cq = min(chunk, s)
    out = chunked_causal_attention(q, k, v, chunk_q=cq, chunk_k=cq)
    out = out.reshape(b, s, cfg.q_dim)
    return apply_linear(p["o"], out, mode, **kw)


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, n_layers: int,
                  dtype=jnp.float8_e4m3fn) -> Params:
    shape = (n_layers, batch, cfg.num_kv_heads, max_len, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def kv_cache_spec(cfg: ModelConfig, batch: int, max_len: int, n_layers: int,
                  dtype=jnp.float8_e4m3fn) -> Params:
    shape = (n_layers, batch, cfg.num_kv_heads, max_len, cfg.head_dim)
    return {"k": jax.ShapeDtypeStruct(shape, dtype),
            "v": jax.ShapeDtypeStruct(shape, dtype)}


def _update_cache_local(cache_l: jax.Array, new: jax.Array, pos: jax.Array,
                        lane: jax.Array, s_local: int) -> jax.Array:
    """Write (B, Hkv, D) into this lane's (B, Hkv, S_local, D) context shard
    iff `pos` falls in its range — no cross-lane traffic (the token lands in
    exactly one lane's SRAM, Fig 7b)."""
    local = pos - lane * s_local
    in_range = (local >= 0) & (local < s_local)
    idx = jnp.clip(local, 0, s_local - 1)
    updated = jax.lax.dynamic_update_slice(
        cache_l, new[:, :, None].astype(cache_l.dtype), (0, 0, idx, 0))
    return jnp.where(in_range, updated, cache_l)


def gqa_decode(p: Params, x: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
               pos: jax.Array, cfg: ModelConfig, mode: str,
               axis_name: Optional[str], n_lanes: int, **kw
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token GQA decode against the lane-local KV shard.

    Runs INSIDE shard_map over `axis_name`: k_cache/v_cache are the local
    (B, Hkv, S_local, D) context shards; x (B, D) is replicated. Returns
    (out (B, D), new_k_local, new_v_local).
    """
    b, _ = x.shape
    positions = pos[None, None]  # broadcast to (B, 1)
    q, k_new, v_new = _project_qkv(p, x[:, None], cfg, mode, positions, **kw)
    q = q[:, 0]                     # (B, H, D)
    k_new, v_new = k_new[:, 0], v_new[:, 0]  # (B, Hkv, D)

    s_local = k_cache.shape[2]
    lane = jax.lax.axis_index(axis_name) if axis_name else jnp.int32(0)
    k_cache = _update_cache_local(k_cache, k_new / KV_CACHE_SCALE, pos, lane, s_local)
    v_cache = _update_cache_local(v_cache, v_new / KV_CACHE_SCALE, pos, lane, s_local)

    # local visibility mask: global position index of each local slot
    slot = lane * s_local + jnp.arange(s_local)
    mask = (slot <= pos)[None, :]   # (1, S_local) → broadcast over B

    out = core_attn.gqa_decode(
        q, k_cache.astype(jnp.float32), v_cache.astype(jnp.float32),
        axis_name=axis_name, variant="tom",
        mask_local=jnp.broadcast_to(mask, (b, s_local)),
        kv_scale=jnp.float32(KV_CACHE_SCALE),
    ).astype(x.dtype)
    out = apply_linear(p["o"], out.reshape(b, cfg.q_dim), mode, **kw)
    return out, k_cache, v_cache


# ---------------------------------------------------------------------------
# MLA block (DeepSeek-V2): compressed-latent cache, absorbed decode
# ---------------------------------------------------------------------------


def init_mla(key: jax.Array, cfg: ModelConfig, mode: str, **kw) -> Params:
    m = cfg.mla
    d = cfg.d_model
    h = cfg.num_heads
    ks = jax.random.split(key, 6)
    qh = h * (m.qk_nope_head_dim + m.qk_rope_head_dim)
    return {
        "q_a": init_linear(ks[0], d, m.q_lora_rank, mode, **kw),
        "q_a_norm": layers.init_rms_norm(m.q_lora_rank),
        "q_b": init_linear(ks[1], m.q_lora_rank, qh, mode,
                           lora=layers.lora_for(cfg, "q", mode), **kw),
        "kv_a": init_linear(ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim, mode, **kw),
        "kv_a_norm": layers.init_rms_norm(m.kv_lora_rank),
        "kv_b": init_linear(ks[3], m.kv_lora_rank,
                            h * (m.qk_nope_head_dim + m.v_head_dim), mode,
                            lora=layers.lora_for(cfg, "v", mode), **kw),
        "o": init_linear(ks[4], h * m.v_head_dim, d, mode,
                         lora=layers.lora_for(cfg, "o", mode), **kw),
    }


def mla_spec(cfg: ModelConfig, mode: str, **kw) -> Params:
    m = cfg.mla
    d = cfg.d_model
    h = cfg.num_heads
    qh = h * (m.qk_nope_head_dim + m.qk_rope_head_dim)
    return {
        "q_a": linear_spec(d, m.q_lora_rank, mode, **kw),
        "q_a_norm": {"w": jax.ShapeDtypeStruct((m.q_lora_rank,), jnp.float32)},
        "q_b": linear_spec(m.q_lora_rank, qh, mode, **kw),
        "kv_a": linear_spec(d, m.kv_lora_rank + m.qk_rope_head_dim, mode, **kw),
        "kv_a_norm": {"w": jax.ShapeDtypeStruct((m.kv_lora_rank,), jnp.float32)},
        "kv_b": linear_spec(m.kv_lora_rank, h * (m.qk_nope_head_dim + m.v_head_dim),
                            mode, **kw),
        "o": linear_spec(h * m.v_head_dim, d, mode, **kw),
    }


def _mla_q(p: Params, x: jax.Array, cfg: ModelConfig, mode: str,
           positions: jax.Array, **kw):
    m = cfg.mla
    h = cfg.num_heads
    qa = apply_linear(p["q_a"], x, mode, **kw)
    qa = layers.rms_norm(qa, p["q_a_norm"]["w"], cfg.norm_eps)
    qb = apply_linear(p["q_b"], qa, mode, **kw)
    qb = qb.reshape(*x.shape[:-1], h, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope = qb[..., :m.qk_nope_head_dim]
    q_rope = layers.apply_rope(qb[..., m.qk_nope_head_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(p: Params, x: jax.Array, cfg: ModelConfig, mode: str,
                positions: jax.Array, **kw):
    m = cfg.mla
    kv = apply_linear(p["kv_a"], x, mode, **kw)
    latent = layers.rms_norm(kv[..., :m.kv_lora_rank], p["kv_a_norm"]["w"], cfg.norm_eps)
    k_rope = layers.apply_rope(kv[..., None, m.kv_lora_rank:], positions, cfg.rope_theta)
    return latent, k_rope[..., 0, :]


def mla_train(p: Params, x: jax.Array, cfg: ModelConfig, mode: str,
              chunk: int = 512, **kw) -> jax.Array:
    """Full-seq MLA: reconstruct per-head K/V from the latent (train path)."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    positions = jnp.arange(s)[None, :]
    q_nope, q_rope = _mla_q(p, x, cfg, mode, positions, **kw)
    latent, k_rope = _mla_latent(p, x, cfg, mode, positions, **kw)
    kvb = apply_linear(p["kv_b"], latent, mode, **kw)
    kvb = kvb.reshape(b, s, h, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = kvb[..., :m.qk_nope_head_dim], kvb[..., m.qk_nope_head_dim:]
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, :, None], (b, s, h, m.qk_rope_head_dim))], -1)
    # head-shard the reconstructed q/k/v (128 heads ÷ 16 lanes; §Perf cell A)
    q = act_sharding.constrain(q, "heads")
    k = act_sharding.constrain(k, "heads")
    v = act_sharding.constrain(v, "heads")
    # pad v head dim up to qk dim for the shared kernel, then slice back
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.v_head_dim != qk_dim:
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk_dim - m.v_head_dim)))
    cq = min(chunk, s)
    out = chunked_causal_attention(q, k, v, chunk_q=cq, chunk_k=cq, scale=scale)
    out = out[..., :m.v_head_dim].reshape(b, s, h * m.v_head_dim)
    return apply_linear(p["o"], out, mode, **kw)


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, n_layers: int,
                   dtype=jnp.float8_e4m3fn) -> Params:
    m = cfg.mla
    return {"latent": jnp.zeros((n_layers, batch, max_len, m.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((n_layers, batch, max_len, m.qk_rope_head_dim), dtype)}


def mla_cache_spec(cfg: ModelConfig, batch: int, max_len: int, n_layers: int,
                   dtype=jnp.float8_e4m3fn) -> Params:
    m = cfg.mla
    return {
        "latent": jax.ShapeDtypeStruct((n_layers, batch, max_len, m.kv_lora_rank), dtype),
        "k_rope": jax.ShapeDtypeStruct((n_layers, batch, max_len, m.qk_rope_head_dim), dtype),
    }


def mla_decode(p: Params, x: jax.Array, latent_cache: jax.Array,
               rope_cache: jax.Array, pos: jax.Array, cfg: ModelConfig,
               mode: str, axis_name: Optional[str], n_lanes: int, **kw
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Absorbed-form MLA decode over the context-sharded compressed cache.

    score_h = q_nopeᵀ·W_kb_kʰ·latent + q_rope·k_rope ; the attention runs in
    latent space so the cache never decompresses — TOM's two-phase softmax
    applies unchanged over the latent context tiles.
    """
    m = cfg.mla
    h = cfg.num_heads
    b, _ = x.shape
    positions = pos[None, None]
    q_nope, q_rope = _mla_q(p, x[:, None], cfg, mode, positions, **kw)
    q_nope, q_rope = q_nope[:, 0], q_rope[:, 0]        # (B, H, dn), (B, H, dr)
    latent_new, k_rope_new = _mla_latent(p, x[:, None], cfg, mode, positions, **kw)
    latent_new, k_rope_new = latent_new[:, 0], k_rope_new[:, 0]

    s_local = latent_cache.shape[1]
    lane = jax.lax.axis_index(axis_name) if axis_name else jnp.int32(0)

    def upd(cache, new):
        local = pos - lane * s_local
        in_r = (local >= 0) & (local < s_local)
        idx = jnp.clip(local, 0, s_local - 1)
        u = jax.lax.dynamic_update_slice(
            cache, (new / KV_CACHE_SCALE)[:, None].astype(cache.dtype), (0, idx, 0))
        return jnp.where(in_r, u, cache)

    latent_cache = upd(latent_cache, latent_new)
    rope_cache = upd(rope_cache, k_rope_new)

    # absorb W_kb into the query / output
    wkb = _dense_weight(p["kv_b"], x.dtype)            # (R, H*(dn+dv))
    wkb = wkb.reshape(m.kv_lora_rank, h, m.qk_nope_head_dim + m.v_head_dim)
    w_k = wkb[..., :m.qk_nope_head_dim]                # (R, H, dn)
    w_v = wkb[..., m.qk_nope_head_dim:]                # (R, H, dv)

    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope.astype(jnp.float32),
                       w_k.astype(jnp.float32))        # (B, H, R)
    lat = latent_cache.astype(jnp.float32) * KV_CACHE_SCALE   # (B, S_l, R)
    rp = rope_cache.astype(jnp.float32) * KV_CACHE_SCALE      # (B, S_l, dr)
    scores = (jnp.einsum("bhr,bsr->bhs", q_lat, lat)
              + jnp.einsum("bhd,bsd->bhs", q_rope.astype(jnp.float32), rp))
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    scores = scores * scale

    slot = lane * s_local + jnp.arange(s_local)
    mask = (slot <= pos)[None, None, :]
    scores = jnp.where(mask, scores, NEG_INF)

    # two-phase tree softmax (C3) over latent context tiles
    m_loc = jnp.max(scores, axis=-1)
    m_glob = tree_max(m_loc, axis_name)
    pexp = jnp.exp(scores - m_glob[..., None])
    d_loc = jnp.sum(pexp, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", pexp, lat)
    o_lat = tree_sum(o_lat, axis_name)
    den = tree_sum(d_loc, axis_name)
    o_lat = o_lat / jnp.maximum(den[..., None], 1e-30)

    out = jnp.einsum("bhr,rhd->bhd", o_lat, w_v.astype(jnp.float32))  # (B, H, dv)
    out = apply_linear(p["o"], out.reshape(b, h * m.v_head_dim).astype(x.dtype),
                       mode, **kw)
    return out, latent_cache, rope_cache


def _dense_weight(p: Params, dtype) -> jax.Array:
    """Materialize a linear's weight (for the MLA absorb einsums)."""
    if "w" in p:
        from repro.core.ternary import ste_quantize
        return ste_quantize(p["w"].astype(jnp.float32)).astype(dtype)
    from repro.core.ternary import unpack2
    return (unpack2(p["packed"]).astype(jnp.float32) * p["scale"]).astype(dtype)

"""Explicit-lanes decode: the paper's dataflow written as `shard_map`.

The GSPMD serve path (models/transformer.py) lets XLA's partitioner derive
TOM's collectives from shardings. This module is the ground truth the other
direction: every lane's program is written out exactly as §IV-C/D describes —

    per layer:
      1. q/k/v/o GEMVs: each lane multiplies its K-slice of the packed
         ternary ROM against its activation slice; partial sums cross the
         reduction tree (ONE psum per GEMV — Fig 7a)
      2. decode attention: KV tiled across lanes over the context dim;
         two-phase softmax = pmax round, rescale, psum round (Fig 7b)
      3. FFN: same lane-tiled ternary GEMVs
    lanes never exchange data except via tree_sum/tree_max.

Dense GQA architectures (the paper's BitNet-2B class). Tests assert
equivalence with the GSPMD decode on a multi-device host mesh, which is the
claim in DESIGN.md §2.2: the partitioner's lowering and the hand-written
lane program compute the same function.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import attention as core_attn
from repro.core import ternary
from repro.core.lanes import tree_sum
from repro.models.layers import KV_CACHE_SCALE, Params

AXIS = "model"


# ---------------------------------------------------------------------------
# lane-local primitives
# ---------------------------------------------------------------------------


def _lane_linear_packed(x_local: jax.Array, packed_local: jax.Array,
                        scale: jax.Array, *, reduce: bool = True) -> jax.Array:
    """x (B, K/L) @ ROM-slice (K/L / 4, N) ×scale, tree-reduced (Fig 7a)."""
    w = ternary.unpack2(packed_local).astype(jnp.bfloat16)
    y = jnp.einsum("bk,kn->bn", x_local.astype(jnp.bfloat16), w,
                   preferred_element_type=jnp.float32) * scale
    return tree_sum(y, AXIS) if reduce else y


def _split_x(x: jax.Array) -> jax.Array:
    """Take this lane's K-slice of a replicated activation."""
    lanes = jax.lax.psum(1, AXIS)
    idx = jax.lax.axis_index(AXIS)
    k_local = x.shape[-1] // lanes
    return jax.lax.dynamic_slice_in_dim(x, idx * k_local, k_local, axis=-1)


def _rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w).astype(x.dtype)


def _rope(x, pos, theta):
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[..., None, None] * freqs  # (B,1,D/2)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                           x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# one decoder layer, lane-resident
# ---------------------------------------------------------------------------


def _lane_layer(lp: Params, x: jax.Array, kc: jax.Array, vc: jax.Array,
                pos: jax.Array, cfg: ModelConfig):
    """x: (B, D) replicated; kc/vc: (B, Hkv, S/L, D) lane-local context tile;
    pos: scalar (single-stream decode — the paper's regime).

    Returns (x', kc', vc'). Every GEMV = local partial + tree_sum; attention
    = Fig 7b two-phase over the lane-tiled cache."""
    eps = cfg.norm_eps
    h = _rms_norm(x, lp["norm1"]["w"], eps)
    hl = _split_x(h)

    q = _lane_linear_packed(hl, lp["attn"]["q"]["packed"], lp["attn"]["q"]["scale"])
    k = _lane_linear_packed(hl, lp["attn"]["k"]["packed"], lp["attn"]["k"]["scale"])
    v = _lane_linear_packed(hl, lp["attn"]["v"]["packed"], lp["attn"]["v"]["scale"])
    b = x.shape[0]
    q = q.reshape(b, cfg.num_heads, cfg.head_dim)
    k = k.reshape(b, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(b, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = _rms_norm(q, lp["attn"]["q_norm"]["w"], eps)
        k = _rms_norm(k, lp["attn"]["k_norm"]["w"], eps)
    posb = jnp.broadcast_to(pos[None], (b,))
    q = _rope(q, posb, cfg.rope_theta)
    k = _rope(k, posb, cfg.rope_theta)

    # --- cache insert: pos lands in exactly one lane's context tile --------
    lanes = jax.lax.psum(1, AXIS)
    lane = jax.lax.axis_index(AXIS)
    s_local = kc.shape[2]
    owner = pos // s_local                    # which lane owns this position
    local_pos = pos % s_local
    k_q = (k / KV_CACHE_SCALE).astype(kc.dtype)
    v_q = (v / KV_CACHE_SCALE).astype(vc.dtype)
    kc_new = jax.lax.dynamic_update_slice(kc, k_q[:, :, None], (0, 0, local_pos, 0))
    vc_new = jax.lax.dynamic_update_slice(vc, v_q[:, :, None], (0, 0, local_pos, 0))
    is_owner = (owner == lane)  # scalar pos → scalar predicate
    kc = jnp.where(is_owner, kc_new, kc)
    vc = jnp.where(is_owner, vc_new, vc)

    # --- two-phase attention over lane tiles (Fig 7b) ----------------------
    base = lane * s_local
    mask_local = (base + jnp.arange(s_local)) <= pos          # (S/L,)
    mask_local = jnp.broadcast_to(mask_local[None], (b, s_local))
    kf = kc.astype(jnp.float32) * KV_CACHE_SCALE
    vf = vc.astype(jnp.float32) * KV_CACHE_SCALE
    attn = core_attn.gqa_decode(q, kf, vf, axis_name=AXIS, variant="tom",
                                mask_local=mask_local)
    attn = attn.reshape(b, cfg.q_dim).astype(x.dtype)

    o = _lane_linear_packed(_split_x(attn), lp["attn"]["o"]["packed"],
                            lp["attn"]["o"]["scale"]).astype(x.dtype)
    x = x + o

    h2 = _rms_norm(x, lp["norm2"]["w"], eps)
    h2l = _split_x(h2)
    up = _lane_linear_packed(h2l, lp["ffn"]["up"]["packed"],
                             lp["ffn"]["up"]["scale"])
    if cfg.ffn_kind == "swiglu":
        gate = _lane_linear_packed(h2l, lp["ffn"]["gate"]["packed"],
                                   lp["ffn"]["gate"]["scale"])
        act = jax.nn.silu(gate) * up
    elif cfg.ffn_kind == "relu2":
        act = jnp.square(jax.nn.relu(up))
    else:
        act = jax.nn.gelu(up)
    act = act.astype(x.dtype)
    down = _lane_linear_packed(_split_x(act), lp["ffn"]["down"]["packed"],
                               lp["ffn"]["down"]["scale"]).astype(x.dtype)
    return x + down, kc, vc


# ---------------------------------------------------------------------------
# whole-model decode step under shard_map
# ---------------------------------------------------------------------------


def make_lane_decode_step(cfg: ModelConfig, mesh: Mesh):
    """Explicit-lane decode step for dense GQA serve-mode params.

    Signature matches Model.decode_step: (params, cache, token (B,), pos ())
    → (logits (B, V), cache). Only the 'model' axis participates; batch
    stays replicated (the paper's single-stream regime)."""
    assert cfg.attention_kind == "gqa" and cfg.moe is None and cfg.ssm is None

    def body(params, k_cache, v_cache, token, pos):
        # embedding rows are replicated (packed_rows gather is local)
        emb = params["embed"]
        from repro.models.layers import unpack_rows
        x = (unpack_rows(emb["packed_rows"][token]).astype(jnp.float32)
             * emb["scale"]).astype(jnp.bfloat16)

        def layer(carry, inp):
            xc, = carry
            lp, kc, vc = inp
            xc, kc, vc = _lane_layer(lp, xc, kc, vc, pos, cfg)
            return (xc,), (kc, vc)

        (x,), (k_new, v_new) = jax.lax.scan(
            layer, (x,), (params["layers"], k_cache, v_cache))
        x = _rms_norm(x, params["final_norm"]["w"], cfg.norm_eps)
        if cfg.tie_embeddings:
            w = (unpack_rows(emb["packed_rows"]).astype(jnp.float32)
                 * emb["scale"])
            logits = jnp.einsum("bd,vd->bv", x.astype(jnp.float32), w)
        else:
            logits = _lane_linear_packed(_split_x(x), params["head"]["packed"],
                                         params["head"]["scale"])
        if cfg.vocab_padded != cfg.vocab_size:
            pad_mask = jnp.arange(cfg.vocab_padded) < cfg.vocab_size
            logits = jnp.where(pad_mask, logits, -1e30)
        return logits, k_new, v_new

    # shardings: weights K-sharded over lanes (packed K/4 rows), caches
    # context-sharded, activations/token/logits replicated.
    def build_param_specs(params):
        def spec_for(path, leaf):
            joined = "/".join(str(getattr(e, "key", e)) for e in path)
            if "packed_rows" in joined or "norm" in joined or "scale" in joined:
                return P()
            if joined.endswith("packed"):
                return P(*([None] * (leaf.ndim - 2)), AXIS, None)
            return P()
        return jax.tree_util.tree_map_with_path(spec_for, params)

    def step(params, cache, token, pos):
        in_specs = (build_param_specs(params),
                    P(None, None, None, AXIS, None),   # k (L,B,H,S,D): S over lanes
                    P(None, None, None, AXIS, None),
                    P(), P())
        out_specs = (P(), P(None, None, None, AXIS, None),
                     P(None, None, None, AXIS, None))
        fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        logits, k_new, v_new = fn(params, cache["k"], cache["v"], token, pos)
        return logits, {"k": k_new, "v": v_new}

    return step

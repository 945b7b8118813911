"""Model assembly: blocks → scan-over-layers → loss / prefill / decode.

One implementation serves all ten assigned architectures plus bitnet-2b:

  * dense / vlm / audio — homogeneous GQA blocks (vlm/audio take stub
    embeddings instead of token ids; §ARCHITECTURES note).
  * moe — GQA + MoE FFN; deepseek additionally MLA attention and
    ``first_k_dense`` unstacked prefix layers.
  * ssm — homogeneous Mamba2 blocks.
  * hybrid (zamba2) — Mamba2 backbone with a SHARED attention+FFN block
    applied every ``period`` layers (one weight set reused at all positions).

Layers are stacked and scanned (compact HLO at 88 layers, XLA prefetches the
next layer's weights during the current layer — the runtime analogue of the
paper's pre-wake power gating, DESIGN.md §2.5). The LM loss is computed in
sequence chunks so (B,S,V) logits never materialize.

Distribution is GSPMD: `launch/` jits these fns with in/out shardings from
models/sharding.py. With the paper_tree strategy + context-sharded KV cache,
XLA's partitioner lowers the decode softmax to exactly the paper's two-phase
tree dataflow (all-reduce max, then all-reduce sum — verified against the
explicit shard_map implementation in core/attention.py by tests, and in the
dry-run HLO by benchmarks/roofline.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import layers, moe as moe_mod, ssm as ssm_mod
from repro.models.layers import KV_CACHE_SCALE, Params
from repro.obs import names

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def init_attn_block(key, cfg: ModelConfig, mode: str, dtype, dense_ffn: int = 0) -> Params:
    ks = jax.random.split(key, 3)
    p: Params = {"norm1": layers.init_rms_norm(cfg.d_model),
                 "norm2": layers.init_rms_norm(cfg.d_model)}
    if cfg.attention_kind == "mla":
        p["attn"] = attn_mod.init_mla(ks[0], cfg, mode, dtype=dtype)
    else:
        p["attn"] = attn_mod.init_gqa(ks[0], cfg, mode, dtype=dtype)
    ffn_lora = {n: layers.lora_for(cfg, n, mode) for n in ("up", "gate", "down")}
    if dense_ffn:
        p["ffn"] = layers.init_ffn(ks[1], cfg.d_model, dense_ffn, cfg.ffn_kind,
                                   mode, dtype=dtype, lora_map=ffn_lora)
    elif cfg.moe is not None:
        p["moe"] = moe_mod.init_moe(ks[1], cfg, mode, dtype=dtype)
    else:
        p["ffn"] = layers.init_ffn(ks[1], cfg.d_model, cfg.d_ff, cfg.ffn_kind,
                                   mode, dtype=dtype, lora_map=ffn_lora)
    return p


def attn_block_train(p: Params, x: jax.Array, cfg: ModelConfig, mode: str,
                     chunk: int, **kw) -> Tuple[jax.Array, jax.Array]:
    h = layers.rms_norm(x, p["norm1"]["w"], cfg.norm_eps)
    if cfg.attention_kind == "mla":
        a = attn_mod.mla_train(p["attn"], h, cfg, mode, chunk=chunk, **kw)
    else:
        a = attn_mod.gqa_train(p["attn"], h, cfg, mode, chunk=chunk, **kw)
    x = x + a
    h2 = layers.rms_norm(x, p["norm2"]["w"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        f, aux = moe_mod.moe_ffn(p["moe"], h2, cfg, mode, **kw)
    else:
        f = layers.apply_ffn(p["ffn"], h2, cfg.ffn_kind, mode, **kw)
    return x + f, aux


def attn_block_decode(p: Params, x: jax.Array, cache_slices, pos, cfg: ModelConfig,
                      mode: str, **kw):
    """x: (B, D); cache_slices: per-layer cache arrays (GQA: k,v / MLA:
    latent,k_rope). Returns (x', new_cache_slices, aux)."""
    h = layers.rms_norm(x, p["norm1"]["w"], cfg.norm_eps)
    if cfg.attention_kind == "mla":
        a, c0, c1 = _mla_decode_gspmd(p["attn"], h, cache_slices[0], cache_slices[1],
                                      pos, cfg, mode, **kw)
    else:
        a, c0, c1 = _gqa_decode_gspmd(p["attn"], h, cache_slices[0], cache_slices[1],
                                      pos, cfg, mode, **kw)
    x = x + a
    h2 = layers.rms_norm(x, p["norm2"]["w"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        f, aux = moe_mod.moe_ffn(p["moe"], h2, cfg, mode, **kw)
    else:
        f = layers.apply_ffn(p["ffn"], h2, cfg.ffn_kind, mode, **kw)
    return x + f, (c0, c1), aux


# --- GSPMD decode attention (context-sharded cache; stable two-phase softmax)


def _pos2d(pos: jax.Array) -> jax.Array:
    """pos () or (B,) → (B-or-1, 1) position matrix for RoPE on a 1-token x."""
    return pos[None, None] if pos.ndim == 0 else pos[:, None]


def _update_cache_at(cache: jax.Array, new: jax.Array, pos: jax.Array,
                     seq_axis: int) -> jax.Array:
    """Write one new timestep into the cache at ``pos``.

    Scalar ``pos`` (all sequences aligned — the dry-run decode cells) uses a
    single dynamic_update_slice. Vector ``pos`` (B,) (continuous batching —
    every slot at its own depth) vmaps the update over the batch axis, which
    XLA lowers to a scatter.
    """
    if pos.ndim == 0:
        idx = [jnp.zeros((), jnp.int32)] * cache.ndim
        idx[seq_axis] = pos
        return jax.lax.dynamic_update_slice(cache, new, tuple(idx))

    def one(c, n, p):  # c: cache[b], n: new[b], seq axis shifted left by 1
        idx = [jnp.zeros((), jnp.int32)] * c.ndim
        idx[seq_axis - 1] = p
        return jax.lax.dynamic_update_slice(c, n, tuple(idx))

    return jax.vmap(one)(cache, new, pos)


def _stable_softmax_attend(scores: jax.Array, values: jax.Array,
                           mask: jax.Array) -> jax.Array:
    """scores (B,H,G,S) × values (B,H,S,D) → (B,H,G,D) with the explicit
    max-subtract form. Over a context(S)-sharded mesh axis XLA lowers the max
    and sum reductions to all-reduce max / all-reduce sum — the paper's
    two-phase reduction-tree dataflow (C3)."""
    scores = jnp.where(mask, scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    den = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhgs,bhsd->bhgd", p, values)
    return out / jnp.maximum(den, 1e-30)


def _gqa_decode_gspmd(p, x, k_cache, v_cache, pos, cfg, mode, **kw):
    b, _ = x.shape
    positions = _pos2d(pos)
    q, k_new, v_new = attn_mod._project_qkv(p, x[:, None], cfg, mode, positions, **kw)
    q = q[:, 0].reshape(b, cfg.num_kv_heads, -1, cfg.head_dim)     # (B,Hkv,G,D)
    with names.scope(names.KV_APPEND):
        k_new = (k_new[:, 0] / KV_CACHE_SCALE).astype(k_cache.dtype)
        v_new = (v_new[:, 0] / KV_CACHE_SCALE).astype(v_cache.dtype)
        k_cache = _update_cache_at(k_cache, k_new[:, :, None], pos, seq_axis=2)
        v_cache = _update_cache_at(v_cache, v_new[:, :, None], pos, seq_axis=2)
    s_len = k_cache.shape[2]
    with names.scope(names.ATTN):
        # §Perf C: widening the fp8 cache to bf16 instead of f32 halves the
        # dominant decode HBM term; scores still accumulate in f32 via the
        # dot's preferred_element_type.
        wide = jnp.bfloat16 if kw.get("kv_dtype") == "bf16" else jnp.float32
        kf = k_cache.astype(wide) * KV_CACHE_SCALE
        vf = v_cache.astype(wide) * KV_CACHE_SCALE
        scores = jnp.einsum("bhgd,bhsd->bhgs", q.astype(wide), kf,
                            preferred_element_type=jnp.float32)
        scores = scores * (cfg.head_dim ** -0.5)
        if pos.ndim == 0:
            mask = (jnp.arange(s_len) <= pos)[None, None, None, :]
        else:
            mask = (jnp.arange(s_len)[None] <= pos[:, None])[:, None, None, :]
        out = _stable_softmax_attend(scores, vf, mask)
        out = out.reshape(b, cfg.q_dim).astype(x.dtype)
    return layers.apply_linear(p["o"], out, mode, **kw), k_cache, v_cache


def _mla_decode_gspmd(p, x, latent_cache, rope_cache, pos, cfg, mode, **kw):
    m = cfg.mla
    h = cfg.num_heads
    b, _ = x.shape
    positions = _pos2d(pos)
    q_nope, q_rope = attn_mod._mla_q(p, x[:, None], cfg, mode, positions, **kw)
    q_nope, q_rope = q_nope[:, 0], q_rope[:, 0]
    latent_new, k_rope_new = attn_mod._mla_latent(p, x[:, None], cfg, mode,
                                                  positions, **kw)
    latent_new = (latent_new[:, 0] / KV_CACHE_SCALE).astype(latent_cache.dtype)
    k_rope_new = (k_rope_new[:, 0] / KV_CACHE_SCALE).astype(rope_cache.dtype)
    latent_cache = _update_cache_at(latent_cache, latent_new[:, None], pos,
                                    seq_axis=1)
    rope_cache = _update_cache_at(rope_cache, k_rope_new[:, None], pos,
                                  seq_axis=1)
    wkb = attn_mod._dense_weight(p["kv_b"], jnp.float32)
    wkb = wkb.reshape(m.kv_lora_rank, h, m.qk_nope_head_dim + m.v_head_dim)
    w_k, w_v = wkb[..., :m.qk_nope_head_dim], wkb[..., m.qk_nope_head_dim:]
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope.astype(jnp.float32), w_k)
    lat = latent_cache.astype(jnp.float32) * KV_CACHE_SCALE
    rp = rope_cache.astype(jnp.float32) * KV_CACHE_SCALE
    scores = (jnp.einsum("bhr,bsr->bhs", q_lat, lat)
              + jnp.einsum("bhd,bsd->bhs", q_rope.astype(jnp.float32), rp))
    scores = scores * ((m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5)
    s_len = lat.shape[1]
    if pos.ndim == 0:
        mask = (jnp.arange(s_len) <= pos)[None, None, :]
    else:
        mask = (jnp.arange(s_len)[None] <= pos[:, None])[:, None, :]
    scores = jnp.where(mask, scores, NEG_INF)
    mx = jnp.max(scores, axis=-1, keepdims=True)
    pr = jnp.exp(scores - mx)
    den = jnp.sum(pr, axis=-1, keepdims=True)
    o_lat = jnp.einsum("bhs,bsr->bhr", pr, lat) / jnp.maximum(den, 1e-30)
    out = jnp.einsum("bhr,rhd->bhd", o_lat, w_v)
    out = out.reshape(b, h * m.v_head_dim).astype(x.dtype)
    return layers.apply_linear(p["o"], out, mode, **kw), latent_cache, rope_cache


# ---------------------------------------------------------------------------
# Hybrid pattern helpers (zamba2)
# ---------------------------------------------------------------------------


def hybrid_layout(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(n_groups, mamba_per_group, trailing_mamba) for 'mmmmma...' patterns."""
    pat = cfg.block_pattern
    n_attn = pat.count("a")
    period = pat.index("a") + 1 if "a" in pat else len(pat)
    mpg = period - 1
    trailing = len(pat) - n_attn * period
    assert pat == ("m" * mpg + "a") * n_attn + "m" * trailing, "unsupported pattern"
    return n_attn, mpg, trailing


# ---------------------------------------------------------------------------
# The Model
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    mode: str = "qat"          # qat | serve | qlora
    remat: bool = True
    attn_chunk: int = 512
    loss_chunk: int = 2048
    # Optional NamedSharding for the (B, S, D) residual stream. Launch sets
    # this to P(dp, model, None) — sequence-parallel activations, so the
    # per-layer remat carry is 1/16th per lane (DESIGN.md §5). None = let
    # XLA's SPMD propagation choose.
    act_shard: Any = None
    # Optional NamedSharding for (B, S, H, D) attention tensors — pins
    # q/k/v to head-sharded so chunked-attention tiles never reshard
    # (§Perf cell A). Applied via models/act_sharding context.
    head_shard: Any = None
    # §Perf cell C levers: fuse q/k/v (and up/gate) into one matmul → one
    # tree reduction instead of 3 (2); widen the fp8 KV cache to bf16 rather
    # than f32 during attention (halves the dominant decode HBM reads).
    fuse_proj: bool = False
    kv_widen: str = "f32"
    # Paged decode attention dispatch when decode_step receives a
    # `PagedKVState` (serving/kv.py PagedKV backend):
    #   "auto"   — Pallas paged_flash_decode on TPU (block tables via scalar
    #              prefetch, pages stream HBM→VMEM), XLA gather reference on
    #              CPU (bit-identical to the dense path);
    #   "kernel" — force the Pallas kernel (interpret-mode on CPU; tests);
    #   "gather" — force the XLA gather reference.
    paged_attn: str = "auto"

    def _c(self, x: jax.Array) -> jax.Array:
        """Constrain the residual stream's sharding (3-D activations only)."""
        if self.act_shard is not None and x.ndim == 3:
            return jax.lax.with_sharding_constraint(x, self.act_shard)
        return x

    def _shard_scope(self):
        from repro.models import act_sharding
        return act_sharding.scope(heads=self.head_shard)

    @property
    def dtype(self):
        return jnp.bfloat16 if self.cfg.dtype == "bfloat16" else jnp.float32

    # -- params ------------------------------------------------------------
    def init(self, key: jax.Array) -> Params:
        cfg, mode, dtype = self.cfg, self.mode, self.dtype
        keys = jax.random.split(key, 8)
        p: Params = {"embed": layers.init_embedding(keys[0], cfg.vocab_padded,
                                                    cfg.d_model, mode, dtype),
                     "final_norm": layers.init_rms_norm(cfg.d_model)}
        if not cfg.tie_embeddings:
            p["head"] = layers.init_linear(keys[1], cfg.d_model, cfg.vocab_padded,
                                           mode, dtype=dtype)
        if cfg.family == "ssm":
            p["mamba"] = jax.vmap(
                lambda k: ssm_mod.init_mamba2(k, cfg, mode, dtype)
            )(jax.random.split(keys[2], cfg.num_layers))
        elif cfg.family == "hybrid":
            n_attn, mpg, trailing = hybrid_layout(cfg)
            n_mamba = n_attn * mpg + trailing
            p["mamba"] = jax.vmap(
                lambda k: ssm_mod.init_mamba2(k, cfg, mode, dtype)
            )(jax.random.split(keys[2], n_mamba))
            p["shared_attn"] = init_attn_block(keys[3], cfg, mode, dtype)
        else:
            n_scan = cfg.num_layers
            k_dense = cfg.moe.first_k_dense if cfg.moe else 0
            if k_dense:
                p["prefix"] = [
                    init_attn_block(jax.random.fold_in(keys[4], i), cfg, mode,
                                    dtype, dense_ffn=cfg.moe.dense_d_ff)
                    for i in range(k_dense)
                ]
                n_scan -= k_dense
            p["layers"] = jax.vmap(
                lambda k: init_attn_block(k, cfg, mode, dtype)
            )(jax.random.split(keys[5], n_scan))
        return p

    def param_specs(self) -> Params:
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    # -- embedding / head ----------------------------------------------------
    def _embed(self, p: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        if "embeds" in batch:  # vlm/audio frontend stub
            return batch["embeds"].astype(self.dtype)
        return layers.embed_tokens(p["embed"], batch["tokens"], self.mode, self.dtype)

    def _logits(self, p: Params, x: jax.Array) -> jax.Array:
        if self.cfg.tie_embeddings:
            logits = layers.tied_logits(p["embed"], x, self.mode)
        else:
            logits = layers.lm_head_logits(p["head"], x, self.mode)
        if self.cfg.vocab_padded != self.cfg.vocab_size:
            # pad slots exist only to keep the vocab-sharded table divisible
            # across lanes; mask them out of every softmax/argmax.
            pad_mask = jnp.arange(self.cfg.vocab_padded) < self.cfg.vocab_size
            logits = jnp.where(pad_mask, logits, NEG_INF)
        return logits

    # -- backbone (full sequence) -------------------------------------------
    def backbone(self, p: Params, x: jax.Array, **kw) -> Tuple[jax.Array, jax.Array]:
        with self._shard_scope():
            return self._backbone(p, x, **kw)

    def _backbone(self, p: Params, x: jax.Array, **kw) -> Tuple[jax.Array, jax.Array]:
        cfg, mode = self.cfg, self.mode
        aux_total = jnp.zeros((), jnp.float32)
        x = self._c(x)

        def maybe_remat(f):
            return jax.checkpoint(f) if self.remat else f

        if cfg.family == "ssm":
            def body(carry, lp):
                out = ssm_mod.mamba2_train(lp, _pre_norm(carry, cfg), cfg, mode, **kw)
                return self._c(carry + out), None
            x, _ = jax.lax.scan(maybe_remat(body), x, p["mamba"])
        elif cfg.family == "hybrid":
            n_attn, mpg, trailing = hybrid_layout(cfg)
            head_p = jax.tree.map(
                lambda t: t[:n_attn * mpg].reshape(n_attn, mpg, *t.shape[1:]),
                p["mamba"])
            tail_p = jax.tree.map(lambda t: t[n_attn * mpg:], p["mamba"])

            def group(carry, gp):
                h = carry
                for i in range(mpg):
                    lp = jax.tree.map(lambda t, i=i: t[i], gp)
                    h = h + ssm_mod.mamba2_train(lp, _pre_norm(h, cfg), cfg, mode, **kw)
                h, _ = attn_block_train(p["shared_attn"], h, cfg, mode,
                                        self.attn_chunk, **kw)
                return self._c(h), None

            x, _ = jax.lax.scan(maybe_remat(group), x, head_p)
            for i in range(trailing):
                lp = jax.tree.map(lambda t: t[i], tail_p)
                x = x + ssm_mod.mamba2_train(lp, _pre_norm(x, cfg), cfg, mode, **kw)
        else:
            for lp in p.get("prefix", []):
                x, aux = attn_block_train(lp, x, cfg, mode, self.attn_chunk, **kw)
                aux_total += aux

            def body(carry, lp):
                h, aux_sum = carry
                h, aux = attn_block_train(lp, h, cfg, mode, self.attn_chunk, **kw)
                return (self._c(h), aux_sum + aux), None
            (x, aux_total), _ = jax.lax.scan(maybe_remat(body), (x, aux_total),
                                             p["layers"])
        x = layers.rms_norm(x, p["final_norm"]["w"], cfg.norm_eps)
        return x, aux_total

    # -- training loss --------------------------------------------------------
    def loss_fn(self, p: Params, batch: Dict[str, jax.Array]
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        x = self._embed(p, batch)
        x, aux = self.backbone(p, x, train=(self.mode != "serve"))
        labels = batch["labels"]
        b, s = labels.shape
        chunk = min(self.loss_chunk, s)
        nc = s // chunk

        def chunk_loss(args):
            xc, yc = args
            logits = self._logits(p, xc)                     # (B, c, V) f32
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, yc[..., None].astype(jnp.int32),
                                       axis=-1)[..., 0]
            valid = (yc >= 0)
            nll = jnp.where(valid, logz - gold, 0.0)
            return jnp.sum(nll), jnp.sum(valid)

        xs = x.reshape(b, nc, chunk, cfg.d_model).swapaxes(0, 1)
        ys = labels.reshape(b, nc, chunk).swapaxes(0, 1)
        totals = jax.lax.map(jax.checkpoint(chunk_loss), (xs, ys))
        loss = jnp.sum(totals[0]) / jnp.maximum(jnp.sum(totals[1]), 1.0)
        aux_w = 0.01 if cfg.moe is not None else 0.0
        total = loss + aux_w * aux
        return total, {"ce_loss": loss, "aux_loss": aux, "tokens": jnp.sum(totals[1])}

    # -- caches ----------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int) -> Params:
        cfg = self.cfg
        cache: Params = {}
        if cfg.family == "ssm":
            cache["states"] = ssm_mod.init_ssm_state(cfg, batch, cfg.num_layers)
        elif cfg.family == "hybrid":
            n_attn, mpg, trailing = hybrid_layout(cfg)
            cache["states"] = ssm_mod.init_ssm_state(cfg, batch, n_attn * mpg + trailing)
            cache.update(attn_mod.init_kv_cache(cfg, batch, max_len, n_attn))
        elif cfg.attention_kind == "mla":
            cache.update(attn_mod.init_mla_cache(cfg, batch, max_len, cfg.num_layers))
        else:
            cache.update(attn_mod.init_kv_cache(cfg, batch, max_len, cfg.num_layers))
        return cache

    def cache_specs(self, batch: int, max_len: int) -> Params:
        return jax.eval_shape(lambda: self.init_cache(batch, max_len))

    # -- decode step ------------------------------------------------------------
    def decode_step(self, p: Params, cache, token_or_embed: jax.Array,
                    pos: jax.Array, adapter_idx: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, Params]:
        """One token for the whole batch. token: (B,) int32 (or (B, D) stub
        embed). ``adapter_idx`` (B,) selects each slot's resident multi-tenant
        LoRA adapter (serving/adapters/; 0 = none).

        ``cache`` is either the dict cache from :meth:`init_cache` (dense /
        ssm / hybrid / MLA) or an :class:`~repro.models.attention.PagedKVState`
        handed over by a paged KV backend — block tables instead of a
        contiguous cache, attention dispatched per ``self.paged_attn``.
        Returns (logits (B, V) f32, new cache of the same kind)."""
        if isinstance(cache, attn_mod.PagedKVState):
            return self._paged_decode_step(p, cache, token_or_embed, pos,
                                           adapter_idx)
        cfg, mode = self.cfg, self.mode
        kw = {"fuse": self.fuse_proj, "kv_dtype": self.kv_widen}
        if adapter_idx is not None:
            kw["adapter_idx"] = adapter_idx
        if token_or_embed.ndim == 1:
            x = layers.embed_tokens(p["embed"], token_or_embed, mode, self.dtype)
        else:
            x = token_or_embed.astype(self.dtype)

        new_cache = dict(cache)
        if cfg.family == "ssm":
            def body(h, inp):
                lp, st, cv = inp
                h2 = _pre_norm(h, cfg)
                out, st2, cv2 = ssm_mod.mamba2_decode(lp, h2, st, cv, cfg, mode, **kw)
                return h + out, (st2, cv2)
            x, (st, cv) = jax.lax.scan(body, x, (p["mamba"], cache["states"]["ssm"],
                                                 cache["states"]["conv"]))
            new_cache["states"] = {"ssm": st, "conv": cv}
        elif cfg.family == "hybrid":
            x, new_cache = self._hybrid_decode(p, cache, x, pos, **kw)
        else:
            prefix = p.get("prefix", [])
            kd = len(prefix)
            c0, c1 = self._cache_pair(cache)
            for i, lp in enumerate(prefix):
                x, (s0, s1), _ = attn_block_decode(lp, x, (c0[i], c1[i]), pos, cfg,
                                                   mode, **kw)
                c0 = c0.at[i].set(s0)
                c1 = c1.at[i].set(s1)

            def body(h, inp):
                lp, a, b_ = inp
                h, (a2, b2), _ = attn_block_decode(lp, h, (a, b_), pos, cfg, mode, **kw)
                return h, (a2, b2)
            x, (n0, n1) = jax.lax.scan(body, x, (p["layers"], c0[kd:], c1[kd:]))
            with names.scope(names.KV_APPEND):
                c0 = jax.lax.dynamic_update_slice_in_dim(c0, n0, kd, 0)
                c1 = jax.lax.dynamic_update_slice_in_dim(c1, n1, kd, 0)
            new_cache = self._cache_unpair(cache, c0, c1)

        x = layers.rms_norm(x, p["final_norm"]["w"], cfg.norm_eps)
        logits = self._logits(p, x)
        return logits, new_cache

    # -- paged decode (block tables through the attention stack) ---------------
    def _paged_decode_step(self, p: Params, state, token_or_embed: jax.Array,
                           pos: jax.Array,
                           adapter_idx: Optional[jax.Array] = None):
        """decode_step over a PagedKVState: the slot's block table reaches
        decode attention directly. GQA families only (the paged pool layout
        is (L, pages, Hkv, page, D))."""
        cfg = self.cfg
        assert cfg.attention_kind == "gqa" and cfg.family not in ("ssm", "hybrid"), \
            "paged decode needs a GQA KV cache"
        assert pos.ndim == 1, "paged decode is batched (per-slot positions)"
        mode = self.paged_attn
        if mode == "auto":
            mode = "gather" if jax.default_backend() == "cpu" else "kernel"
        if mode == "gather":
            return self._paged_decode_gather(p, state, token_or_embed, pos,
                                             adapter_idx)
        return self._paged_decode_kernel(p, state, token_or_embed, pos,
                                         adapter_idx)

    def _paged_decode_gather(self, p, state, token_or_embed, pos, adapter_idx):
        """XLA reference: gather the contiguous view from the block tables
        *inside* the jitted step, run the exact dense decode body on it, then
        scatter the new token's k/v back into its page. Op-for-op the dense
        math → token-identical dense↔paged greedy outputs."""
        with names.scope(names.ATTN):
            cache = {"k": attn_mod.gather_pages(state.k_pool, state.tables),
                     "v": attn_mod.gather_pages(state.v_pool, state.tables)}
        logits, new_cache = self.decode_step(p, cache, token_or_embed, pos,
                                             adapter_idx)
        with names.scope(names.KV_APPEND):
            # clip, don't fill: inactive slots carry a stale `pos` that can
            # exceed the gathered view (their write lands on the scratch
            # page and is never read), and jnp's OOB fill value is NaN —
            # which would poison the scratch page and leak into live rows
            # via table padding
            idx = pos.reshape(1, -1, 1, 1, 1).astype(jnp.int32)
            k_tok = jnp.take_along_axis(new_cache["k"], idx, axis=3,
                                        mode="clip")[:, :, :, 0]
            v_tok = jnp.take_along_axis(new_cache["v"], idx, axis=3,
                                        mode="clip")[:, :, :, 0]
            k_pool = attn_mod.scatter_tokens(state.k_pool, state.write_page,
                                             state.write_off, k_tok)
            v_pool = attn_mod.scatter_tokens(state.v_pool, state.write_page,
                                             state.write_off, v_tok)
        return logits, dataclasses.replace(state, k_pool=k_pool, v_pool=v_pool)

    def _paged_decode_kernel(self, p, state, token_or_embed, pos, adapter_idx):
        """Pallas path: per layer, write the token into its page
        (`paged_kv_append`) and run `paged_flash_decode` — the block table
        rides in via scalar prefetch and picks which pool page each context
        step DMAs HBM→VMEM. The layer scan carries the whole pool; each
        layer writes into it in place and the kernel reads its layer of
        that same buffer, so neither a contiguous view nor a per-layer copy
        of the pool is materialized."""
        cfg, mode = self.cfg, self.mode
        interpret = jax.default_backend() == "cpu"
        kw = {"fuse": self.fuse_proj, "kv_dtype": self.kv_widen}
        if adapter_idx is not None:
            kw["adapter_idx"] = adapter_idx
        if token_or_embed.ndim == 1:
            x = layers.embed_tokens(p["embed"], token_or_embed, mode, self.dtype)
        else:
            x = token_or_embed.astype(self.dtype)

        def block(lp, h, kp, vp, layer):
            hn = layers.rms_norm(h, lp["norm1"]["w"], cfg.norm_eps)
            a, kp, vp = attn_mod.gqa_decode_paged(
                lp["attn"], hn, kp, vp, layer, state.tables, state.write_page,
                state.write_off, state.lengths, pos, cfg, mode,
                interpret=interpret, **kw)
            h = h + a
            h2 = layers.rms_norm(h, lp["norm2"]["w"], cfg.norm_eps)
            if "moe" in lp:
                f, _ = moe_mod.moe_ffn(lp["moe"], h2, cfg, mode, **kw)
            else:
                f = layers.apply_ffn(lp["ffn"], h2, cfg.ffn_kind, mode, **kw)
            return h + f, kp, vp

        prefix = p.get("prefix", [])
        kd = len(prefix)
        kp, vp = state.k_pool, state.v_pool
        for i, lp in enumerate(prefix):
            x, kp, vp = block(lp, x, kp, vp, jnp.int32(i))

        def body(carry, inp):
            lp, layer = inp
            return block(lp, *carry, layer), None

        n_layers = kp.shape[0]
        (x, kp, vp), _ = jax.lax.scan(
            body, (x, kp, vp),
            (p["layers"], jnp.arange(kd, n_layers, dtype=jnp.int32)))
        x = layers.rms_norm(x, p["final_norm"]["w"], cfg.norm_eps)
        return self._logits(p, x), dataclasses.replace(state, k_pool=kp,
                                                       v_pool=vp)

    # -- speculative-decode verify ---------------------------------------------
    def verify_step(self, p: Params, cache, tokens: jax.Array,
                    pos: jax.Array, adapter_idx: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, Params]:
        """Score S = k+1 positions per slot in one jitted call (speculative
        decoding's verify). ``tokens`` (B, S) int32 — position 0 is the
        tick's fed token, positions 1.. the proposer's drafts; ``pos`` (B,)
        is each slot's next cache position. Returns ``(logits (B, S, V) f32,
        spans {"k","v"}: (L, B, Hkv, S, D))`` in the fp8 cache encoding.

        The S positions run as a ``lax.scan`` of :meth:`decode_step` —
        op-for-op the single-token decode on every backend (dense math, the
        XLA gather reference, the Pallas ``paged_flash_decode`` views with
        drafts landing page-by-page), so per-position logits are
        **bit-identical** to what sequential decode would produce. That is
        the accept/reject contract: greedy and seeded choices match the
        non-speculative engine exactly, never just approximately. One jit
        dispatch replaces k+1 tick round-trips (the tick-bound overhead
        speculation exists to amortize), and XLA hoists the loop-invariant
        ternary weight decode out of the scan, so drafted positions reuse
        the ROM stream a sequential host loop would re-read.

        Cache/pool mutations stay inside the trace: the dense carry and the
        paged pool copy are discarded by the engine, which commits only the
        accepted span from the returned ``spans`` through the KV backend
        (sliced dense writes / ``PagePool.write_span``) — rejected drafts
        never reach storage. For a paged ``cache``, ``write_page`` /
        ``write_off`` must be the **(B, S)** per-position targets from
        ``PagedKV.verify_state``. GQA families only (same restriction as
        the mid-sequence prefill)."""
        cfg = self.cfg
        assert cfg.attention_kind == "gqa" and cfg.family not in ("ssm", "hybrid"), \
            "speculative verify needs a GQA KV cache"
        assert pos.ndim == 1, "verify is batched (per-slot positions)"
        s = tokens.shape[1]
        if isinstance(cache, attn_mod.PagedKVState):
            def body(state, inp):
                t_j, j, wp_j, wo_j = inp
                st_j = dataclasses.replace(state, write_page=wp_j,
                                           write_off=wo_j,
                                           lengths=pos + j + 1)
                lg, st_new = self.decode_step(p, st_j, t_j, pos + j,
                                              adapter_idx)
                state = dataclasses.replace(state, k_pool=st_new.k_pool,
                                            v_pool=st_new.v_pool)
                return state, lg

            state, lgs = jax.lax.scan(
                body, cache, (tokens.T, jnp.arange(s),
                              jnp.moveaxis(cache.write_page, 1, 0),
                              jnp.moveaxis(cache.write_off, 1, 0)))
            # pull the drafted span back out of the (functional) pool copy:
            # advanced (B, S) page/offset indices land the batch dims first
            wp, wo = cache.write_page, cache.write_off
            k_span = state.k_pool[:, wp, :, wo].transpose(2, 0, 3, 1, 4)
            v_span = state.v_pool[:, wp, :, wo].transpose(2, 0, 3, 1, 4)
            return jnp.moveaxis(lgs, 0, 1), {"k": k_span, "v": v_span}

        def body(c, inp):
            t_j, j = inp
            lg, c = self.decode_step(p, c, t_j, pos + j, adapter_idx)
            return c, lg

        c, lgs = jax.lax.scan(body, cache, (tokens.T, jnp.arange(s)))
        # mode="clip" for the same reason as the paged decode path: stale
        # positions on inactive rows must not pull in jnp's NaN OOB fill
        idx = (pos[:, None] + jnp.arange(s))[None, :, None, :, None]
        k_span = jnp.take_along_axis(c["k"], idx, axis=3, mode="clip")
        v_span = jnp.take_along_axis(c["v"], idx, axis=3, mode="clip")
        return jnp.moveaxis(lgs, 0, 1), {"k": k_span, "v": v_span}


    def _cache_pair(self, cache):
        if self.cfg.attention_kind == "mla":
            return cache["latent"], cache["k_rope"]
        return cache["k"], cache["v"]

    def _cache_unpair(self, cache, c0, c1):
        out = dict(cache)
        if self.cfg.attention_kind == "mla":
            out["latent"], out["k_rope"] = c0, c1
        else:
            out["k"], out["v"] = c0, c1
        return out

    def _hybrid_decode(self, p, cache, x, pos, **kw):
        cfg, mode = self.cfg, self.mode
        n_attn, mpg, trailing = hybrid_layout(cfg)
        st, cv = cache["states"]["ssm"], cache["states"]["conv"]
        kc, vc = cache["k"], cache["v"]
        mam = p["mamba"]
        head_idx = n_attn * mpg
        gp = jax.tree.map(lambda t: t[:head_idx].reshape(n_attn, mpg, *t.shape[1:]), mam)
        st_g = st[:head_idx].reshape(n_attn, mpg, *st.shape[1:])
        cv_g = cv[:head_idx].reshape(n_attn, mpg, *cv.shape[1:])

        def group(h, inp):
            g, s_g, c_g, k_l, v_l = inp
            new_s, new_c = [], []
            for i in range(mpg):
                lp = jax.tree.map(lambda t: t[i], g)
                out, s2, c2 = ssm_mod.mamba2_decode(lp, _pre_norm(h, cfg), s_g[i],
                                                    c_g[i], cfg, mode, **kw)
                h = h + out
                new_s.append(s2)
                new_c.append(c2)
            h, (k2, v2), _ = attn_block_decode(p["shared_attn"], h, (k_l, v_l), pos,
                                               cfg, mode, **kw)
            return h, (jnp.stack(new_s), jnp.stack(new_c), k2, v2)

        x, (s_new, c_new, k_new, v_new) = jax.lax.scan(
            group, x, (gp, st_g, cv_g, kc, vc))
        st = st.at[:head_idx].set(s_new.reshape(head_idx, *st.shape[1:]))
        cv = cv.at[:head_idx].set(c_new.reshape(head_idx, *cv.shape[1:]))
        for i in range(trailing):
            lp = jax.tree.map(lambda t: t[head_idx + i], mam)
            out, s2, c2 = ssm_mod.mamba2_decode(lp, _pre_norm(x, cfg),
                                                st[head_idx + i], cv[head_idx + i],
                                                cfg, mode, **kw)
            x = x + out
            st = st.at[head_idx + i].set(s2)
            cv = cv.at[head_idx + i].set(c2)
        new_cache = dict(cache)
        new_cache["states"] = {"ssm": st, "conv": cv}
        new_cache["k"], new_cache["v"] = k_new, v_new
        return x, new_cache

    # -- prefill ------------------------------------------------------------------
    def prefill(self, p: Params, batch: Dict[str, jax.Array], max_len: int, *,
                pos_offset: int = 0, prefix_kv: Optional[Params] = None,
                adapter_idx: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Params]:
        """Process the whole prompt, fill the cache, return last-token logits.

        Batched prefill (beyond-paper default; the paper's token-by-token
        prefill is available in the simulator + serving engine).

        ``pos_offset``/``prefix_kv`` resume prefill mid-sequence: positions
        start at ``pos_offset``, the cache fills from there, and the prompt
        remainder attends to the already-committed prefix k/v (``{"k","v"}:
        (L, B, Hkv, P, D)`` in the fp8 cache encoding). The prefix is either
        a prefix-cache hit's shared pages or — for chunked prefill — the
        earlier chunks of the same prompt, so chunk i of a long prompt
        resumes at ``pos_offset = i·C`` through the exact same path on both
        KV backends (serving/kv.py materializes ``prefix_kv`` token-granular,
        so chunk boundaries need not be page-aligned). GQA attention families
        only. ``adapter_idx`` threads the multi-tenant LoRA selection (one
        entry per batch row)."""
        with self._shard_scope():
            return self._prefill(p, batch, max_len, pos_offset=pos_offset,
                                 prefix_kv=prefix_kv, adapter_idx=adapter_idx)

    def _prefill(self, p: Params, batch: Dict[str, jax.Array], max_len: int, *,
                 pos_offset: int = 0, prefix_kv: Optional[Params] = None,
                 adapter_idx: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, Params]:
        cfg, mode = self.cfg, self.mode
        x = self._embed(p, batch)
        b, s, _ = x.shape
        cache = self.init_cache(b, max_len)
        kw: Dict[str, Any] = {}
        if adapter_idx is not None:
            kw["adapter_idx"] = adapter_idx
        # (order matters: a resume always carries prefix_kv, and pos_offset
        # may then be a traced scalar — never force bool() on it)
        if prefix_kv is not None or pos_offset:
            assert cfg.attention_kind == "gqa" and cfg.family not in ("ssm", "hybrid"), \
                "mid-sequence prefill (prefix-cache resume) is GQA-only"

        if cfg.family in ("ssm", "hybrid"):
            # run full-seq backbone while extracting final states: recompute
            # states via a decode sweep would be O(S); instead prefill for SSM
            # families processes the sequence chunk-wise through train path and
            # rebuilds states with a final decode of the last token. For the
            # dry-run cells, prefill shapes are only assigned to attention
            # archs' KV path; SSM prefill fills KV (hybrid) + states.
            x_full, _ = self.backbone(p, x, train=False)
            logits = self._logits(p, x_full[:, -1])
            return logits, cache

        prefix = p.get("prefix", [])
        kd = len(prefix)
        c0, c1 = self._cache_pair(cache)
        pk = pv = None
        if prefix_kv is not None:
            pk, pv = prefix_kv["k"], prefix_kv["v"]     # (L, B, Hkv, P, D)

        def fill_block(lp, h, c0_l, c1_l, pk_l=None, pv_l=None):
            hn = layers.rms_norm(h, lp["norm1"]["w"], cfg.norm_eps)
            if cfg.attention_kind == "mla":
                a, c0_l, c1_l = _mla_prefill_fill(lp["attn"], hn, c0_l, c1_l, cfg,
                                                  mode, self.attn_chunk, **kw)
            else:
                a, c0_l, c1_l = _gqa_prefill_fill(lp["attn"], hn, c0_l, c1_l, cfg,
                                                  mode, self.attn_chunk,
                                                  pos_offset=pos_offset,
                                                  prefix_k=pk_l, prefix_v=pv_l,
                                                  **kw)
            h = h + a
            h2 = layers.rms_norm(h, lp["norm2"]["w"], cfg.norm_eps)
            if "moe" in lp:
                f, _ = moe_mod.moe_ffn(lp["moe"], h2, cfg, mode, **kw)
            else:
                f = layers.apply_ffn(lp["ffn"], h2, cfg.ffn_kind, mode, **kw)
            return h + f, c0_l, c1_l

        for i, lp in enumerate(prefix):
            x, s0, s1 = fill_block(lp, x, c0[i], c1[i],
                                   None if pk is None else pk[i],
                                   None if pv is None else pv[i])
            c0 = c0.at[i].set(s0)
            c1 = c1.at[i].set(s1)

        if pk is None:
            def body(h, inp):
                lp, a, b_ = inp
                h, a2, b2 = fill_block(lp, h, a, b_)
                return self._c(h), (a2, b2)

            body = jax.checkpoint(body) if self.remat else body
            x, (n0, n1) = jax.lax.scan(body, x, (p["layers"], c0[kd:], c1[kd:]))
        else:
            def body(h, inp):
                lp, a, b_, pk_l, pv_l = inp
                h, a2, b2 = fill_block(lp, h, a, b_, pk_l, pv_l)
                return self._c(h), (a2, b2)

            body = jax.checkpoint(body) if self.remat else body
            x, (n0, n1) = jax.lax.scan(
                body, x, (p["layers"], c0[kd:], c1[kd:], pk[kd:], pv[kd:]))
        with names.scope(names.KV_APPEND):
            c0 = jax.lax.dynamic_update_slice_in_dim(c0, n0, kd, 0)
            c1 = jax.lax.dynamic_update_slice_in_dim(c1, n1, kd, 0)
        cache = self._cache_unpair(cache, c0, c1)
        x = layers.rms_norm(x, p["final_norm"]["w"], cfg.norm_eps)
        return self._logits(p, x[:, -1]), cache


def _pre_norm(x, cfg):
    # mamba blocks norm with a unit-weight RMS (their own gate_norm carries the
    # learnable scale)
    return layers.rms_norm(x, jnp.ones((cfg.d_model,), jnp.float32), cfg.norm_eps)


def _attend_with_prefix(q, k_new, v_new, k_pref, v_pref, pos_offset):
    """Causal attention for a prompt remainder that starts mid-sequence: the
    queries (global positions ``pos_offset + s``) attend the already-cached
    prefix k/v (fp8 cache encoding, positions ``0..pos_offset``) plus the
    remainder's own keys. q/k/v: (B, S, H*, D); k_pref/v_pref: (B, Hkv, P, D).
    The prefix may be *padded* past the true length (P >= pos_offset — the
    serving engine buckets it to a power of two so chunked-prefill resumes
    reuse compiled graphs) and ``pos_offset`` may be a traced scalar: padded
    prefix keys are masked out by position. Plain masked softmax — the
    serving prefill path is batch-1 and bounded by max_len, so no
    chunking/remat is needed."""
    b, s, h, d = q.shape
    hkv = k_new.shape[2]
    g = h // hkv
    p_len = k_pref.shape[2]          # padded prefix length (>= pos_offset)
    kp = (k_pref.astype(jnp.float32) * KV_CACHE_SCALE).transpose(0, 2, 1, 3)
    vp = (v_pref.astype(jnp.float32) * KV_CACHE_SCALE).transpose(0, 2, 1, 3)
    k_all = jnp.concatenate([kp, k_new.astype(jnp.float32)], axis=1)  # (B,T,Hkv,D)
    v_all = jnp.concatenate([vp, v_new.astype(jnp.float32)], axis=1)
    t = k_all.shape[1]
    qg = q.reshape(b, s, hkv, g, d).astype(jnp.float32)
    scores = jnp.einsum("bshgd,bthd->bshgt", qg, k_all) * (d ** -0.5)
    # key index j: a prefix slot (j < p_len) is real iff j < pos_offset; a
    # remainder key (j - p_len) is causally visible to query i iff <= i
    tidx = jnp.arange(t)[None, :]
    qidx = jnp.arange(s)[:, None]
    visible = jnp.where(tidx < p_len, tidx < pos_offset,
                        (tidx - p_len) <= qidx)
    scores = jnp.where(visible[None, :, None, None, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    pr = jnp.exp(scores - m)
    den = jnp.sum(pr, axis=-1, keepdims=True)
    out = jnp.einsum("bshgt,bthd->bshgd", pr / jnp.maximum(den, 1e-30), v_all)
    return out.reshape(b, s, h, d).astype(q.dtype)


def _gqa_prefill_fill(p, h, k_cache, v_cache, cfg, mode, chunk, *,
                      pos_offset=0, prefix_k=None, prefix_v=None, **kw):
    b, s, _ = h.shape
    positions = jnp.arange(s)[None, :] + pos_offset
    q, k, v = attn_mod._project_qkv(p, h, cfg, mode, positions, **kw)
    with names.scope(names.ATTN):
        if prefix_k is None:
            out = attn_mod.chunked_causal_attention(
                q, k, v, chunk_q=min(chunk, s), chunk_k=min(chunk, s))
        else:
            out = _attend_with_prefix(q, k, v, prefix_k, prefix_v, pos_offset)
    out = layers.apply_linear(p["o"], out.reshape(b, s, cfg.q_dim), mode, **kw)
    with names.scope(names.KV_APPEND):
        k_c = (k / KV_CACHE_SCALE).transpose(0, 2, 1, 3).astype(k_cache.dtype)
        v_c = (v / KV_CACHE_SCALE).transpose(0, 2, 1, 3).astype(v_cache.dtype)
        k_cache = jax.lax.dynamic_update_slice(k_cache, k_c,
                                               (0, 0, pos_offset, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, v_c,
                                               (0, 0, pos_offset, 0))
    return out, k_cache, v_cache


def _mla_prefill_fill(p, h, latent_cache, rope_cache, cfg, mode, chunk, **kw):
    b, s, _ = h.shape
    positions = jnp.arange(s)[None, :]
    out = attn_mod.mla_train(p, h, cfg, mode, chunk=chunk, **kw)
    latent, k_rope = attn_mod._mla_latent(p, h, cfg, mode, positions, **kw)
    latent_cache = jax.lax.dynamic_update_slice(
        latent_cache, (latent / KV_CACHE_SCALE).astype(latent_cache.dtype), (0, 0, 0))
    rope_cache = jax.lax.dynamic_update_slice(
        rope_cache, (k_rope / KV_CACHE_SCALE).astype(rope_cache.dtype), (0, 0, 0))
    return out, latent_cache, rope_cache

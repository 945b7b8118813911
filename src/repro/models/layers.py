"""Shared model layers: norms, RoPE, the three-mode ternary Linear, FFNs,
embeddings.

Every linear in every architecture runs in one of three modes (DESIGN.md §2):

  * ``qat``   — float master weights, BitNet-style ternary STE fake-quant on
                the forward (+ fp8 fake-quant on activations when enabled).
                Used for training from scratch (the way BitNet-2B was made).
  * ``serve`` — weights are packed 2-bit 'ROM' (uint8 (K/4, N) + f32 scale),
                immutable; the paper's deployment form.
  * ``qlora`` — serve-mode base + trainable float LoRA adapters (C4).

Parameters are plain dict pytrees so they stack cleanly for scan-over-layers
and shard with PartitionSpec trees (models/sharding.py).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import fp8, qlora, ternary
from repro.obs import names

Params = Dict[str, jax.Array]

#: Static fp8 KV-cache scale (e4m3 is floating — the scale only guards
#: overflow past ±448; post-norm K/V magnitudes are O(1..30)).
KV_CACHE_SCALE = 4.0


# ---------------------------------------------------------------------------
# Norms & activations
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)).astype(dt)


def init_rms_norm(d: int, dtype=jnp.float32) -> Params:
    return {"w": jnp.ones((d,), dtype)}


ACTIVATIONS = {
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}


def lora_for(cfg, name: str, mode: str) -> Optional[qlora.LoRASpec]:
    """LoRASpec for projection ``name`` iff qlora mode and it's a target.

    Target names follow LoRA convention: q/k/v/o (attention; MLA's q_b and
    kv_b count as 'q'/'v'), up/gate/down (FFN), in_proj/out_proj (Mamba2)."""
    if mode != "qlora" or cfg.lora is None:
        return None
    targets = cfg.lora.targets
    if targets == ("all",) or name in targets:
        return qlora.LoRASpec(rank=cfg.lora.rank, alpha=cfg.lora.alpha,
                              ternary=cfg.lora.ternary_adapters)
    return None


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D) or (..., H, D) with positions broadcastable to S."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                     # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]              # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Three-mode Linear
# ---------------------------------------------------------------------------


def init_linear(key: jax.Array, k: int, n: int, mode: str, *,
                dtype=jnp.bfloat16, lora: Optional[qlora.LoRASpec] = None) -> Params:
    if mode == "qat":
        w = jax.random.normal(key, (k, n), jnp.float32) * (k ** -0.5)
        return {"w": w.astype(dtype)}
    # serve / qlora: packed ROM form
    w = jax.random.normal(key, (k, n), jnp.float32) * (k ** -0.5)
    t, s = ternary.quantize(w)
    p: Params = {"packed": ternary.pack2(t), "scale": s}
    if mode == "qlora" and lora is not None:
        p["lora"] = qlora.init_adapter(jax.random.fold_in(key, 1), k, n, lora)
    return p


def linear_spec(k: int, n: int, mode: str, *,
                lora: Optional[qlora.LoRASpec] = None, dtype=jnp.bfloat16) -> Params:
    """ShapeDtypeStruct tree mirroring init_linear (for the dry-run)."""
    if mode == "qat":
        return {"w": jax.ShapeDtypeStruct((k, n), dtype)}
    p: Params = {
        "packed": jax.ShapeDtypeStruct((k // 4, n), jnp.uint8),
        "scale": jax.ShapeDtypeStruct((), jnp.float32),
    }
    if mode == "qlora" and lora is not None:
        p["lora"] = {
            "a": jax.ShapeDtypeStruct((k, lora.rank), jnp.float32),
            "b": jax.ShapeDtypeStruct((lora.rank, n), jnp.float32),
        }
    return p


def apply_linear(p: Params, x: jax.Array, mode: str, *,
                 fp8_acts: bool = False,
                 lora: Optional[qlora.LoRASpec] = None,
                 train: bool = False,
                 fuse: bool = False,
                 kv_dtype: str = "f32",
                 adapter_idx: Optional[jax.Array] = None,
                 scope: Optional[str] = names.TERNARY_PROJ) -> jax.Array:  # noqa: ARG001
    # ``fuse``/``kv_dtype`` are consumed by fused/attention call sites;
    # accepted (and ignored) here so the flags thread through **kw untouched.
    # ``adapter_idx`` (B,) selects each batch row's resident multi-tenant
    # adapter; it only acts on projections carrying a ``lora_mt`` stack.
    """The mode dispatch. In serve/qlora mode the base is ternary-packed ROM:
    decode-then-matmul (XLA fuses; the Pallas kernel path is selected by the
    serving engine for the hot GEMVs where shapes allow), named ``scope``
    in the trace (None: the caller's own scope names it)."""
    if fp8_acts:
        x = fp8.fake_quantize(x)
    if mode == "qat":
        w = ternary.ste_quantize(p["w"].astype(jnp.float32))
        y = jnp.einsum("...k,kn->...n", x.astype(jnp.float32), w,
                       preferred_element_type=jnp.float32)
        return y.astype(x.dtype)
    with names.scope(scope) if scope else contextlib.nullcontext():
        # §Perf: decode the 2-bit ROM to bf16, not f32 — ternary {−1,0,+1}
        # is exact in bf16 and the dot still accumulates f32; halves the
        # dominant dequant HBM traffic (the Pallas kernel decodes in-VMEM).
        w = ternary.unpack2(p["packed"]).astype(jnp.bfloat16)
        # ROM immutability: gradients must not reach the base weight/scale —
        # but MUST keep flowing through x to earlier layers (stop-grad the
        # weight side only, never the matmul output).
        y = jnp.einsum("...k,kn->...n", x.astype(jnp.bfloat16), w,
                       preferred_element_type=jnp.float32)
        y = (y * jax.lax.stop_gradient(p["scale"])).astype(x.dtype)
    if mode == "qlora" and "lora" in p:
        y = y + qlora.adapter_path(x, p["lora"], lora or qlora.LoRASpec(),
                                   train=train).astype(y.dtype)
    if adapter_idx is not None and "lora_mt" in p:
        y = y + _multi_tenant_lora(p["lora_mt"], x, adapter_idx).astype(y.dtype)
    return y


def _multi_tenant_lora(mt: Params, x: jax.Array, adapter_idx: jax.Array) -> jax.Array:
    """Per-row gathered ternary-LoRA contribution (serving/adapters/). Rows
    whose index is 0 hit the null adapter (zero codes, zero scale) and
    contribute exactly 0 — bit-identical to a no-adapter engine."""
    from repro.kernels.batched_lora import ops as blora_ops
    with names.scope(names.LORA):
        return blora_ops.batched_lora(x, mt["a"], mt["b"], mt["s"],
                                      adapter_idx)


def apply_linear_fused(parts, x: jax.Array, mode: str, *,
                       fp8_acts: bool = False, train: bool = False,
                       lora=None, fuse: bool = True,
                       adapter_idx: Optional[jax.Array] = None):
    """Fused multi-projection linear: one matmul over N-concatenated weights.

    With Fig-7a K-sharding every GEMV's partial sum costs one tree reduction;
    q/k/v (and up/gate) share the same input x, so concatenating their packed
    weights along N turns 3 (resp. 2) all-reduces into ONE over the concat
    width — a pure collective-count win (§Perf cell C). Per-tensor scales are
    applied per output slice after the shared matmul. Serve/qlora path only.
    """
    if fp8_acts:
        x = fp8.fake_quantize(x)
    if mode == "qat":
        ws = [ternary.ste_quantize(p["w"].astype(jnp.float32)) for p in parts]
        w = jnp.concatenate(ws, axis=-1)
        y = jnp.einsum("...k,kn->...n", x.astype(jnp.float32), w,
                       preferred_element_type=jnp.float32)
        outs, off = [], 0
        for p in parts:
            n = p["w"].shape[-1]
            outs.append(y[..., off:off + n].astype(x.dtype))
            off += n
        return outs
    with names.scope(names.TERNARY_PROJ):
        packed = jnp.concatenate([p["packed"] for p in parts], axis=-1)
        w = ternary.unpack2(packed).astype(jnp.bfloat16)
        y = jnp.einsum("...k,kn->...n", x.astype(jnp.bfloat16), w,
                       preferred_element_type=jnp.float32)
    outs, off = [], 0
    for p in parts:
        n = p["packed"].shape[-1]
        with names.scope(names.TERNARY_PROJ):
            yi = (y[..., off:off + n]
                  * jax.lax.stop_gradient(p["scale"])).astype(x.dtype)
        if mode == "qlora" and "lora" in p:
            yi = yi + qlora.adapter_path(x, p["lora"], lora or qlora.LoRASpec(),
                                         train=train).astype(yi.dtype)
        if adapter_idx is not None and "lora_mt" in p:
            yi = yi + _multi_tenant_lora(p["lora_mt"], x, adapter_idx).astype(yi.dtype)
        outs.append(yi)
        off += n
    return outs


# ---------------------------------------------------------------------------
# FFN (swiglu / gelu / relu2), dense
# ---------------------------------------------------------------------------


def init_ffn(key: jax.Array, d: int, dff: int, kind: str, mode: str, *,
             lora_map: Optional[Dict[str, "qlora.LoRASpec"]] = None,
             **kw) -> Params:
    ks = jax.random.split(key, 3)
    lm = lora_map or {}
    p = {"up": init_linear(ks[0], d, dff, mode, lora=lm.get("up"), **kw),
         "down": init_linear(ks[1], dff, d, mode, lora=lm.get("down"), **kw)}
    if kind == "swiglu":
        p["gate"] = init_linear(ks[2], d, dff, mode, lora=lm.get("gate"), **kw)
    return p


def ffn_spec(d: int, dff: int, kind: str, mode: str, **kw) -> Params:
    p = {"up": linear_spec(d, dff, mode, **kw),
         "down": linear_spec(dff, d, mode, **kw)}
    if kind == "swiglu":
        p["gate"] = linear_spec(d, dff, mode, **kw)
    return p


def apply_ffn(p: Params, x: jax.Array, kind: str, mode: str, **kw) -> jax.Array:
    if kw.get("fuse") and kind == "swiglu" and mode != "qat":
        sub = {k: v for k, v in kw.items() if k not in ("fuse", "kv_dtype")}
        up, gate = apply_linear_fused([p["up"], p["gate"]], x, mode, **sub)
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(up.dtype) * up
    else:
        up = apply_linear(p["up"], x, mode, **kw)
        if kind == "swiglu":
            gate = apply_linear(p["gate"], x, mode, **kw)
            h = jax.nn.silu(gate.astype(jnp.float32)).astype(up.dtype) * up
        else:
            h = ACTIVATIONS[kind if kind in ACTIVATIONS else "gelu"](up)
    return apply_linear(p["down"], h, mode, **kw)


# ---------------------------------------------------------------------------
# Embedding (row-packed ternary in serve mode) + LM head
# ---------------------------------------------------------------------------


def pack_rows(t: jax.Array) -> jax.Array:
    """Ternary (V, D) → uint8 (V, D/4): each row packs its own features, so
    a token gather returns packed rows that unpack locally."""
    v, d = t.shape
    assert d % 4 == 0
    c = ternary.encode2(t.reshape(v, d // 4, 4).swapaxes(-1, -2))  # (V, 4, D/4)
    return (c[:, 0] | (c[:, 1] << 2) | (c[:, 2] << 4) | (c[:, 3] << 6)).astype(jnp.uint8)


def unpack_rows(p: jax.Array) -> jax.Array:
    """uint8 (..., D/4) → int8 (..., D)."""
    slots = [ternary.decode2((p >> (2 * i)) & 3) for i in range(4)]
    st = jnp.stack(slots, axis=-1)  # (..., D/4, 4)
    return st.reshape(*p.shape[:-1], p.shape[-1] * 4)


def init_embedding(key: jax.Array, vocab: int, d: int, mode: str,
                   dtype=jnp.bfloat16) -> Params:
    w = jax.random.normal(key, (vocab, d), jnp.float32) * 0.02
    if mode == "qat":
        return {"w": w.astype(dtype)}
    t, s = ternary.quantize(w)
    return {"packed_rows": pack_rows(t), "scale": s}


def embedding_spec(vocab: int, d: int, mode: str, dtype=jnp.bfloat16) -> Params:
    if mode == "qat":
        return {"w": jax.ShapeDtypeStruct((vocab, d), dtype)}
    return {"packed_rows": jax.ShapeDtypeStruct((vocab, d // 4), jnp.uint8),
            "scale": jax.ShapeDtypeStruct((), jnp.float32)}


def embed_tokens(p: Params, tokens: jax.Array, mode: str, dtype=jnp.bfloat16) -> jax.Array:
    with names.scope(names.EMBED):
        if mode == "qat":
            return p["w"][tokens].astype(dtype)
        rows = p["packed_rows"][tokens]           # (..., D/4) uint8 gather
        return (unpack_rows(rows).astype(jnp.float32)
                * p["scale"]).astype(dtype)


def lm_head_logits(head_p: Params, x: jax.Array, mode: str) -> jax.Array:
    """x (..., D) → logits (..., V). Head weight layout is (D, V) (or the
    packed column form); tied embeddings pass the embedding params through
    models/transformer.py which transposes appropriately."""
    with names.scope(names.LM_HEAD):
        return apply_linear(head_p, x, mode, scope=None).astype(jnp.float32)


def tied_logits(embed_p: Params, x: jax.Array, mode: str) -> jax.Array:
    with names.scope(names.LM_HEAD):
        if mode == "qat":
            return jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                              embed_p["w"].astype(jnp.float32))
        w = (unpack_rows(embed_p["packed_rows"]).astype(jnp.float32)
             * embed_p["scale"])
        return jnp.einsum("...d,vd->...v", x.astype(jnp.float32), w)

"""Operations and bytes the algorithm needs, from a configuration's shapes.

These are what the work requires, not what the compiled program happens to
do: a program that materialises an unpacked weight or reads padding moves
more bytes, and its share of the roofline falls. Every ternary weight counts
as one multiply-add (2 FLOPs) against the bf16 peak; packed ternary weights
are 2 bits; the KV cache is fp8 (1 byte per element).
"""
from __future__ import annotations

from chipbench.reference.model import Dims

KV_BYTES = 1          # fp8 e4m3 cache element
ACT_BYTES = 2         # bf16 activations
OUT_BYTES = 4         # f32 kernel outputs


def layer_linear_params(d: Dims) -> int:
    """Weights of the projections of one block (q, k, v, o and the FFN)."""
    attn = d.d_model * d.q_dim + 2 * d.d_model * d.kv_dim + d.q_dim * d.d_model
    ffn = 2 * d.d_model * d.d_ff
    return attn + ffn


def linear_params(d: Dims) -> int:
    """Projection weights of all blocks (embedding and head not included)."""
    return d.layers * layer_linear_params(d)


def head_params(d: Dims) -> int:
    return d.d_model * d.vocab


def kv_bytes_per_token(d: Dims) -> int:
    """fp8 K and V of one token over all layers."""
    return d.layers * 2 * d.kv_dim * KV_BYTES


def adapter_flops_per_token(d: Dims, rank: int, targets=("q", "v")) -> int:
    """The low-rank path of one token through every layer: x·A then ·B."""
    n_of = {"q": d.q_dim, "k": d.kv_dim, "v": d.kv_dim, "o": d.d_model}
    k_of = {"q": d.d_model, "k": d.d_model, "v": d.d_model, "o": d.q_dim}
    return d.layers * sum(2 * rank * (k_of[t] + n_of[t]) for t in targets)


def attn_flops(d: Dims, ctx: int) -> int:
    """QK and PV of one query against ``ctx`` keys, over all layers."""
    return d.layers * 4 * d.heads * d.head_dim * ctx


def decode_token_flops(d: Dims, ctx: int) -> int:
    """One decoded token whose attention sees ``ctx`` positions (itself
    included): projections, attention and the vocabulary head."""
    return 2 * linear_params(d) + 2 * head_params(d) + attn_flops(d, ctx)


def prefill_flops(d: Dims, n: int) -> int:
    """Prefill of ``n`` prompt tokens: projections of every token, causal
    attention (token i sees i + 1 positions) and the head at the last one."""
    return (2 * linear_params(d) * n
            + d.layers * 4 * d.heads * d.head_dim * n * (n + 1) // 2
            + 2 * head_params(d))


def packed_weight_bytes(d: Dims) -> int:
    """2-bit projections plus the 2-bit embedding and, if untied, head."""
    tables = head_params(d) * (1 if d.tied else 2)
    return (linear_params(d) + tables) // 4


# -- kernels ------------------------------------------------------------------


def flash_decode_call(d: Dims, contexts) -> tuple:
    """(flops, bytes) of ``paged_flash_decode`` over one layer for the live
    slots whose attention sees ``contexts`` positions: the fp8 K and V of
    those positions, each slot's bf16 query and its f32 output."""
    ctx = sum(int(c) for c in contexts)
    n = len(contexts)
    flops = 4 * d.heads * d.head_dim * ctx
    nbytes = (2 * d.kv_heads * d.head_dim * ctx * KV_BYTES
              + n * d.q_dim * (ACT_BYTES + OUT_BYTES))
    return flops, nbytes


def lora_call(d: Dims, rows: int, k: int, n: int, rank: int,
              adapters: int) -> tuple:
    """(flops, bytes) of one ``batched_lora_matmul`` call: ``rows`` token
    rows of width ``k`` through rank-``rank`` A (k, r) and B (r, n) of
    ``adapters`` distinct 2-bit adapters, f32 out."""
    flops = 2 * rows * rank * (k + n)
    nbytes = (rows * k * ACT_BYTES + adapters * (k * rank + rank * n) // 4
              + adapters * 4 + rows * n * OUT_BYTES)
    return flops, nbytes

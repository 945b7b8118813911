"""Plain float32 forward of the served decoder, for the check that decides
``correct``. Written from the configuration's equations in ``jax.numpy``,
with no kernel, cache, packing or batching of the program under test.

The block of ``configs/bitnet-2b.json`` (pre-norm); the tiny StarCoder2
test configuration takes the same module with tanh-GELU and an untied head:

    h  = rmsnorm(x)                       unit gain, eps from the config
    q, k, v = h·Wq, h·Wk, h·Wv  (+ the tenant's low-rank path on q and v)
    q, k  rotated by RoPE (half-split form, theta from the config)
    x += causal GQA softmax(q·kᵀ/√d)·v · Wo
    x += act(rmsnorm(x)·Wup)·Wdown        act = ReLU² or tanh-GELU
    logits = rmsnorm(x)·Wheadᵀ            tied: the embedding table

Weights are defined by the seed, as the served model draws them: each
projection W (k × n) is N(0, 1)·k^-½ from its own key and then ternarised
per tensor by absmean (t = clip(round(W / mean|W|), -1, 1), scale mean|W|);
the embedding table is N(0, 1)·0.02, ternarised the same way. Keys follow
the split tree ``split(PRNGKey(seed), 8)``: [0] embedding, [1] untied head,
[5] the layer stack (split per layer, then 3 ways: attention, FFN; attention
4 ways q, k, v, o; FFN 3 ways, of which up and down are used). A tenant's adapter is two
float draws from ``numpy.random.default_rng(seed + 1)`` per target, in
registration order (tenant, then q before v): A (L, K, r)·r^-½ and
B (L, r, N)·0.02, each ternarised per layer, scaled by α/r = 2.

Everything here runs in float32 under ``highest`` matmul precision. With
``low=True`` every matmul operand on the activation side is rounded to fp8
(e4m3, scaled per row): the control, one precision step below the bf16 the
configurations state.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import Seq

HI = jax.lax.Precision.HIGHEST
QUANT_EPS = 1e-8
FP8_MAX = 448.0
NEG = -1e30


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    act: str            # "relu2" | "gelu_tanh"
    tied: bool          # head shares the embedding table
    rope_theta: float
    norm_eps: float

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim


_ACTS = {"relu2": "relu2", "gelu_pytorch_tanh": "gelu_tanh"}


def dims_of(model: dict) -> Dims:
    """``Dims`` from the ``model`` block of a configuration file."""
    return Dims(layers=int(model["num_hidden_layers"]),
                d_model=int(model["hidden_size"]),
                heads=int(model["num_attention_heads"]),
                kv_heads=int(model["num_key_value_heads"]),
                head_dim=int(model["head_dim"]),
                d_ff=int(model["intermediate_size"]),
                vocab=int(model["vocab_size"]),
                act=_ACTS[model["hidden_act"]],
                tied=bool(model["tie_word_embeddings"]),
                rope_theta=float(model["rope_theta"]),
                norm_eps=float(model["rms_norm_eps"]))


def ternarise(w):
    """absmean ternary: (values in {-1, 0, 1} as f32, per-tensor scale)."""
    s = jnp.mean(jnp.abs(w).astype(jnp.float32))
    return jnp.clip(jnp.round(w / (s + QUANT_EPS)), -1, 1), s


def _proj(key, k: int, n: int):
    return ternarise(jax.random.normal(key, (k, n), jnp.float32) * (k ** -0.5))


def to_fp8(x):
    """Round to e4m3 with a per-row scale (the control's precision)."""
    a = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / FP8_MAX
    a = jnp.where(a > 0, a, 1.0)
    return (x / a).astype(jnp.float8_e4m3fn).astype(jnp.float32) * a


def _mm(x, w, low: bool):
    t, s = w
    x = to_fp8(x) if low else x
    return jnp.matmul(x, t, precision=HI) * s


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta: float):
    """x (B, S, H, D) at positions 0..S-1."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _act(u, kind: str):
    if kind == "relu2":
        return jnp.square(jnp.maximum(u, 0.0))
    if kind == "gelu_tanh":
        c = np.float32(np.sqrt(2.0 / np.pi))
        return 0.5 * u * (1.0 + jnp.tanh(c * (u + 0.044715 * u ** 3)))
    raise ValueError(f"unknown activation {kind!r}")


def _attention(q, k, v, low: bool, chunk: int):
    """Causal GQA, queries in chunks of ``chunk``. q (B,S,H,D), k/v
    (B,S,Hkv,D) → (B, S, H·D)."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    if low:
        q, k, v = to_fp8(q), to_fp8(k), to_fp8(v)
    nc = s // chunk
    qc = q.reshape(b, nc, chunk, hkv, g, d).transpose(1, 0, 3, 4, 2, 5)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    keys = jnp.arange(s)

    def one(args):
        i, qi = args
        sc = jnp.einsum("bhgqd,bhkd->bhgqk", qi, kt, precision=HI) * d ** -0.5
        qpos = i * chunk + jnp.arange(chunk)
        sc = jnp.where(qpos[:, None] >= keys[None, :], sc, NEG)
        p = jax.nn.softmax(sc, axis=-1)
        if low:
            p = to_fp8(p)
        return jnp.einsum("bhgqk,bhkd->bhgqd", p, vt, precision=HI)

    out = jax.lax.map(one, (jnp.arange(nc), qc))      # (nc,B,Hkv,G,c,D)
    return out.transpose(1, 0, 4, 2, 3, 5).reshape(b, s, h * d)


def _layer_weights(stack_key, i, dims: Dims):
    lk = jax.random.split(stack_key, dims.layers)[i]
    ks = jax.random.split(lk, 3)
    a = jax.random.split(ks[0], 4)
    f = jax.random.split(ks[1], 3)
    d = dims.d_model
    w = {"q": _proj(a[0], d, dims.q_dim), "k": _proj(a[1], d, dims.kv_dim),
         "v": _proj(a[2], d, dims.kv_dim), "o": _proj(a[3], dims.q_dim, d),
         "up": _proj(f[0], d, dims.d_ff), "down": _proj(f[1], dims.d_ff, d)}
    return w


def _lora(h, ad, aidx, low: bool):
    a, b, s = ad                      # (T+1, K, r), (T+1, r, N), (T+1,)
    h = to_fp8(h) if low else h
    z = jnp.einsum("bsk,bkr->bsr", h, a[aidx], precision=HI)
    y = jnp.einsum("bsr,brn->bsn", z, b[aidx], precision=HI)
    return y * s[aidx][:, None, None]


@functools.partial(jax.jit, static_argnames=("dims", "low", "chunk"))
def _block(x, stack_key, i, lora, aidx, *, dims: Dims, low: bool,
           chunk: int):
    w = _layer_weights(stack_key, i, dims)
    b, s, _ = x.shape
    h = _rms(x, dims.norm_eps)
    q, k, v = _mm(h, w["q"], low), _mm(h, w["k"], low), _mm(h, w["v"], low)
    if lora is not None:
        q = q + _lora(h, lora["q"], aidx, low)
        v = v + _lora(h, lora["v"], aidx, low)
    q = _rope(q.reshape(b, s, dims.heads, dims.head_dim), dims.rope_theta)
    k = _rope(k.reshape(b, s, dims.kv_heads, dims.head_dim), dims.rope_theta)
    v = v.reshape(b, s, dims.kv_heads, dims.head_dim)
    x = x + _mm(_attention(q, k, v, low, chunk), w["o"], low)
    h = _rms(x, dims.norm_eps)
    return x + _mm(_act(_mm(h, w["up"], low), dims.act), w["down"], low)


@functools.partial(jax.jit, static_argnames=("dims",))
def _tables(seed_key, *, dims: Dims):
    """(embedding values (V, D), its scale, head values (D, V), head scale)."""
    keys = jax.random.split(seed_key, 8)
    emb = ternarise(jax.random.normal(keys[0], (dims.vocab, dims.d_model),
                                      jnp.float32) * 0.02)
    head = emb if dims.tied else _proj(keys[1], dims.d_model, dims.vocab)
    return emb, head


@functools.partial(jax.jit, static_argnames=("dims", "low", "tied"))
def _gaps(x, pos, served, head, ref_logits_of=None, *, dims: Dims,
          low: bool, tied: bool):
    """Per scored position: the reference's best logit minus the logit of
    ``served`` (the program's token), and, from a second hidden state
    ``ref_logits_of`` computed at low precision, the gap of the token that
    the low-precision logits put first. x (B, S, D) final residual; pos
    (B, P) scored positions; served (B, P)."""
    t, s = head
    wt = t.T if tied else t                            # (D, V)

    def logits(h, lo):
        h = _rms(h, dims.norm_eps)
        h = to_fp8(h) if lo else h
        return jnp.matmul(h, wt, precision=HI) * s

    def one(args):
        xb, pb, sb, lb = args
        ref = logits(xb[pb], False)                    # (P, V)
        best = jnp.max(ref, axis=-1)
        gap = best - jnp.take_along_axis(ref, sb[:, None], -1)[:, 0]
        if lb is None:
            return gap, gap
        pick = jnp.argmax(logits(lb[pb], True), axis=-1)
        return gap, best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]

    if ref_logits_of is None:
        g, _ = jax.lax.map(lambda a: one((*a, None)), (x, pos, served))
        return g, None
    return jax.lax.map(one, (x, pos, served, ref_logits_of))


def adapter_stacks(dims: Dims, seed: int, tenants: int, rank: int):
    """Per target, (A (L, T+1, K, r), B (L, T+1, r, N), s (L, T+1)) in f32
    ternary values; row 0 of the tenant axis is the null adapter."""
    rng = np.random.default_rng(seed + 1)
    kn = {"q": (dims.d_model, dims.q_dim), "v": (dims.d_model, dims.kv_dim)}
    out = {t: ([], [], []) for t in kn}
    for _ in range(tenants):
        for target, (k, n) in kn.items():
            a = rng.normal(size=(dims.layers, k, rank)).astype(np.float32) \
                * (rank ** -0.5)
            b = rng.normal(size=(dims.layers, rank, n)).astype(np.float32) \
                * 0.02
            ta, sa = jax.vmap(ternarise)(jnp.asarray(a))
            tb, sb = jax.vmap(ternarise)(jnp.asarray(b))
            out[target][0].append(ta)
            out[target][1].append(tb)
            out[target][2].append(sa * sb * np.float32(2.0))
    stacks = {}
    for target, (k, n) in kn.items():
        z = lambda *shape: jnp.zeros((dims.layers, 1, *shape), jnp.float32)
        a = jnp.concatenate([z(k, rank)] + [x[:, None] for x in out[target][0]], 1)
        b = jnp.concatenate([z(rank, n)] + [x[:, None] for x in out[target][1]], 1)
        s = jnp.concatenate([jnp.zeros((dims.layers, 1))]
                            + [x[:, None] for x in out[target][2]], 1)
        stacks[target] = (a, b, s)
    return stacks


def _forward(dims: Dims, seed: int, seqs: List[Seq], length: int,
             tenants: int, rank: int, lows, chunk: int, n_out: int = 0):
    """Final residual stream of every sequence, padded to ``length``, once
    per precision in ``lows``; with the scored positions and tokens, padded
    to ``n_out`` of them (at least the longest served)."""
    b = len(seqs)
    toks = np.zeros((b, length), np.int32)
    n_out = max([n_out] + [len(q.served) for q in seqs])
    pos = np.zeros((b, n_out), np.int32)
    served = np.zeros((b, n_out), np.int32)
    for i, q in enumerate(seqs):
        feed = np.concatenate([q.prompt, q.served[:-1]])
        if len(feed) > length:
            raise ValueError(f"sequence of {len(feed)} > reference length "
                             f"{length}")
        toks[i, :len(feed)] = feed
        p0 = len(q.prompt) - 1
        n = len(q.served)
        pos[i, :n] = np.arange(p0, p0 + n)
        pos[i, n:] = p0 + n - 1                 # repeats, dropped after
        served[i, :n] = q.served
        served[i, n:] = q.served[-1]
    aidx = jnp.asarray([0 if q.tenant is None else q.tenant + 1
                        for q in seqs], jnp.int32)
    key = jax.random.PRNGKey(seed)
    stack_key = jax.random.split(key, 8)[5]
    emb, head = _tables(key, dims=dims)
    lora = adapter_stacks(dims, seed, tenants, rank) if tenants else None
    chunk = chunk if length % chunk == 0 else length
    x0 = emb[0][jnp.asarray(toks)] * emb[1]
    xs = {low: x0 for low in lows}
    for i in range(dims.layers):
        lay = None if lora is None else {
            t: tuple(a[i] for a in st) for t, st in lora.items()}
        for low in xs:
            xs[low] = _block(xs[low], stack_key, jnp.int32(i), lay, aidx,
                             dims=dims, low=low, chunk=chunk)
    return xs, jnp.asarray(pos), jnp.asarray(served), head


def served_gaps(dims: Dims, seed: int, seqs: List[Seq], length: int, *,
                tenants: int = 0, rank: int = 8, control: bool = False,
                chunk: int = 128, n_out: int = 0):
    """The reference's gap at every served token of ``seqs`` (a list of
    arrays, one per sequence), and with ``control`` the gaps of the tokens
    the fp8 control puts first at the same positions. Sequences are padded
    to ``length`` tokens and ``n_out`` scored positions, so that one compiled
    program serves every run."""
    lows = (False, True) if control else (False,)
    xs, pos, served, head = _forward(dims, seed, seqs, length, tenants, rank,
                                     lows, chunk, n_out)
    gap, ctl = _gaps(xs[False], pos, served, head, xs.get(True), dims=dims,
                     low=control, tied=dims.tied)
    keep = [len(q.served) for q in seqs]
    gap = np.asarray(gap)
    out = {"gap": [gap[i, :n] for i, n in enumerate(keep)]}
    if control:
        ctl = np.asarray(ctl)
        out["control_gap"] = [ctl[i, :n] for i, n in enumerate(keep)]
    return out


def logits(dims: Dims, seed: int, seqs: List[Seq], length: int, *,
           tenants: int = 0, rank: int = 8, low: bool = False,
           chunk: int = 128) -> List[np.ndarray]:
    """The reference's logits at the positions that predict each served
    token: one (n_served, V) array per sequence. For small sizes (tests)."""
    xs, pos, _, (t, s) = _forward(dims, seed, seqs, length, tenants, rank,
                                  (low,), chunk)
    h = _rms(xs[low], dims.norm_eps)
    h = jnp.take_along_axis(h, pos[..., None], axis=1)
    h = to_fp8(h) if low else h
    out = np.asarray(jnp.matmul(h, t.T if dims.tied else t, precision=HI) * s)
    return [out[i, :len(q.served)] for i, q in enumerate(seqs)]

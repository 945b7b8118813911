"""Kernel-layer benchmark: Pallas kernels vs their pure-jnp oracles, placed
on the roofline.

TPU kernels are validated in interpret mode on CPU (correctness) and timed
against the XLA path (directional only on CPU — the structural win is the
dry-run memory term). Each timed case also gets a roofline placement via
`repro.serving.obs.classify`: analytic FLOPs + array-traffic bytes against
the `repro.obs.hardware.detect()` peaks yield achieved GFLOP/s, GB/s and an
achieved-vs-roofline efficiency (``pct_of_roof``) per kernel. Covers:
  * ternary_matmul — packed 2-bit decode-in-kernel GEMM (C1's runtime analogue)
  * flash_decode — context-tiled online-softmax decode (C3's in-lane kernel)
  * paged_flash_decode — the block-table-indexed serving twin
  * batched_lora — multi-tenant packed-ternary SGMV (adapter decode path)

Perf trajectory lands in ``BENCH_kernels.json`` at the repo root (stable
keys; wall-derived leaves are regression-gate-noisy by name, the analytic
FLOP/byte leaves still compare).
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ternary
from repro.kernels.batched_lora import ops as bl_ops
from repro.kernels.flash_decode import ops as fd_ops
from repro.kernels.flash_decode import ref as fd_ref
from repro.kernels.ternary_matmul import ops as tm_ops
from repro.kernels.ternary_matmul import ref as tm_ref
from repro.obs.hardware import detect
from repro.serving.obs import classify
from benchmarks.common import Report, time_fn, write_bench_json


def _roofline_case(r: Report, bench_out: dict, name: str, case: str,
                   flops: float, nbytes: float, wall_s: float, hw) -> None:
    """One timed kernel case → report rows + BENCH leaf dict."""
    roof = classify(flops, nbytes, wall_s, hw)
    bench_out.setdefault(name, {})[case] = {
        "flops": flops,
        "bytes": nbytes,
        "intensity": round(roof["intensity"], 4),
        "bound": roof["bound"],
        "wall_us": round(wall_s * 1e6, 1),
        "achieved_gflops": round(roof["achieved_gflops"], 3),
        "achieved_gbs": round(roof["achieved_gbs"], 3),
        "pct_of_roof": round(roof["pct_of_roof"], 5),
    }
    r.row(f"{name}/{case}/wall_us", round(wall_s * 1e6, 1), "XLA ref path")
    r.row(f"{name}/{case}/pct_of_roof", round(roof["pct_of_roof"], 5),
          f"{roof['bound']}-bound, {roof['achieved_gflops']:.2f} GFLOP/s "
          f"/ {roof['achieved_gbs']:.2f} GB/s achieved on {hw.name}")


def run(quick: bool = False) -> Report:
    r = Report("kernels")
    rng = np.random.default_rng(0)
    hw = detect()
    bench_out = {"hardware": hw.to_dict()}

    # --- ternary matmul -------------------------------------------------------
    shapes = [(256, 512, 256), (512, 1024, 512)] if quick else \
             [(256, 512, 256), (512, 1024, 512), (1024, 2048, 1024)]
    for m, k, n in shapes:
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
        t, s = ternary.quantize(w)
        packed = ternary.pack2(t)
        ref = tm_ref.ternary_matmul_ref(x, packed, s)
        out = tm_ops.ternary_matmul(x, packed, s, interpret=True)
        err = float(jnp.max(jnp.abs(out - ref)) / (jnp.max(jnp.abs(ref)) + 1e-9))
        r.row(f"ternary_matmul/{m}x{k}x{n}/allclose", round(err, 8),
              "pallas(interpret) vs jnp oracle")
        t_ref = time_fn(lambda: jax.block_until_ready(
            tm_ref.ternary_matmul_ref(x, packed, s)), iters=3)
        flops = 2.0 * m * k * n
        nbytes = float(x.nbytes + packed.nbytes + s.nbytes + m * n * 4)
        _roofline_case(r, bench_out, "ternary_matmul", f"{m}x{k}x{n}",
                       flops, nbytes, t_ref, hw)

    # --- flash decode ---------------------------------------------------------
    cases = [(2, 8, 2, 512, 64), (1, 8, 4, 1024, 128)]
    for b, hq, hkv, s_len, d in cases:
        g = hq // hkv
        q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
        k_ = jnp.asarray(rng.normal(size=(b, hkv, s_len, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, hkv, s_len, d)), jnp.float32)
        length = jnp.asarray(s_len - 7, jnp.int32)
        ref = fd_ref.flash_decode_ref(q.reshape(b, hkv, g, d), k_, v, length)
        out = fd_ops.decode_attention(q, k_, v, length, interpret=True)
        err = float(jnp.max(jnp.abs(out.reshape(b, hkv, g, d) - ref))
                    / (jnp.max(jnp.abs(ref)) + 1e-9))
        r.row(f"flash_decode/b{b}h{hq}s{s_len}d{d}/allclose", round(err, 8), "")
        t_ref = time_fn(lambda: jax.block_until_ready(
            fd_ref.flash_decode_ref(q.reshape(b, hkv, g, d), k_, v, length)),
            iters=3)
        flops = 4.0 * b * hq * s_len * d          # QK^T + PV matmuls
        nbytes = float(q.nbytes + k_.nbytes + v.nbytes + q.nbytes)
        _roofline_case(r, bench_out, "flash_decode", f"b{b}h{hq}s{s_len}d{d}",
                       flops, nbytes, t_ref, hw)

    # --- paged flash decode (serving twin: block-table-indexed pool) ----------
    pcases = [(2, 8, 2, 16, 16), (4, 8, 4, 16, 32)] if quick else \
             [(2, 8, 2, 16, 16), (4, 8, 4, 16, 32), (4, 8, 4, 32, 32)]
    for b, hq, hkv, page, n_p in pcases:
        d = 64
        g = hq // hkv
        n_pages = b * n_p + 1                      # +1 scratch page
        q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
        # one layer: the pool's leading layer axis has size 1
        k_pool = jnp.asarray(rng.normal(size=(1, n_pages, hkv, page, d)),
                             jnp.float32)
        v_pool = jnp.asarray(rng.normal(size=(1, n_pages, hkv, page, d)),
                             jnp.float32)
        tables = jnp.asarray(
            rng.permutation(b * n_p).reshape(b, n_p) + 1, jnp.int32)
        lengths = jnp.asarray(
            rng.integers(page, n_p * page + 1, size=b), jnp.int32)
        t_ref = time_fn(lambda: jax.block_until_ready(
            fd_ops.paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                                          layer=0, use_kernel=False)),
            iters=3)
        s_ctx = float(jnp.sum(lengths))            # live tokens attended
        flops = 4.0 * hq * d * s_ctx
        # traffic: q/out + the gathered pages (kernel DMAs exactly the
        # table-named pages, not the whole pool)
        nbytes = float(2 * q.nbytes
                       + 2 * b * n_p * page * hkv * d * 4)
        _roofline_case(r, bench_out, "paged_flash_decode",
                       f"b{b}h{hq}p{page}x{n_p}", flops, nbytes, t_ref, hw)

    # --- batched LoRA (multi-tenant SGMV over packed-ternary stacks) ----------
    lcases = [(4, 512, 8, 512, 4)] if quick else \
             [(4, 512, 8, 512, 4), (8, 1024, 16, 1024, 8)]
    for bsz, k_dim, rank, n_dim, n_adapters in lcases:
        x = jnp.asarray(rng.normal(size=(bsz, k_dim)), jnp.float32)
        a = jnp.asarray(rng.integers(0, 255, size=(n_adapters, k_dim // 4, rank)),
                        jnp.uint8)
        bc = jnp.asarray(rng.integers(0, 255, size=(n_adapters, rank // 4, n_dim)),
                         jnp.uint8)
        scales = jnp.ones((n_adapters,), jnp.float32)
        idx = jnp.asarray(rng.integers(0, n_adapters, size=bsz), jnp.int32)
        t_ref = time_fn(lambda: jax.block_until_ready(
            bl_ops.batched_lora(x, a, bc, scales, idx, use_kernel=False)),
            iters=3)
        flops = 2.0 * bsz * k_dim * rank + 2.0 * bsz * rank * n_dim
        nbytes = float(x.nbytes + a.nbytes + bc.nbytes + scales.nbytes
                       + idx.nbytes + bsz * n_dim * 4)
        _roofline_case(r, bench_out, "batched_lora",
                       f"b{bsz}k{k_dim}r{rank}n{n_dim}", flops, nbytes,
                       t_ref, hw)

    write_bench_json("kernels", bench_out)
    print("[bench_kernels]", json.dumps(bench_out))
    r.save()
    return r


if __name__ == "__main__":
    import sys
    run(quick="--quick" in sys.argv)

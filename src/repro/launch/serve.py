"""Serving driver: load (or init) a packed-ternary model and run a batched
request stream through the gateway (scheduler → engine → metrics).

CPU-scale usage (end-to-end example path):

    PYTHONPATH=src python -m repro.launch.serve \
        --arch bitnet-2b --preset tiny --requests 16 --slots 4 --max-new 16 \
        --kv paged --page 32 --prefix-cache

Chunked prefill (SLO isolation — long prompts stream in chunks while other
slots keep decoding):

    PYTHONPATH=src python -m repro.launch.serve \
        --arch bitnet-2b --preset tiny --requests 16 --slots 4 \
        --prefill batched --prefill-chunk 32 --prompt-len 200 --kv paged

Multi-tenant adapters (one ternary base, many QLoRA fine-tunes):

    PYTHONPATH=src python -m repro.launch.serve \
        --arch bitnet-2b --preset tiny --requests 16 --slots 4 \
        --adapters 4 --adapter-rank 8 --adapter-budget-kb 64 --adapter-rate 0.8

Prints one JSON blob: request-level latency stats plus the gateway metrics
registry (TTFT/TBT histograms, queue depth, pool occupancy, preemptions).

Cluster posture: the same engine runs with the model jit-sharded over the
production mesh (the decode_32k dry-run cells prove those graphs compile);
slots become the global batch and the KV cache shards over (data, model) —
batch over data, context over model, exactly Table I's distributed SRAM.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Optional

import jax
import numpy as np

from repro.ckpt import checkpoint as ckpt_mod
from repro.configs.base import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import reduce_config
from repro.models.transformer import Model
from repro.serving import (DenseKV, PagedKV, ReplicaRouter, RequestSpec,
                           SamplingParams, ServeEngine, replica_meshes,
                           shard_engine)
from repro.serving.gateway import Gateway


def build_engine(arch: str, preset: str, *, slots: int, max_len: int,
                 prefill: str, prefill_chunk: Optional[int] = None,
                 ckpt_dir: Optional[str] = None,
                 seed: int = 0, kv: str = "dense", page: int = 64,
                 n_pages: Optional[int] = None,
                 prefix_cache: bool = False, spec_k: int = 0,
                 spec_adaptive: bool = False,
                 n_adapters: int = 0, adapter_rank: int = 8,
                 adapter_budget_kb: Optional[float] = None,
                 host_cache_mb: float = 0.0,
                 disk_cache_dir: Optional[str] = None,
                 disk_cache_mb: float = 256.0, prefetch: bool = False,
                 tracer=None, profiler=None) -> ServeEngine:
    cfg = reduce_config(get_config(arch), preset)
    model = Model(cfg, mode="serve")
    # one compiled program: eager init would dispatch op by op and hold a
    # full f32 weight stack per projection before packing it
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    if ckpt_dir:
        step = ckpt_mod.latest_step(ckpt_dir)
        if step is not None:
            state, _ = ckpt_mod.restore(ckpt_dir, step, {"params": params})
            params = state["params"]
            print(f"[serve] restored packed weights from step {step}")
    adapters = None
    if n_adapters > 0:
        from repro.serving.adapters import (AdapterRegistry, AdapterServing,
                                            AdapterSpec,
                                            synthetic_adapter_stacks)
        spec = AdapterSpec(rank=adapter_rank, alpha=2.0 * adapter_rank,
                           targets=("q", "v"))
        registry = AdapterRegistry(spec)
        rng = np.random.default_rng(seed + 1)
        for i in range(n_adapters):
            registry.register(
                f"tenant-{i}",
                synthetic_adapter_stacks(rng, cfg, spec, cfg.num_layers))
        per_adapter = registry.get("tenant-0").nbytes
        budget = (int(adapter_budget_kb * 1024) if adapter_budget_kb
                  else per_adapter * max(2, n_adapters // 2))
        adapters = AdapterServing(model, registry, budget_bytes=budget,
                                  max_resident=max(2, min(n_adapters, slots * 2)))
        print(f"[serve] {n_adapters} tenants registered "
              f"({per_adapter}B each, SRAM budget {budget}B)")
    backend = (PagedKV(page=page, n_pages=n_pages) if kv == "paged"
               else DenseKV())
    tiered = None
    if host_cache_mb > 0 or disk_cache_dir:
        from repro.serving import TieredStore
        tiered = TieredStore(
            host_budget_bytes=int(host_cache_mb * (1 << 20)),
            disk_budget_bytes=int(disk_cache_mb * (1 << 20)),
            disk_dir=disk_cache_dir)
        print(f"[serve] tiered memory: host {host_cache_mb}MB"
              + (f", disk {disk_cache_mb}MB at {disk_cache_dir}"
                 if disk_cache_dir else "")
              + (", prefetch on" if prefetch else ""))
    return ServeEngine(model, params, max_slots=slots, max_len=max_len,
                       prefill=prefill, prefill_chunk=prefill_chunk,
                       seed=seed, kv=backend, spec_decode=spec_k > 0,
                       spec_adaptive=spec_adaptive,
                       prefix_cache=prefix_cache, adapters=adapters,
                       tiered=tiered, prefetch=prefetch,
                       tracer=tracer, profiler=profiler)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="bitnet-2b")
    ap.add_argument("--preset", default="tiny", choices=("tiny", "small", "full"))
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--prefill", default="token", choices=("token", "batched"))
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="split batched prefill into this many tokens per "
                         "tick (SLO isolation: decode slots keep emitting "
                         "during a long prompt's prefill; requires "
                         "--prefill batched)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft up to this many tokens "
                         "per tick by n-gram prompt lookup and verify them "
                         "in one multi-token step (0 = off; greedy/seeded "
                         "requests only, outputs token-identical either way)")
    ap.add_argument("--spec-adaptive", action="store_true",
                    help="adapt each slot's draft width to its live accept "
                         "rate (EWMA, clamped to --spec-k; requires --spec-k)")
    ap.add_argument("--async", dest="async_runtime", action="store_true",
                    help="drive the engine through the asynchronous "
                         "dispatch/backlog runtime (device kept >= 1 tick "
                         "ahead; outputs token-identical to the sync loop)")
    ap.add_argument("--async-depth", type=int, default=1,
                    help="device-ahead pipeline depth for --async")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve this many engine replicas behind the "
                         "prefix-cache-aware router (each replica gets its "
                         "own (data=1, model=--tp) submesh, KV pool and "
                         "dispatch thread; implies the async runtime)")
    ap.add_argument("--tp", type=int, default=1,
                    help="model-parallel lanes per replica (devices must "
                         "divide; 1 on a single-device host)")
    ap.add_argument("--aot-warmup", action="store_true",
                    help="AOT-compile the prefill length buckets "
                         "(lower().compile() per pow2 bucket) and pre-trace "
                         "decode/sample/verify before serving — steady-state "
                         "jit_compiles stays 0 (asserted by the CI smoke)")
    ap.add_argument("--http-port", type=int, default=None,
                    help="serve an HTTP/SSE front on this port instead of "
                         "the synthetic request stream (implies --async; "
                         "0 = ephemeral; POST /v1/shutdown stops the "
                         "process gracefully)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = disabled)")
    ap.add_argument("--kv", "--kv-backend", dest="kv", default="dense",
                    choices=("dense", "paged"))
    ap.add_argument("--page", type=int, default=64)
    ap.add_argument("--n-pages", type=int, default=None,
                    help="pool capacity (default: slots * max_len / page)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share identical prompt prefixes via the page trie "
                         "(requires --kv paged)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many identical system-prompt tokens "
                         "to every request (exercises the prefix cache)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request SLO deadline (EDF scheduling)")
    ap.add_argument("--adapters", type=int, default=0,
                    help="register this many synthetic QLoRA tenants and "
                         "serve them multi-tenant (0 = single personality)")
    ap.add_argument("--adapter-rank", type=int, default=8)
    ap.add_argument("--adapter-budget-kb", type=float, default=None,
                    help="adapter SRAM budget (default: half the tenants fit)")
    ap.add_argument("--adapter-rate", type=float, default=1.0,
                    help="fraction of requests that carry an adapter_id")
    ap.add_argument("--host-cache-mb", type=float, default=0.0,
                    help="host-RAM tier budget for the tiered memory "
                         "hierarchy: evicted adapter packs and prefix-cache "
                         "KV pages demote here instead of being dropped, "
                         "and re-admit bit-identical (0 = tiering off "
                         "unless --disk-cache-dir is set)")
    ap.add_argument("--disk-cache-dir", default=None,
                    help="directory for the disk tier (mmapped CRC-checked "
                         "files); entries cascade host → disk under "
                         "host-budget pressure")
    ap.add_argument("--disk-cache-mb", type=float, default=256.0,
                    help="disk tier budget (only with --disk-cache-dir)")
    ap.add_argument("--prefetch", action="store_true",
                    help="scheduler prefetch hook: walk the pending queue "
                         "each tick and warm upcoming adapters / spilled "
                         "prefixes up the hierarchy before their turn")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default=None,
                    help="capture a Chrome trace_event trace of the tick "
                         "loop: *.jsonl → strict JSONL, anything else → "
                         "{'traceEvents': [...]} JSON; both open at "
                         "ui.perfetto.dev")
    ap.add_argument("--trace-ring", type=int, default=None,
                    help="keep only the newest N trace events (bounded "
                         "memory on long runs; default unbounded)")
    ap.add_argument("--prom-out", default=None,
                    help="write the metrics registry in Prometheus text "
                         "exposition format to this path (atomic rewrite "
                         "every --prom-every ticks and once at exit)")
    ap.add_argument("--prom-every", type=int, default=50,
                    help="tick window between --prom-out rewrites")
    ap.add_argument("--profile-out", default=None,
                    help="write the merged performance-attribution report "
                         "(per-compiled-function roofline placement, "
                         "per-phase SLO breakdown, recompile offenders, "
                         "%%-of-tick host overhead) as JSON to this path; "
                         "dispatches run blocked while profiling")
    args = ap.parse_args(argv)
    enable_compile_cache()

    tracer = None
    if args.trace_out:
        from repro.serving.obs import Tracer
        tracer = Tracer(ring=args.trace_ring)
    profiler = None
    if args.profile_out:
        from repro.serving.obs import ProfileRegistry
        profiler = ProfileRegistry()
    n_rep = max(1, args.replicas)
    sharded = n_rep > 1 or args.tp > 1
    meshes = replica_meshes(n_rep, tp=args.tp) if sharded else [None]
    engines, warmups = [], []
    for mesh in meshes:
        e = build_engine(args.arch, args.preset, slots=args.slots,
                         max_len=args.max_len, prefill=args.prefill,
                         prefill_chunk=args.prefill_chunk,
                         ckpt_dir=args.ckpt_dir, seed=args.seed, kv=args.kv,
                         page=args.page, n_pages=args.n_pages,
                         prefix_cache=args.prefix_cache, spec_k=args.spec_k,
                         spec_adaptive=args.spec_adaptive,
                         n_adapters=args.adapters,
                         adapter_rank=args.adapter_rank,
                         adapter_budget_kb=args.adapter_budget_kb,
                         host_cache_mb=args.host_cache_mb,
                         disk_cache_dir=args.disk_cache_dir,
                         disk_cache_mb=args.disk_cache_mb,
                         prefetch=args.prefetch,
                         tracer=tracer if not engines else None,
                         profiler=profiler if not engines else None)
        if mesh is not None:
            shard_engine(e, mesh)
        if args.aot_warmup:
            info = e.warmup_aot(
                max_prompt_len=args.shared_prefix + args.prompt_len)
            warmups.append(info)
            print(f"[serve] replica {len(engines)}: AOT warmup — "
                  f"{info['aot_executables']} prefill executables, "
                  f"{info['jit_warmed']} jit traces in {info['wall_s']:.2f}s",
                  flush=True)
        engines.append(e)
    eng = engines[0]
    gws = [Gateway(e) for e in engines]
    gw = gws[0]
    if args.prom_out:
        gw.prom_out = args.prom_out
        gw.prom_every = args.prom_every

    def warmup_blob():
        return {
            "aot_executables": sum(w["aot_executables"] for w in warmups),
            "jit_warmed": sum(w["jit_warmed"] for w in warmups),
            "compiles": sum(w["compiles"] for w in warmups),
            "wall_s": round(sum(w["wall_s"] for w in warmups), 3),
        }

    if args.http_port is not None:
        # front-door mode: no synthetic stream — serve HTTP/SSE until a
        # client POSTs /v1/shutdown (the CI smoke's graceful-stop path)
        from repro.serving.runtime import AsyncServeRuntime, ServingHTTPFront
        rts = [AsyncServeRuntime(g, depth=args.async_depth) for g in gws]
        if n_rep > 1:
            runtime = ReplicaRouter(rts).start()
            metrics_blob = runtime.gw.metrics.to_dict
        else:
            runtime = rts[0].start()
            metrics_blob = gw.metrics_dict
        front = ServingHTTPFront(runtime, port=args.http_port).start()
        print(f"[serve] http/sse front on 127.0.0.1:{front.port} "
              f"({n_rep} replica(s), async depth {args.async_depth})",
              flush=True)
        try:
            front.serve_until_shutdown()
        finally:
            front.close()
            for rt in rts:
                rt.close(raise_on_poison=False)
        out = {"replicas": n_rep,
               "completed": sum(e.stats.completed for e in engines),
               "tokens_out": sum(e.stats.tokens_out for e in engines),
               "jit_compiles": sum(e.stats.jit_compiles for e in engines),
               "poisoned": runtime.poisoned,
               "tick_host_overhead_frac": round(
                   eng.stats.host_overhead_frac, 4),
               "energy": gw.energy.gauges(),
               "metrics": metrics_blob()}
        if args.aot_warmup:
            out["warmup"] = warmup_blob()
            out["warmup_compiles"] = sum(
                e.stats.warmup_compiles for e in engines)
        print("[serve]", json.dumps(out))
        return 1 if runtime.poisoned else 0

    rng = np.random.default_rng(args.seed)
    vocab = eng.cfg.vocab_size
    system = list(rng.integers(0, min(vocab, 1000), size=args.shared_prefix))
    workload = []
    for i in range(args.requests):
        plen = int(rng.integers(max(2, args.prompt_len // 2), args.prompt_len + 1))
        prompt = system + list(rng.integers(0, min(vocab, 1000), size=plen))
        adapter_id = None
        if args.adapters > 0 and rng.random() < args.adapter_rate:
            adapter_id = f"tenant-{i % args.adapters}"
        workload.append((
            prompt,
            RequestSpec(max_new_tokens=args.max_new,
                        priority=i % 2,            # mixed SLO classes
                        deadline_ms=args.deadline_ms,
                        adapter_id=adapter_id),
            SamplingParams(temperature=args.temperature, top_p=args.top_p,
                           spec_k=args.spec_k)))

    router = None
    if n_rep > 1:
        from repro.serving.runtime import AsyncServeRuntime
        t0 = time.time()
        with ReplicaRouter([AsyncServeRuntime(g, depth=args.async_depth)
                            for g in gws]) as router:
            tickets = [router.submit(p, spec=s, sampling=sp)
                       for p, s, sp in workload]
            router.drain()
            reqs = [t.req for t in tickets]
        wall = time.time() - t0
        stats = eng.stats
    elif args.async_runtime:
        from repro.serving.runtime import AsyncServeRuntime
        t0 = time.time()
        with AsyncServeRuntime(gw, depth=args.async_depth) as rt:
            tickets = [rt.submit(p, spec=s, sampling=sp)
                       for p, s, sp in workload]
            rt.drain()
            reqs = [t.req for t in tickets]
        wall = time.time() - t0
        stats = eng.stats
    else:
        reqs = [gw.submit(p, s, sp) for p, s, sp in workload]
        t0 = time.time()
        stats = gw.run_until_drained()
        wall = time.time() - t0

    done = [r for r in reqs if r.state == "done"]
    ttfts = [r.ttft_s for r in done] or [0.0]
    lats = [r.latency_s for r in done] or [0.0]
    out = {
        "requests": len(reqs),
        "completed": stats.completed,
        "tokens_out": stats.tokens_out,
        "wall_s": round(wall, 3),
        "throughput_tps": round(stats.tokens_out / wall, 1),
        "ttft_p50_ms": round(float(np.median(ttfts)) * 1e3, 1),
        "ttft_p99_ms": round(float(np.quantile(ttfts, 0.99)) * 1e3, 1),
        "latency_p50_ms": round(float(np.median(lats)) * 1e3, 1),
        "phase_breakdown_ms": stats.phase_breakdown_ms(),
        "tick_gap_ms_mean": round(stats.tick_gap_ms_mean, 4),
        "tick_host_overhead_frac": round(stats.host_overhead_frac, 4),
        "jit_compiles": stats.jit_compiles,
        "energy": gw.energy.gauges(),
        "metrics": gw.metrics_dict(),
    }
    if args.aot_warmup:
        out["warmup"] = warmup_blob()
        out["warmup_compiles"] = sum(e.stats.warmup_compiles
                                     for e in engines)
        out["aot_fallbacks"] = sum(e.stats.aot_fallbacks for e in engines)
    if router is not None:
        out["replicas"] = n_rep
        out["completed"] = sum(e.stats.completed for e in engines)
        out["tokens_out"] = sum(e.stats.tokens_out for e in engines)
        out["throughput_tps"] = round(out["tokens_out"] / wall, 1)
        out["jit_compiles"] = sum(e.stats.jit_compiles for e in engines)
        out["routing"] = router.gw.metrics.to_dict()["fleet"]["counters"]
    if args.spec_k:
        out["spec"] = {"drafted": stats.spec_drafted,
                       "accepted": stats.spec_accepted,
                       "accept_rate": round(stats.spec_accept_rate, 4),
                       "verify_ticks": stats.spec_ticks}
    if eng.adapters is not None:
        out["adapters"] = eng.adapters.stats()
    if eng.tiered is not None:
        out["tiered"] = dict(eng.tiered.stats(),
                             prefix_readmits=stats.prefix_readmits,
                             prefix_readmit_tokens=stats.prefix_readmit_tokens,
                             prefetch_hits=stats.prefetch_hits,
                             kv_spilled_pages=stats.kv_spilled_pages)
    if args.trace_out:
        eng.trace.dump(args.trace_out)
        print(f"[serve] trace → {args.trace_out} "
              f"({len(eng.trace.events)} events; open at ui.perfetto.dev)",
              file=sys.stderr)
    if args.prom_out:
        from repro.serving.obs.prom import write_prom
        write_prom(args.prom_out, gw.metrics.to_prom_text())
    if args.profile_out:
        from repro.serving.obs import attribution_report
        report = attribution_report(gw, profiler)
        with open(args.profile_out, "w") as f:
            json.dump(report, f, indent=2)
        n_fns = len(report.get("functions", ()))
        print(f"[serve] attribution → {args.profile_out} "
              f"({n_fns} compiled functions, host overhead "
              f"{report['host_overhead']['frac_of_tick']:.1%} of tick)",
              file=sys.stderr)
    print("[serve]", json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

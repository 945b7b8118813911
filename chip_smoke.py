"""Chip smoke test: serve full-width BitNet-2B on TPU through the normal engine.

Run from the repository root on a machine with a TPU:

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --replicas 4   # four one-chip replicas, router path only

One chip: builds the engine with ``repro.launch.serve.build_engine`` at the
published widths of ``configs/bitnet_2b.py`` (30 layers, d=2560, 20/5 heads
of 128, d_ff=6912, vocab 128,256; random weights from ``--seed``) with the
paged fp8 KV pool, batched prefill and synthetic QLoRA tenants, AOT-warms
it, and serves a handful of greedy requests through ``Gateway``. Most carry
an ``adapter_id``, some none. With every slot decoding and the longest
contexts on their second KV page, one decode step of the Pallas kernel
path (``Model(paged_attn="kernel")``) is compared on logits against the
XLA gather path (``paged_attn="gather"``), ``paged_flash_decode`` alone is
compared with its XLA reference on every layer of the live pool, and the
engine's compiled decode step is checked for both Pallas kernels and for
aliasing both KV pools (it consumes the pool it is handed).

``--replicas N``: N full-width replicas, each on its own chip, serve the
same requests behind ``ReplicaRouter`` as a one-replica engine on device 0;
each replica's decode logits are compared with the reference engine's.

Earlier lines report what was checked; compile and serve seconds there are
information only, never a measurement claim. The last line of standard
output is one JSON object naming the device. Any failure raises, so the
exit code is non-zero and that line is never printed. Single process: the
parent holds every chip it uses.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

#: Kernel vs gather decode logits, and each replica vs the reference engine,
#: must agree to this relative L2 error over the live rows' real-vocab
#: logits. The kernel and gather paths read the same fp8 pages and differ
#: only in the order of their f32 attention arithmetic, which then rounds
#: into the bf16 residual stream of each of the 30 layers.
LOGITS_REL_TOL = 2e-2

#: ``paged_flash_decode`` alone against its XLA reference on the live pool.
#: Both widen the same fp8 pages and work in f32; they differ in summation
#: order and in how the online softmax rescales across pages.
ATTN_REL_TOL = 1e-2

ARCH = "bitnet-2b"
SLOTS = 8
MAX_LEN = 256
PAGE = 64
MAX_NEW = 16
N_ADAPTERS = 3
ADAPTER_RANK = 8
#: Enough adapter budget for every tenant at full width (~0.5 MB each).
ADAPTER_BUDGET_KB = 4096.0


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require_tpu():
    """The devices, or SystemExit before any work when JAX finds no TPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{devices[0].platform!r} devices {devices}")
    return devices


def workload(vocab: int, n: int, lens, seed: int):
    """Greedy requests; every fourth carries no adapter. Prompt lengths are
    picked from ``lens`` so prefill stays in a few pow-2 buckets."""
    from repro.serving import RequestSpec, SamplingParams
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        prompt = rng.integers(0, vocab, size=lens[i % len(lens)]).tolist()
        adapter_id = None if i % 4 == 3 else f"tenant-{i % N_ADAPTERS}"
        out.append((prompt,
                    RequestSpec(max_new_tokens=MAX_NEW, adapter_id=adapter_id),
                    SamplingParams()))
    return out


def engine(preset: str, seed: int):
    from repro.launch.serve import build_engine
    return build_engine(ARCH, preset, slots=SLOTS, max_len=MAX_LEN,
                        prefill="batched", kv="paged", page=PAGE, seed=seed,
                        n_adapters=N_ADAPTERS, adapter_rank=ADAPTER_RANK,
                        adapter_budget_kb=ADAPTER_BUDGET_KB)


def serve_and_probe(gw, work, min_pos: int = 0):
    """Submit ``work`` through ``gw`` and tick until every slot that can be
    busy is decoding and one of them feeds position ``min_pos`` or later;
    return the requests and the arguments of the next decode step (live
    engine state, nothing committed, the pools copied: the engine's decode
    step consumes the pools it is handed), with the workload index of each
    live slot. The caller drains the gateway."""
    import jax.numpy as jnp
    eng = gw.engine
    reqs = [gw.submit(p, s, sp) for p, s, sp in work]
    want = min(eng.max_slots, len(reqs))
    for _ in range(4 * MAX_NEW):
        gw.step()
        active = [i for i in range(eng.max_slots)
                  if eng._is_decoding(i) and eng.slot_req[i].output]
        if len(active) == want and max(eng.pos[active]) >= min_pos:
            break
    else:
        raise RuntimeError(f"{len(active)} of {want} slots decoding, at "
                           f"positions {eng.pos[active].tolist()}; wanted "
                           f"one at {min_pos} or later")
    fed = np.zeros((eng.max_slots,), np.int32)
    for i in active:
        fed[i] = eng._fed_token(i)
    state = eng.kv.decode_state(active, eng.pos)
    state = dataclasses.replace(state, k_pool=jnp.copy(state.k_pool),
                                v_pool=jnp.copy(state.v_pool))
    args = (eng._effective_params(), state, jnp.asarray(fed),
            jnp.asarray(eng.pos.copy()), eng._adapter_idx())
    index = {id(r): j for j, r in enumerate(reqs)}
    return reqs, args, active, [index[id(eng.slot_req[i])] for i in active]


def live_logits(logits, active, vocab: int) -> np.ndarray:
    out = np.asarray(logits, np.float32)[active, :vocab]
    if not np.isfinite(out).all():
        raise AssertionError("non-finite decode logits")
    return out


def rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def check_done(reqs) -> int:
    bad = [(r.uid, r.state, len(r.output)) for r in reqs
           if r.state != "done" or len(r.output) != MAX_NEW]
    if bad:
        raise AssertionError(f"requests not served to completion: {bad}")
    return sum(len(r.output) for r in reqs)


def kernel_calls(compiled) -> dict:
    """``tpu_custom_call`` instructions in a compiled executable, counted by
    kernel name."""
    counts: dict = {}
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = line.split("=", 1)[0].replace("ROOT", "").strip()
            name = name.lstrip("%").rsplit(".", 1)[0]
            counts[name] = counts.get(name, 0) + 1
    return counts


def attention_check(state, active, num_heads: int, seed: int):
    """``paged_flash_decode`` against its XLA reference (``use_kernel=False``)
    on every layer of the live pool, with seeded random queries, over the
    slots whose context spans more than one page. Returns those slots, the
    worst layer's rel L2 err, and a control: the err over all layers when
    the kernel sees only each slot's first page."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_decode.ops import paged_decode_attention
    from repro.models.layers import KV_CACHE_SCALE
    rows = [i for i in active if int(state.lengths[i]) > PAGE]
    q = jax.random.normal(jax.random.PRNGKey(seed),
                          (SLOTS, num_heads, state.k_pool.shape[-1]),
                          jnp.bfloat16)

    def attend(layer, lengths, use_kernel):
        out = paged_decode_attention(
            q, state.k_pool, state.v_pool, state.tables, lengths,
            jnp.float32(KV_CACHE_SCALE), layer=jnp.int32(layer),
            use_kernel=use_kernel)
        return np.asarray(out, np.float32)[rows]

    first_page = jnp.minimum(state.lengths, PAGE)
    refs, errs, ctls = [], [], []
    for layer in range(state.k_pool.shape[0]):
        refs.append(attend(layer, state.lengths, False))
        errs.append(rel_err(attend(layer, state.lengths, True), refs[-1]))
        ctls.append(attend(layer, first_page, True))
    return rows, max(errs), rel_err(np.stack(ctls), np.stack(refs))


def one_chip(devices, preset: str = "full", seed: int = 0) -> None:
    import jax
    import jax.numpy as jnp
    from repro.serving.gateway import Gateway

    t0 = time.perf_counter()
    eng = engine(preset, seed)
    cfg = eng.cfg
    _log(f"model {ARCH} preset={preset}: {cfg.num_layers} layers, "
         f"d={cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}x"
         f"{cfg.head_dim}, d_ff={cfg.d_ff}, vocab {cfg.vocab_size}")
    warm = eng.warmup_aot(max_prompt_len=64)
    jax.block_until_ready(eng.params)
    _log(f"build + AOT warmup {time.perf_counter() - t0:.1f} s "
         f"({warm['compiles']} compiles; information only)")
    pool_bytes = eng.pool.k.nbytes + eng.pool.v.nbytes
    _log(f"compiled decode step, largest table view: aliases "
         f"{warm['decode_alias_bytes']} B of arguments to outputs (KV pools "
         f"{pool_bytes} B), temporaries {warm['decode_temp_bytes']} B")
    if not warm["decode_alias_bytes"] >= pool_bytes:
        raise AssertionError("the decode step does not update the KV pools "
                             "in place")

    gw = Gateway(eng)
    work = workload(cfg.vocab_size, 12, (24, 30, 40, 62), seed)
    t0 = time.perf_counter()
    # probe once the longest contexts hold ten tokens on their second KV
    # page, so the comparisons cover the kernel's walk across a block table
    reqs, args, active, _ = serve_and_probe(gw, work, min_pos=PAGE + 10)
    serve_s = time.perf_counter() - t0
    n_live_ad = sum(1 for i in active if eng.slot_adapter[i])
    steps = {m: jax.jit(dataclasses.replace(eng.model,
                                            paged_attn=m).decode_step)
             for m in ("kernel", "gather")}
    logits = {m: live_logits(step(*args)[0], active, cfg.vocab_size)
              for m, step in steps.items()}
    err = rel_err(logits["kernel"], logits["gather"])
    agree = float(np.mean(logits["kernel"].argmax(-1)
                          == logits["gather"].argmax(-1)))
    lengths = np.asarray(args[3])[active] + 1
    _log(f"decode logits, kernel vs gather path: {len(active)} live slots "
         f"({n_live_ad} with adapters), contexts {lengths.min()}-"
         f"{lengths.max()} tokens, rel L2 err {err:.3e} "
         f"(tol {LOGITS_REL_TOL:g}), max abs diff "
         f"{np.abs(logits['kernel'] - logits['gather']).max():.3e}, "
         f"argmax agreement {agree:.3f}")
    if not err <= LOGITS_REL_TOL:
        raise AssertionError(f"kernel vs gather logits rel err {err:.3e}")
    # control: the same executable attending to each slot's first token only
    # must fail the tolerance, or the comparison above could not see a fault
    params, state, *rest = args
    short = dataclasses.replace(state, lengths=jnp.minimum(state.lengths, 1))
    ctl = rel_err(live_logits(steps["kernel"](params, short, *rest)[0],
                              active, cfg.vocab_size), logits["gather"])
    _log(f"control, kernel path attending to the first token only: rel L2 "
         f"err {ctl:.3e} (must exceed tol)")
    if not ctl > LOGITS_REL_TOL:
        raise AssertionError("the logits tolerance cannot tell a broken "
                             "attention from a working one")
    rows, attn_err, attn_ctl = attention_check(state, active, cfg.num_heads,
                                               seed)
    _log(f"paged_flash_decode vs XLA reference, all {cfg.num_layers} layers "
         f"of the live pool, {len(rows)} slots past their first page: worst "
         f"rel L2 err {attn_err:.3e} (tol {ATTN_REL_TOL:g}); control, kernel "
         f"sees the first page only: rel L2 err {attn_ctl:.3e}")
    if not rows:
        raise AssertionError("no slot's context spans two pages")
    if not attn_err <= ATTN_REL_TOL < attn_ctl:
        raise AssertionError("paged_flash_decode disagrees with its "
                             "reference, or the check cannot see a page")
    decode = eng._decode._fn.lower(*args).compile()
    calls, mem = kernel_calls(decode), decode.memory_analysis()
    _log(f"tpu_custom_call ops in the engine's compiled decode step: "
         f"{sum(calls.values())} {calls}; its arguments "
         f"{mem.argument_size_in_bytes} B, temporaries "
         f"{mem.temp_size_in_bytes} B")
    for name in ("paged_flash_decode", "batched_lora_matmul"):
        if not calls.get(name):
            raise AssertionError(f"{name} missing from the decode executable")
    # the two compared programs differ in the attention they run
    compared = {m: kernel_calls(step.lower(*args).compile())
                for m, step in steps.items()}
    _log(f"tpu_custom_call ops in the compared steps: {compared}")
    if (not compared["kernel"].get("paged_flash_decode")
            or compared["gather"].get("paged_flash_decode")):
        raise AssertionError("only the kernel path may run paged_flash_decode")

    t0 = time.perf_counter()
    stats = gw.run_until_drained()
    serve_s += time.perf_counter() - t0
    tokens = check_done(reqs)
    n_ad = sum(1 for _, spec, _ in work if spec.adapter_id)
    _log(f"served {len(reqs)} requests ({n_ad} with adapters), {tokens} "
         f"tokens generated in {serve_s:.1f} s (information only)")
    _log(f"jit_compiles after warmup {stats.jit_compiles}, "
         f"aot_fallbacks {stats.aot_fallbacks}")
    if stats.jit_compiles or stats.aot_fallbacks:
        raise AssertionError("serving compiled or left an AOT executable "
                             "after warmup")
    if stats.tokens_out != tokens:
        raise AssertionError(f"engine counted {stats.tokens_out} tokens")
    _log(f"peak_bytes_in_use {devices[0].memory_stats()['peak_bytes_in_use']}")


def replicas(devices, n: int, preset: str = "full", seed: int = 0) -> None:
    import jax
    from repro.serving import AsyncServeRuntime, ReplicaRouter
    from repro.serving import replica_meshes, shard_engine
    from repro.serving.gateway import Gateway

    if len(devices) < n:
        raise AssertionError(f"{n} replicas need {n} devices: {devices}")
    t0 = time.perf_counter()
    ref = engine(preset, seed)
    fleet = [shard_engine(engine(preset, seed), m) for m in replica_meshes(n)]
    placed = [jax.tree.leaves(e.params)[0].devices() for e in fleet]
    if any(len(p) != 1 for p in placed) or len(set.union(*placed)) != n:
        raise AssertionError(f"replicas not on distinct devices: {placed}")
    placed = [p.pop() for p in placed]
    if jax.tree.leaves(ref.params)[0].devices() != {devices[0]}:
        raise AssertionError("reference engine is not on device 0")
    _log(f"{n} replicas on {[str(d) for d in placed]}, reference on "
         f"{devices[0]} ({time.perf_counter() - t0:.1f} s; information only)")

    vocab = ref.cfg.vocab_size
    work = workload(vocab, 4 * n, (24, 30), seed)
    t0 = time.perf_counter()
    ref_gw = Gateway(ref)
    ref_reqs = [ref_gw.submit(p, s, sp) for p, s, sp in work]
    ref_gw.run_until_drained()
    check_done(ref_reqs)
    with ReplicaRouter([AsyncServeRuntime(Gateway(e), depth=1)
                        for e in fleet]) as router:
        tickets = [router.submit(p, spec=s, sampling=sp, timeout=600)
                   for p, s, sp in work]
        router.drain(timeout=900)
        outs = [t.result() for t in tickets]
    tokens = check_done([t.req for t in tickets])
    routed = router.gw.metrics.to_dict()["fleet"]["counters"]
    per_replica = [routed.get(f"routed__r{r}", 0) for r in range(n)]
    same = sum(o == r.output for o, r in zip(outs, ref_reqs))
    _log(f"router served {len(work)} requests, {tokens} tokens, per replica "
         f"{per_replica}; {same}/{len(work)} token streams equal the "
         f"reference engine's ({time.perf_counter() - t0:.1f} s; "
         f"information only)")

    probe = work[:SLOTS]
    gw = Gateway(ref)
    _, args, active, rows = serve_and_probe(gw, probe)
    ref_logits = live_logits(ref._decode(*args)[0], active, vocab)
    gw.run_until_drained()
    for r, e in enumerate(fleet):
        gw = Gateway(e)
        _, args, active_r, rows_r = serve_and_probe(gw, probe)
        if sorted(rows_r) != sorted(rows):
            raise AssertionError(f"replica {r} decodes other probe requests")
        # slots may differ (adapter affinity orders admission): align by
        # request, since a row's logits depend only on its own state
        got = live_logits(e._decode(*args)[0], active_r, vocab)
        got = got[[rows_r.index(j) for j in rows]]
        err = rel_err(got, ref_logits)
        _log(f"replica {r} on {placed[r]}: decode logits vs reference "
             f"engine rel L2 err {err:.3e} (tol {LOGITS_REL_TOL:g}), max abs "
             f"diff {np.abs(got - ref_logits).max():.3e}")
        if not err <= LOGITS_REL_TOL:
            raise AssertionError(f"replica {r} logits rel err {err:.3e}")
        gw.run_until_drained()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--replicas", type=int, default=1,
                    help="N > 1: run only the N one-chip replicas behind the "
                         "router against a one-replica engine on device 0")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = require_tpu()
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    _log(f"jax.devices() = {devices}")
    cache = Path(enable_compile_cache())
    n_entries = len(list(cache.iterdir())) if cache.is_dir() else 0
    _log(f"compile cache: {cache} ({n_entries} entries at start)")
    if args.replicas > 1:
        replicas(devices, args.replicas, seed=args.seed)
    else:
        one_chip(devices, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The engine's decode step consumes the KV state it is handed.

``ServeEngine`` jits its decode step with the state donated: ``commit()``
replaces the state right after the dispatch, so the engine never reads a
donated buffer again, and the step's new pool is written into the buffer it
came in. These tests check that the compiled step really aliases both pools
(what ``warmup_aot`` reports), that every pool handed to a decode dispatch
is consumed, and that a served workload touching every path that holds
pool references — admissions, preemption, a speculative verify tick, the
async runtime's pipelined dispatch — runs through and serves the gather
path's tokens on the Pallas kernel path.
"""
import dataclasses
import time

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.launch.train import reduce_config
from repro.models.transformer import Model
from repro.serving import (AsyncServeRuntime, DenseKV, PagedKV, RequestSpec,
                           SamplingParams, ServeEngine)
from repro.serving.gateway import Gateway

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="module")
def model_params():
    cfg = reduce_config(get_config("bitnet-2b"), "tiny")
    model = Model(cfg, mode="serve")
    return model, model.init(jax.random.PRNGKey(0))


def _pool_bytes(eng):
    return eng.pool.k.nbytes + eng.pool.v.nbytes


@pytest.mark.parametrize("attn", ["gather", "kernel"])
def test_warmup_reports_both_pools_aliased(model_params, attn):
    model, params = model_params
    eng = ServeEngine(dataclasses.replace(model, paged_attn=attn), params,
                      max_slots=2, max_len=32, kv=PagedKV(page=8))
    warm = eng.warmup_aot()
    assert warm["decode_alias_bytes"] >= _pool_bytes(eng)
    assert warm["decode_temp_bytes"] is not None


def test_dense_cache_is_donated_too(model_params):
    model, params = model_params
    eng = ServeEngine(model, params, max_slots=2, max_len=32, kv=DenseKV())
    cache = eng.kv.cache
    warm = eng.warmup_aot()
    assert warm["decode_alias_bytes"] >= cache["k"].nbytes + cache["v"].nbytes
    eng.submit([3, 4, 5], RequestSpec(max_new_tokens=3))
    eng.run_until_drained()
    assert cache["k"].is_deleted() and cache["v"].is_deleted()


def _spy_pools(eng):
    """Record the pools handed to every decode dispatch of ``eng``."""
    seen = []
    decode = eng._decode

    def spy(params, state, *rest):
        seen.append((state.k_pool, state.v_pool))
        return decode(params, state, *rest)

    eng._decode = spy
    return seen


# cycling prompts: greedy decode repeats itself quickly, so n-gram drafts
# are on offer and verify ticks run
MOTIFS = ([11, 23, 37] * 4, [5, 9] * 5 + [5], list(range(40, 47)))


def _serve(model, params, attn, *, runtime):
    """Three low-priority requests, then, once they decode, a long
    high-priority one: the 8-page pool cannot hold all four as they grow,
    so a low-priority one is preempted."""
    eng = ServeEngine(dataclasses.replace(model, paged_attn=attn), params,
                      max_slots=4, max_len=64, kv=PagedKV(page=8, n_pages=8),
                      spec_decode=True)
    seen = _spy_pools(eng)
    low = [(p, RequestSpec(max_new_tokens=10, priority=2),
            SamplingParams(spec_k=4)) for p in MOTIFS]
    high = (list(range(60, 90)), RequestSpec(max_new_tokens=8, priority=0),
            SamplingParams(spec_k=4))
    if not runtime:
        reqs = [eng.submit(*w) for w in low]
        for _ in range(3):
            eng.tick()
        reqs.append(eng.submit(*high))
        eng.run_until_drained()
        assert all(r.state == "done" for r in reqs)
        return [r.output for r in reqs], eng, seen
    with AsyncServeRuntime(Gateway(eng), depth=1) as rt:
        tickets = [rt.submit(p, spec=s, sampling=sp) for p, s, sp in low]
        deadline = time.monotonic() + 120
        while not all(t.tokens() for t in tickets):
            assert time.monotonic() < deadline, "low-priority work stalled"
            time.sleep(0.01)
        tickets.append(rt.submit(high[0], spec=high[1], sampling=high[2]))
        rt.drain(timeout=300)
        return [t.result() for t in tickets], eng, seen


def test_served_workload_consumes_pools_and_matches_gather(model_params):
    model, params = model_params
    ref, _, _ = _serve(model, params, "gather", runtime=False)
    out, eng, seen = _serve(model, params, "kernel", runtime=True)
    assert out == ref
    st = eng.stats
    assert st.preemptions >= 1 and st.spec_ticks >= 1
    assert st.decode_steps == len(seen) > 0
    # every pool a decode step was handed is gone: the step consumed it,
    # and nothing read it afterwards (a read would have raised)
    assert all(k.is_deleted() and v.is_deleted() for k, v in seen)
    assert not eng.pool.k.is_deleted() and not eng.pool.v.is_deleted()
    assert eng.pool.pages_free == eng.pool.cfg.n_pages


def test_two_lane_engine_serves_one_device_tokens():
    """`serve.py --tp 2` on the kernel path: an engine placed by
    `shard_engine` on a (data=1, model=2) mesh warms up (the decode step's
    memory analysis lowers from the placements its arguments had), serves
    with no recompile, and gives the one-device engine's tokens, with the
    pool's pages split over the lanes (18 pages) and replicated (19)."""
    import os
    import pathlib
    import subprocess
    import sys
    import textwrap
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax
        from repro.configs.base import get_config
        from repro.launch.train import reduce_config
        from repro.models.transformer import Model
        from repro.serving import PagedKV, RequestSpec, ServeEngine
        from repro.serving.sharded import replica_meshes, shard_engine

        cfg = reduce_config(get_config("bitnet-2b"), "tiny")
        model = Model(cfg, mode="serve", paged_attn="kernel")
        params = model.init(jax.random.PRNGKey(0))

        def serve(n_pages, mesh=None):
            eng = ServeEngine(model, params, max_slots=3, max_len=48,
                              kv=PagedKV(page=8, n_pages=n_pages))
            if mesh is not None:
                shard_engine(eng, mesh)
            warm = eng.warmup_aot()
            # one device's pools, aliased in place
            assert warm["decode_alias_bytes"] >= sum(
                p.addressable_shards[0].data.nbytes
                for p in (eng.pool.k, eng.pool.v))
            reqs = [eng.submit(list(range(3 + i, 13 + 2 * i)),
                               RequestSpec(max_new_tokens=8))
                    for i in range(4)]
            for _ in range(60):
                eng.tick()
                if all(r.state == "done" for r in reqs):
                    break
            assert eng.stats.jit_compiles == 0, eng.stats.jit_compiles
            return eng, [list(r.output) for r in reqs]

        _, want = serve(17)
        mesh = replica_meshes(1, tp=2)[0]
        for n_pages, split in ((17, True), (18, False)):
            eng, got = serve(n_pages, mesh)
            spec = eng.pool.k.sharding.spec
            assert (spec[1:2] == ("model",)) == split, spec
            assert got == want, (n_pages, got, want)
        print("TWO-LANES-OK")
    """)
    res = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=600, env={**os.environ, "PYTHONPATH": "src"},
        cwd=str(pathlib.Path(__file__).resolve().parents[1]))
    assert res.returncode == 0, (res.stdout[-2000:], res.stderr[-3000:])
    assert "TWO-LANES-OK" in res.stdout

"""Multi-tenant QLoRA adapter serving: registry/cache units, batched
ternary-LoRA kernel vs reference, freeze→serve round-trip, scheduler
adapter-affinity invariants, SRAM-budget churn, and the acceptance bar —
a batch mixing ≥3 distinct adapters (plus None slots) produces per-slot
greedy outputs token-identical to running each request alone, in both
kv='dense' and kv='paged'."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core import qlora, ternary
from repro.kernels.batched_lora.batched_lora import batched_lora_matmul
from repro.kernels.batched_lora.ref import batched_lora_ref
from repro.launch.train import reduce_config
from repro.models.transformer import Model
from repro.serving import PagedKV, RequestSpec, ServeEngine
from repro.serving.adapters import (AdapterCache, AdapterRegistry,
                                    AdapterServing, AdapterSpec,
                                    synthetic_adapter_stacks, target_dims)
from repro.serving.engine import Request
from repro.serving.gateway import Gateway, Scheduler

jax.config.update("jax_enable_x64", False)

SPEC = AdapterSpec(rank=8, alpha=16.0, targets=("q", "v"))


def _kv(name):
    """Map a parametrize id to a fresh KV backend instance."""
    return PagedKV(page=8) if name == "paged" else None


@pytest.fixture(scope="module")
def model_params():
    cfg = reduce_config(get_config("bitnet-2b"), "tiny")
    model = Model(cfg, mode="serve")
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def registry(model_params):
    model, _ = model_params
    reg = AdapterRegistry(SPEC)
    rng = np.random.default_rng(7)
    for i in range(4):
        reg.register(f"tenant-{i}",
                     synthetic_adapter_stacks(rng, model.cfg, SPEC,
                                              model.cfg.num_layers, scale=0.05))
    return reg


def make_serving(model, registry, *, budget_adapters=4, max_resident=4):
    nbytes = registry.get("tenant-0").nbytes
    return AdapterServing(model, registry, budget_bytes=nbytes * budget_adapters,
                          max_resident=max_resident)


# ---------------------------------------------------------------------------
# Kernel vs reference (interpreter-mode, per the repo's Pallas test idiom)
# ---------------------------------------------------------------------------


def _stacks(n_adapters, k, r, n, seed=0):
    g = np.random.default_rng(seed)
    a_codes = np.zeros((n_adapters, k // 4, r), np.uint8)
    b_codes = np.zeros((n_adapters, r // 4, n), np.uint8)
    scales = np.zeros((n_adapters,), np.float32)
    for i in range(1, n_adapters):              # slot 0 stays the null adapter
        frozen = qlora.freeze_adapter({
            "a": jnp.asarray(g.normal(size=(k, r)), jnp.float32),
            "b": jnp.asarray(g.normal(size=(r, n)), jnp.float32)})
        a_codes[i] = np.asarray(frozen["a"].packed)
        b_codes[i] = np.asarray(frozen["b"].packed)
        scales[i] = float(frozen["a"].scale) * float(frozen["b"].scale) * 2.0
    return jnp.asarray(a_codes), jnp.asarray(b_codes), jnp.asarray(scales)


def _acts(seed, shape):
    """Small-integer activations: with ternary A/B every partial sum is an
    integer below 2**24, exact in f32, so any summation order (kernel slot
    sums, XLA dot blocking per shape and host) gives the same bits and a
    mismatch can only be a decode, indexing or scaling fault."""
    g = np.random.default_rng(seed)
    return jnp.asarray(g.integers(-8, 9, size=shape), jnp.float32)


class TestBatchedLoraKernel:
    @pytest.mark.parametrize("k,r,n", [(64, 8, 128), (320, 16, 256),
                                       (128, 4, 384), (2560, 8, 640),
                                       (2560, 16, 2560)])
    def test_kernel_matches_ref(self, k, r, n):
        a, b, s = _stacks(5, k, r, n, seed=k + n)
        x = _acts(1, (6, k))
        idx = jnp.asarray([0, 1, 2, 3, 4, 2], jnp.int32)
        got = batched_lora_matmul(x, a, b, s, idx, interpret=True)
        want = batched_lora_ref(x, a, b, s, idx)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_null_adapter_row_is_exactly_zero(self):
        a, b, s = _stacks(3, 64, 8, 128, seed=9)
        x = _acts(2, (3, 64))
        got = np.asarray(batched_lora_matmul(x, a, b, s,
                                             jnp.asarray([1, 0, 2], jnp.int32),
                                             interpret=True))
        assert np.all(got[1] == 0.0)
        assert np.any(got[0] != 0.0) and np.any(got[2] != 0.0)

    def test_segmented_rows_are_independent(self):
        """Row b's output depends only on adapter idx[b] — the SGMV contract
        that makes mixed-tenant batches safe."""
        a, b, s = _stacks(4, 64, 8, 128, seed=11)
        x = _acts(3, (4, 64))
        mixed = np.asarray(batched_lora_ref(
            x, a, b, s, jnp.asarray([1, 2, 3, 1], jnp.int32)))
        for row, ad in enumerate([1, 2, 3, 1]):
            solo = np.asarray(batched_lora_ref(
                x[row:row + 1], a, b, s, jnp.asarray([ad], jnp.int32)))
            np.testing.assert_array_equal(mixed[row], solo[0])

    def test_ref_3d_prefill_shape(self):
        a, b, s = _stacks(3, 64, 8, 128, seed=13)
        x = _acts(4, (2, 5, 64))
        got = batched_lora_ref(x, a, b, s, jnp.asarray([1, 2], jnp.int32))
        assert got.shape == (2, 5, 128)
        flat = batched_lora_ref(x[0], a, b, s, jnp.asarray([1] * 5, jnp.int32))
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(flat),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Registry: versioning, freeze round-trip, byte accounting
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_versioning(self, model_params):
        model, _ = model_params
        reg = AdapterRegistry(SPEC)
        rng = np.random.default_rng(0)
        v1 = reg.register("t", synthetic_adapter_stacks(rng, model.cfg, SPEC,
                                                        model.cfg.num_layers))
        v2 = reg.register("t", synthetic_adapter_stacks(rng, model.cfg, SPEC,
                                                        model.cfg.num_layers))
        assert (v1.version, v2.version) == (1, 2)
        assert reg.get("t").version == 2            # latest by default
        assert reg.get("t", version=1) is v1        # rollback addressable
        with pytest.raises(KeyError):
            reg.get("unknown")
        with pytest.raises(KeyError):
            reg.get("t", version=3)

    def test_adapter_bytes_matches_packed_sizes(self, registry, model_params):
        """`adapter_bytes` accounting == actual packed codes + f32 scales."""
        model, _ = model_params
        entry = registry.get("tenant-0")
        actual = 0
        for target, pk in entry.packs.items():
            actual += (pk["a_codes"].nbytes + pk["a_scale"].nbytes
                       + pk["b_codes"].nbytes + pk["b_scale"].nbytes)
        formula = sum(
            model.cfg.num_layers
            * qlora.adapter_bytes(*target_dims(model.cfg, t), SPEC.lora_spec)
            for t in SPEC.targets)
        assert entry.nbytes == formula == actual

    def test_freeze_roundtrip_matches_fake_quant_eval(self):
        """Frozen ternary pack → serve path matches the STE fake-quant path
        at eval: same ternary codes, scales applied in a different
        association order only."""
        rng = np.random.default_rng(5)
        k, r, n = 64, 8, 128
        a = jnp.asarray(rng.normal(size=(k, r)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(r, n)), jnp.float32) * 0.1
        spec = qlora.LoRASpec(rank=r, alpha=16.0, ternary=True)
        x = jnp.asarray(rng.normal(size=(3, k)), jnp.float32)
        # eval-mode two-path reference (quantize → dequantize → matmul)
        want = qlora.adapter_path(x, {"a": a, "b": b}, spec, train=False)
        # serve path: freeze to packed codes, combined scale, gathered matmul
        frozen = qlora.freeze_adapter({"a": a, "b": b})
        a_codes = frozen["a"].packed[None]
        b_codes = frozen["b"].packed[None]
        s = (frozen["a"].scale * frozen["b"].scale * spec.scaling)[None]
        got = batched_lora_ref(x[None], a_codes, b_codes, s,
                               jnp.asarray([0], jnp.int32))[0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    def test_rejects_bad_shapes(self, model_params):
        model, _ = model_params
        reg = AdapterRegistry(SPEC)
        rng = np.random.default_rng(0)
        stacks = synthetic_adapter_stacks(rng, model.cfg, SPEC,
                                          model.cfg.num_layers)
        bad = {t: dict(ab) for t, ab in stacks.items()}
        bad["q"] = {"a": bad["q"]["a"][..., :4], "b": bad["q"]["b"]}
        with pytest.raises(ValueError):
            reg.register("bad", bad)
        with pytest.raises(ValueError):
            reg.register("partial", {"q": stacks["q"]})
        with pytest.raises(ValueError):
            AdapterRegistry(AdapterSpec(rank=6))    # not packable


# ---------------------------------------------------------------------------
# SRAM-budget cache: LRU churn, pinning, byte budget
# ---------------------------------------------------------------------------


class TestAdapterCache:
    def test_lru_eviction_under_byte_budget(self):
        c = AdapterCache(budget_bytes=250, max_entries=8)
        for name in ("a", "b"):
            c.admit(name, 100)
        c.lookup("a")                       # a is now more recent than b
        _, evicted = c.admit("c", 100)      # must evict LRU = b
        assert evicted == ["b"]
        assert c.is_resident("a") and c.is_resident("c") and not c.is_resident("b")
        assert c.bytes_used <= c.budget_bytes
        assert c.evictions == 1

    def test_pinned_never_evicted(self):
        c = AdapterCache(budget_bytes=250, max_entries=8)
        c.admit("a", 100); c.pin("a")
        c.admit("b", 100); c.pin("b")
        assert not c.can_admit("c", 100)    # everything pinned: no room
        with pytest.raises(MemoryError):
            c.admit("c", 100)
        c.unpin("b")
        assert c.can_admit("c", 100)
        _, evicted = c.admit("c", 100)
        assert evicted == ["b"] and c.is_resident("a")

    def test_slot_exhaustion_evicts(self):
        c = AdapterCache(budget_bytes=10_000, max_entries=2)
        c.admit("a", 10); c.admit("b", 10)
        slot_a = c.slot_of("a")
        c.lookup("b")                       # a becomes LRU
        slot_c, evicted = c.admit("c", 10)
        assert evicted == ["a"] and slot_c == slot_a    # slot recycled
        assert sorted(c.resident_ids()) == ["b", "c"]

    def test_oversized_adapter_never_admissible(self):
        c = AdapterCache(budget_bytes=50, max_entries=4)
        assert not c.can_admit("huge", 51)

    def test_stats_shape(self):
        c = AdapterCache(budget_bytes=100, max_entries=2)
        c.admit("a", 10)
        c.lookup("a"); c.lookup("zz")
        st = c.stats()
        assert st["resident"] == 1 and st["hits"] == 1 and st["misses"] == 1
        assert st["hit_rate"] == 0.5 and st["budget_bytes"] == 100


# ---------------------------------------------------------------------------
# Scheduler adapter-affinity: batching help, never a priority/EDF violation
# ---------------------------------------------------------------------------


def _req(uid, prompt_len=4, deadline_s=None, **spec_kw):
    return Request(uid, list(range(prompt_len)), spec=RequestSpec(**spec_kw),
                   deadline_s=deadline_s, t_submit=time.time())


class TestAffinityScheduling:
    def test_warm_preferred_within_class(self):
        s = Scheduler()
        s.push(_req(1, adapter_id="cold"))
        s.push(_req(2, adapter_id="warm"))
        got = s.pop_next(prefer=lambda r: r.adapter_id == "warm")
        assert got.uid == 2                   # later arrival, same class: ok

    def test_priority_never_violated_by_affinity(self):
        """A higher-priority cold-adapter request is never starved by warm
        lower-priority traffic."""
        s = Scheduler()
        s.push(_req(1, priority=0, adapter_id="cold"))
        s.push(_req(2, priority=1, adapter_id="warm"))
        s.push(_req(3, priority=1, adapter_id="warm"))
        got = s.pop_next(prefer=lambda r: r.adapter_id == "warm")
        assert got.uid == 1

    def test_edf_never_violated_by_affinity(self):
        s = Scheduler()
        now = time.time()
        s.push(_req(1, priority=1, deadline_s=now + 1.0, adapter_id="cold"))
        s.push(_req(2, priority=1, deadline_s=now + 9.0, adapter_id="warm"))
        got = s.pop_next(prefer=lambda r: r.adapter_id == "warm")
        assert got.uid == 1                   # earlier deadline wins

    def test_affinity_respects_admission(self):
        s = Scheduler()
        s.push(_req(1, adapter_id="warm"))
        s.push(_req(2, adapter_id="cold"))
        got = s.pop_next(can_admit=lambda r: r.adapter_id != "warm",
                         prefer=lambda r: r.adapter_id == "warm")
        assert got.uid == 2

    def test_engine_affinity_no_priority_starvation(self, model_params,
                                                    registry):
        """End-to-end: with one free slot, a high-priority cold-adapter
        request is admitted ahead of queued warm-adapter traffic."""
        model, params = model_params
        ad = make_serving(model, registry, budget_adapters=1, max_resident=1)
        eng = ServeEngine(model, params, max_slots=1, max_len=64,
                          adapters=ad)
        warm_up = eng.submit([1, 2, 3],
                             RequestSpec(max_new_tokens=2,
                                         adapter_id="tenant-0"))
        eng.run_until_drained()
        assert warm_up.state == "done" and ad.is_resident("tenant-0")
        hi_cold = eng.submit([4, 5],
                             RequestSpec(max_new_tokens=2, priority=0,
                                         adapter_id="tenant-1"))
        lo_warm = eng.submit([6, 7],
                             RequestSpec(max_new_tokens=2, priority=1,
                                         adapter_id="tenant-0"))
        eng.tick()
        assert hi_cold.state == "running"
        assert lo_warm.state == "queued"
        eng.run_until_drained()
        assert hi_cold.state == "done" and lo_warm.state == "done"


# ---------------------------------------------------------------------------
# Engine end-to-end: mixed-tenant batches, budget churn, pin safety
# ---------------------------------------------------------------------------


class TestMultiTenantServing:
    def _solo(self, model, params, registry, kv, prompt, adapter_id):
        ad = make_serving(model, registry)
        eng = ServeEngine(model, params, max_slots=1, max_len=64,
                          kv=_kv(kv), adapters=ad)
        r = eng.submit(prompt, RequestSpec(max_new_tokens=6,
                                           adapter_id=adapter_id))
        eng.run_until_drained()
        assert r.state == "done"
        return r.output

    @pytest.mark.parametrize("kv", ["dense", "paged"])
    def test_mixed_batch_token_identical_to_solo(self, model_params, registry,
                                                 kv):
        """Acceptance: ≥3 distinct adapter_ids + None slots, per-slot greedy
        outputs == unbatched per-request reference, dense and paged."""
        model, params = model_params
        rng = np.random.default_rng(3)
        prompts = [list(rng.integers(0, 100, size=int(rng.integers(4, 12))))
                   for _ in range(5)]
        tenants = [None, "tenant-0", "tenant-1", "tenant-2", None]
        ad = make_serving(model, registry)
        eng = ServeEngine(model, params, max_slots=4, max_len=64, kv=_kv(kv),
                          adapters=ad)
        reqs = [eng.submit(p, RequestSpec(max_new_tokens=6, adapter_id=t))
                for p, t in zip(prompts, tenants)]
        eng.run_until_drained()
        assert all(r.state == "done" for r in reqs)
        for r, p, t in zip(reqs, prompts, tenants):
            assert r.output == self._solo(model, params, registry, kv, p, t), \
                f"slot with adapter {t} diverged from solo reference"

    @pytest.mark.parametrize("kv", ["dense", "paged"])
    def test_none_slots_identical_to_plain_engine(self, model_params, registry,
                                                  kv):
        """adapter_id=None slots must stay token-identical to an engine with
        no adapter subsystem at all."""
        model, params = model_params
        prompt = list(range(20, 29))
        plain = ServeEngine(model, params, max_slots=2, max_len=64,
                            kv=_kv(kv))
        r0 = plain.submit(prompt, RequestSpec(max_new_tokens=6))
        plain.run_until_drained()

        ad = make_serving(model, registry)
        eng = ServeEngine(model, params, max_slots=2, max_len=64, kv=_kv(kv),
                          adapters=ad)
        r1 = eng.submit(prompt, RequestSpec(max_new_tokens=6))    # None slot
        r2 = eng.submit(list(range(5)),
                        RequestSpec(max_new_tokens=6,
                                    adapter_id="tenant-1"))       # neighbour
        eng.run_until_drained()
        assert r1.output == r0.output
        assert r2.state == "done"

    def test_adapter_changes_outputs(self, model_params, registry):
        model, params = model_params
        prompt = list(range(30, 40))
        none_out = self._solo(model, params, registry, "dense", prompt, None)
        tenant_out = self._solo(model, params, registry, "dense", prompt,
                                "tenant-0")
        assert none_out != tenant_out

    def test_budget_churn_and_pinning(self, model_params, registry):
        """Cache respects its byte budget under tenant churn; an adapter with
        an in-flight request is never evicted."""
        model, params = model_params
        ad = make_serving(model, registry, budget_adapters=2, max_resident=2)
        eng = ServeEngine(model, params, max_slots=2, max_len=64, adapters=ad)
        reqs = [eng.submit(list(range(4)),
                           RequestSpec(max_new_tokens=3,
                                       adapter_id=f"tenant-{i}"))
                for i in range(4)]
        budget = ad.cache.budget_bytes
        while any(r.state in ("queued", "running") for r in reqs):
            eng.tick()
            assert ad.cache.bytes_used <= budget
            for slot, r in enumerate(eng.slot_req):
                if r is not None and r.adapter_id is not None:
                    # in-flight ⇒ resident and pinned, idx mapped
                    assert ad.is_resident(r.adapter_id)
                    assert ad.pinned(r.adapter_id)
                    assert eng.slot_adapter[slot] > 0
        assert all(r.state == "done" for r in reqs)
        assert ad.cache.evictions >= 1              # 4 tenants through 2 slots
        assert all(not ad.cache.pinned(i) for i in ad.cache.resident_ids())

    def test_pinned_budget_exhaustion_queues_not_crashes(self, model_params,
                                                         registry):
        """When every budget byte is pinned by running requests, a third
        tenant waits in the queue (admission control), then completes."""
        model, params = model_params
        ad = make_serving(model, registry, budget_adapters=2, max_resident=2)
        eng = ServeEngine(model, params, max_slots=3, max_len=64, adapters=ad)
        a = eng.submit(list(range(6)),
                       RequestSpec(max_new_tokens=8, adapter_id="tenant-0"))
        b = eng.submit(list(range(6)),
                       RequestSpec(max_new_tokens=8, adapter_id="tenant-1"))
        c = eng.submit(list(range(6)),
                       RequestSpec(max_new_tokens=8, adapter_id="tenant-2"))
        eng.tick()
        assert a.state == "running" and b.state == "running"
        assert c.state == "queued"                  # slot free, budget pinned
        eng.run_until_drained()
        assert c.state == "done"

    def test_unknown_or_oversized_adapter_rejected(self, model_params,
                                                   registry):
        model, params = model_params
        ad = make_serving(model, registry)
        eng = ServeEngine(model, params, max_slots=1, max_len=64, adapters=ad)
        assert eng.submit(
            [1, 2], RequestSpec(adapter_id="nope")).state == "rejected"
        no_ad = ServeEngine(model, params, max_slots=1, max_len=64)
        assert no_ad.submit(
            [1, 2], RequestSpec(adapter_id="tenant-0")).state == "rejected"

    def test_preemption_unpins_and_resumes_with_adapter(self, model_params,
                                                        registry):
        """A preempted tenant request unpins its adapter and, once re-
        admitted, reproduces the unpreempted output."""
        model, params = model_params
        solo = self._solo(model, params, registry, "paged",
                          list(range(30, 49)), "tenant-1")
        ad = make_serving(model, registry)
        eng = ServeEngine(model, params, max_slots=2, max_len=64,
                          kv=PagedKV(page=8, n_pages=6), adapters=ad)
        eng.submit(list(range(1, 20)),
                   RequestSpec(max_new_tokens=10, priority=0))
        lo = eng.submit(list(range(30, 49)),
                        RequestSpec(max_new_tokens=10, priority=2,
                                    adapter_id="tenant-1"))
        eng.run_until_drained()
        assert lo.n_preempts >= 1
        assert lo.output[:6] == solo                # same greedy trajectory
        assert not ad.pinned("tenant-1")


# ---------------------------------------------------------------------------
# Batched prefill after a prefix-cache hit (position-offset fix)
# ---------------------------------------------------------------------------


class TestPrefixHitBatchedPrefill:
    def test_no_token_fallback_and_identical_outputs(self, model_params):
        """Regression (ROADMAP item): batched prefill used to fall back to
        token mode after a prefix hit. Now it resumes mid-sequence (position
        offset + attention over cached prefix pages): one prefill tick, same
        tokens as the token-mode path."""
        model, params = model_params
        shared = list(range(10, 26))               # 2 full pages of 8
        tail = [3, 4, 5, 6, 7]
        outs = {}
        for mode in ("token", "batched"):
            eng = ServeEngine(model, params, max_slots=2, max_len=64,
                              kv=PagedKV(page=8), prefix_cache=True,
                              prefill=mode)
            warm = eng.submit(shared + tail, RequestSpec(max_new_tokens=5))
            eng.run_until_drained()                # commits the shared pages
            hit = eng.submit(shared + tail, RequestSpec(max_new_tokens=5))
            eng.run_until_drained()
            assert hit.prefix_hit_tokens == 16
            outs[mode] = (warm.output, hit.output)
            if mode == "batched":
                # the whole remainder ran through one batched prefill call
                assert hit.prefill_ticks == 1
        assert outs["token"] == outs["batched"]

    def test_offset_prefill_positions_match_dense_reference(self, model_params):
        """Model-level check: prefill(pos_offset, prefix_kv) fills the cache
        identically (within fp8 rounding) to one full prefill from zero."""
        model, params = model_params
        toks = np.asarray([list(range(40, 72))], np.int32)
        split = 16
        _, full = model.prefill(params, {"tokens": jnp.asarray(toks)}, 64)
        # first half from zero, second half resumed with the cached prefix
        _, head = model.prefill(params,
                                {"tokens": jnp.asarray(toks[:, :split])}, 64)
        prefix = {"k": head["k"][:, :, :, :split], "v": head["v"][:, :, :, :split]}
        logits2, resumed = model.prefill(
            params, {"tokens": jnp.asarray(toks[:, split:])}, 64,
            pos_offset=split, prefix_kv=prefix)
        got = np.asarray(resumed["k"].astype(jnp.float32))[:, :, :, split:32]
        want = np.asarray(full["k"].astype(jnp.float32))[:, :, :, split:32]
        np.testing.assert_allclose(got, want, rtol=0.2, atol=0.1)  # fp8 cache
        logits1, _ = model.prefill(params, {"tokens": jnp.asarray(toks)}, 64)
        assert int(jnp.argmax(logits1)) == int(jnp.argmax(logits2))


# ---------------------------------------------------------------------------
# Gateway surface: metrics JSON
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestMultiTenantBenchSmoke:
    def test_bench_multitenant_quick(self, tmp_path):
        """Bench-shaped: drives benchmarks/bench_multitenant end-to-end and
        checks the emitted artifact."""
        import json

        from benchmarks.bench_multitenant import run
        from benchmarks.common import ARTIFACTS
        run(quick=True)
        out = json.loads((ARTIFACTS / "BENCH_multitenant.json").read_text())
        assert set(out) == {"baseline", "single", "multi", "observability",
                            "tiered"}
        assert out["multi"]["completed"] == 8
        tiered = out["tiered"]
        assert tiered["prefix_readmits"] > 0
        assert tiered["kv_spilled_pages"] > 0
        assert tiered["readmit_speedup"] > 0.0
        obs = out["observability"]
        assert obs["phase_breakdown_ms"], obs
        assert obs["energy_per_token_j"] >= 0.0
        assert 0.0 <= obs["gated_bank_fraction"] <= 1.0
        assert 0.0 <= out["multi"]["adapter_hit_rate"] <= 1.0
        assert out["multi"]["adapter_bytes_used"] \
            <= out["multi"]["adapter_budget_bytes"]


class TestGatewayAdapterMetrics:
    def test_metrics_json_reports_adapter_cache(self, model_params, registry):
        model, params = model_params
        ad = make_serving(model, registry, budget_adapters=2, max_resident=2)
        gw = Gateway(ServeEngine(model, params, max_slots=2, max_len=64,
                                 adapters=ad))
        for i in range(3):
            gw.submit(list(range(4)),
                      RequestSpec(max_new_tokens=3, adapter_id=f"tenant-{i}"))
        gw.submit(list(range(4)), RequestSpec(max_new_tokens=3))
        gw.run_until_drained()
        m = gw.metrics_dict()
        g = m["gauges"]
        assert g["adapter_cache_resident"] <= 2
        assert g["adapter_cache_bytes_used"] <= g["adapter_cache_budget_bytes"]
        assert g["adapter_cache_evictions"] >= 1
        assert 0.0 <= g["adapter_cache_hit_rate"] <= 1.0
        assert m["counters"]["adapter_requests_total"] == 3
        assert m["counters"]["adapter_requests__tenant-0"] == 1

"""Performance-attribution tests: roofline classification, the profiler's
cost/memory capture and structural-vs-XLA cross-check, SLO latency
attribution golden cases, and the engine/gateway integration (profiled
serving run → validated attribution report + attributed Prom counters)."""
import types

import jax
import jax.numpy as jnp
import pytest

from repro.obs.hardware import CPU_HOST, PEAKS, TPU_V5E, HardwareSpec, detect
from repro.serving.gateway.metrics import Metrics
from repro.serving.obs import (ProfileRegistry, SLOAttribution, SLO_PHASES,
                               attribution_report, classify, validate_report)
from repro.serving.obs.prom import render_text

jax.config.update("jax_enable_x64", False)

HW = HardwareSpec(name="test", peak_flops=100e9, hbm_bw=10e9,
                  ici_link_bw=1e9, hbm_bytes=1 << 30)       # ridge OI = 10


class TestHardwareSpec:
    def test_ridge_and_roof(self):
        assert HW.ridge_intensity == pytest.approx(10.0)
        # below the ridge the roof is bandwidth-sloped, above it flat
        assert HW.roof_flops(1.0) == pytest.approx(10e9)
        assert HW.roof_flops(1000.0) == pytest.approx(100e9)

    def test_detect_never_raises(self):
        """On a device kind the table holds, detect() answers for it."""
        hw = detect()
        assert hw is PEAKS[jax.devices()[0].device_kind]
        assert hw.peak_flops > 0 and hw.hbm_bw > 0

    def test_detect_keys_by_device_kind(self, monkeypatch):
        assert PEAKS["TPU v5 lite"] is TPU_V5E
        assert PEAKS["cpu"] is CPU_HOST
        # an unknown accelerator never borrows the v5e peaks
        monkeypatch.setattr(jax, "devices", lambda: [
            types.SimpleNamespace(device_kind="TPU v4")])
        with pytest.raises(KeyError, match="TPU v4"):
            detect()

    def test_roofline_bench_shares_the_spec(self):
        from benchmarks import roofline
        assert roofline.PEAK_FLOPS == TPU_V5E.peak_flops
        assert roofline.HBM_BW == TPU_V5E.hbm_bw


class TestClassify:
    def test_memory_bound(self):
        # OI = 1 < ridge 10; achieved 5 GB/s of a 10 GB/s roof
        r = classify(1e6, 1e6, 2e-4, HW)
        assert r["bound"] == "memory"
        assert r["intensity"] == pytest.approx(1.0)
        assert r["pct_of_roof"] == pytest.approx(0.5)
        assert r["achieved_gbs"] == pytest.approx(5.0)

    def test_compute_bound(self):
        # OI = 100 > ridge; achieved 50 GFLOP/s of the 100 GFLOP/s peak
        r = classify(1e8, 1e6, 2e-3, HW)
        assert r["bound"] == "compute"
        assert r["pct_of_roof"] == pytest.approx(0.5)
        assert r["achieved_gflops"] == pytest.approx(50.0)

    def test_unknown_without_capture(self):
        r = classify(0.0, 0.0, 1e-3, HW)
        assert r["bound"] == "unknown" and r["pct_of_roof"] == 0.0

    def test_pure_data_movement(self):
        # zero FLOPs: placement degrades to achieved-vs-peak bandwidth
        r = classify(0.0, 1e6, 1e-4, HW)
        assert r["bound"] == "memory"
        assert r["pct_of_roof"] == pytest.approx(1.0)


class TestProfileCapture:
    def test_capture_and_cross_check_band(self):
        """A loop-free jitted matmul: structural and XLA FLOP counts must
        agree (the cross-check band), and cost capture must populate every
        roofline input."""
        prof = ProfileRegistry(hw=CPU_HOST)
        f = jax.jit(lambda a, b: a @ b)
        a = jnp.ones((64, 128), jnp.float32)
        b = jnp.ones((128, 32), jnp.float32)
        jax.block_until_ready(f(a, b))
        prof.observe_call("matmul", f, (a, b), {}, 1e-3)
        rec = prof.records[("matmul", prof_sig := next(iter(prof.records))[1])]
        assert rec.analyzed and rec.capture_error is None
        assert rec.calls == 1 and rec.wall_s == pytest.approx(1e-3)
        assert rec.flops == pytest.approx(2 * 64 * 128 * 32, rel=0.1)
        assert rec.xla_flops > 0
        assert 0.5 <= rec.flops_xla_ratio <= 2.0      # loop-free: ratio ~ 1
        assert rec.bytes > 0
        row = prof.function_rows()[0]
        assert row["bound"] in ("memory", "compute")
        assert row["signature"] == prof_sig

    def test_compile_calls_skip_the_timing_mean(self):
        prof = ProfileRegistry(hw=CPU_HOST, capture=False)
        f = jax.jit(lambda a: a + 1)
        x = jnp.ones((8,), jnp.float32)
        prof.observe_call("add", f, (x,), {}, 2.0, compiled=True)
        prof.observe_call("add", f, (x,), {}, 1e-3)
        rec = next(iter(prof.records.values()))
        assert rec.compiles == 1 and rec.calls == 1
        assert rec.mean_s == pytest.approx(1e-3)
        offs = prof.recompile_offenders()
        assert offs and offs[0]["fn"] == "add" and offs[0]["compiles"] == 1

    def test_report_schema(self):
        prof = ProfileRegistry(hw=CPU_HOST)
        f = jax.jit(lambda a: a * 2)
        x = jnp.ones((4, 4), jnp.float32)
        jax.block_until_ready(f(x))
        prof.observe_call("mul", f, (x,), {}, 1e-4)
        counts = validate_report(prof.report())
        assert counts["functions"] == 1
        with pytest.raises(AssertionError):
            validate_report({"hardware": {}, "functions": []})


class _StubReq:
    """Just the request surface SLOAttribution touches."""

    def __init__(self, uid, t_submit):
        self.uid = uid
        self.t_submit = t_submit
        self.t_admit = None
        self.t_done = None
        self.state = "queued"
        self.stall_s = 0.0


class TestSLOAttribution:
    def test_queued_only_cancel(self):
        """A request cancelled while still queued: its whole wall time is
        queue_wait, and the components sum to the wall exactly."""
        slo = SLOAttribution()
        req = _StubReq(1, 100.0)
        slo.observe_submit(req)
        req.state = "cancelled"
        comp = slo.close(req, now=105.0)
        assert comp["queue_wait"] == pytest.approx(5.0)
        assert sum(comp.values()) == pytest.approx(5.0)
        snap, wall = slo.snapshot(req)
        assert wall == pytest.approx(5.0)
        assert sum(snap.values()) == pytest.approx(wall)

    def test_preempted_golden(self):
        """submit +0 → admit +1 → token +2 → preempt +3 → re-admit +4 (stays
        preempted: replay prefill is preemption cost) → token +5 → done +6.
        Base epoch is nonzero: 0.0 timestamps mean "unset" to the engine."""
        slo = SLOAttribution()
        req = _StubReq(2, 100.0)
        slo.observe_submit(req)
        req.t_admit = 101.0
        slo.observe_admit(req)
        slo.observe_token(req, now=102.0)
        slo.observe_preempt(req, now=103.0)
        req.t_admit = 104.0
        slo.observe_admit(req)                  # must NOT restart prefill
        slo.observe_token(req, now=105.0)
        req.state = "done"
        comp = slo.close(req, now=106.0)
        assert comp["queue_wait"] == pytest.approx(1.0)
        assert comp["prefill"] == pytest.approx(1.0)
        assert comp["preempted"] == pytest.approx(2.0)      # +3 → +5
        assert comp["decode"] == pytest.approx(2.0)         # +2→+3 and +5→+6
        assert sum(comp.values()) == pytest.approx(6.0)

    def test_chunked_prefill_stall_carved(self):
        """Stall wall time is carved out of decode (never other phases) and
        the sum-to-wall identity survives the carve."""
        slo = SLOAttribution()
        req = _StubReq(3, 100.0)
        slo.observe_submit(req)
        req.t_admit = 101.0
        slo.observe_admit(req)
        slo.observe_token(req, now=103.0)
        req.stall_s = 0.5
        req.state = "done"
        comp = slo.close(req, now=105.0)
        assert comp["prefill"] == pytest.approx(2.0)
        assert comp["decode"] == pytest.approx(1.5)
        assert comp["decode_stall"] == pytest.approx(0.5)
        assert sum(comp.values()) == pytest.approx(5.0)

    def test_stall_clamped_to_decode(self):
        # a stall claim larger than the decode interval cannot push any
        # component negative
        slo = SLOAttribution()
        req = _StubReq(4, 100.0)
        slo.observe_submit(req)
        req.t_admit = 101.0
        slo.observe_admit(req)
        slo.observe_token(req, now=102.0)
        req.stall_s = 99.0
        req.state = "expired"
        comp = slo.close(req, now=103.0)
        assert comp["decode"] == 0.0
        assert comp["decode_stall"] == pytest.approx(1.0)
        assert min(comp.values()) >= 0.0
        assert sum(comp.values()) == pytest.approx(3.0)

    def test_close_idempotent_and_violations(self):
        slo = SLOAttribution()
        req = _StubReq(5, 100.0)
        slo.observe_submit(req)
        req.state = "expired"
        first = slo.close(req, now=101.0)
        again = slo.close(req, now=999.0)       # frozen: later close ignored
        assert again == first and slo.closed == 1
        slo.note_violation("queue_wait")
        slo.note_violation("queue_wait")
        assert slo.violations == {"queue_wait": 2}

    def test_prom_renders_attributed_counters(self):
        m = Metrics()
        m.inc("slo_violation__queue_wait")
        m.inc("slo_violation__decode", 2)
        m.observe("slo_phase_ms__decode", 12.5)
        text = render_text(m)
        assert 'slo_violation{id="queue_wait"} 1' in text
        assert 'slo_violation{id="decode"} 2' in text
        assert "slo_phase_ms" in text


@pytest.fixture(scope="module")
def profiled_run():
    """One profiled serving run on the tiny model: profiler + SLO wiring +
    an unmeetable deadline so a violation gets attributed."""
    from repro.configs.base import get_config
    from repro.launch.train import reduce_config
    from repro.models.transformer import Model
    from repro.serving import PagedKV, RequestSpec, ServeEngine
    from repro.serving.gateway import Gateway

    cfg = reduce_config(get_config("bitnet-2b"), "tiny")
    model = Model(cfg, mode="serve")
    params = model.init(jax.random.PRNGKey(0))
    prof = ProfileRegistry()
    eng = ServeEngine(model, params, max_slots=2, max_len=64,
                      kv=PagedKV(page=8, n_pages=24), profiler=prof)
    gw = Gateway(eng)
    reqs = [gw.submit([1, 2, 3, 4], RequestSpec(max_new_tokens=4)),
            gw.submit([5, 6, 7], RequestSpec(max_new_tokens=4)),
            gw.submit([8, 9], RequestSpec(max_new_tokens=3,
                                          deadline_ms=0.01))]
    gw.run_until_drained()
    return gw, prof, reqs


class TestEngineIntegration:
    def test_capture_on_tiny_model(self, profiled_run):
        gw, prof, _ = profiled_run
        rows = prof.function_rows()
        assert rows, "profiler saw no dispatches"
        names = {r["fn"] for r in rows}
        assert any("decode" in n for n in names)
        captured = [r for r in rows if r["capture_error"] is None
                    and r["flops"] > 0]
        assert captured, f"no cost capture succeeded: {rows}"
        # the decode graph scans over layers: the loop-weighted structural
        # count must be >= XLA's once-counted figure
        for r in captured:
            assert r["flops_xla_ratio"] >= 0.9
            assert r["bound"] in ("memory", "compute")
            assert r["calls"] > 0 and r["mean_ms"] > 0

    def test_attribution_report_validates(self, profiled_run):
        gw, prof, _ = profiled_run
        report = attribution_report(gw, prof)
        counts = validate_report(report)
        assert counts["functions"] >= 1
        assert set(report["slo"]["phases"]) == set(SLO_PHASES)
        assert report["host_overhead"]["frac_of_tick"] >= 0.0

    def test_components_sum_to_wall(self, profiled_run):
        """Acceptance invariant: every request's attribution components sum
        to its wall time."""
        gw, _, reqs = profiled_run
        for req in reqs:
            comp, wall = gw.slo.snapshot(req)
            assert wall > 0.0
            assert min(comp.values()) >= 0.0
            assert sum(comp.values()) == pytest.approx(wall, abs=1e-6)

    def test_violation_attributed_and_rendered(self, profiled_run):
        gw, _, reqs = profiled_run
        assert gw.metrics.counter("slo_violations_total") >= 1
        attributed = {n: v for n, v in gw.metrics.counters.items()
                      if n.startswith("slo_violation__")}
        assert attributed, "violation not attributed to any phase"
        assert sum(attributed.values()) == \
            gw.metrics.counter("slo_violations_total")
        text = render_text(gw.metrics)
        assert 'slo_violation{id="' in text
        rep = gw.slo_report()
        assert rep["violations_total"] >= 1
        assert rep["requests_closed"] == len(reqs)


class TestThreadedDispatch:
    """Observability correctness under the async runtime's thread model:
    the compile watch must attribute compiles race-free across threads, and
    the engine's host-gap probe must never count cross-thread wall time."""

    def test_compile_watch_concurrent_single_attribution(self):
        import threading
        from repro.serving.obs import CompileWatch

        calls = []
        fn = jax.jit(lambda x: x * 2)
        watch = CompileWatch(fn, "mul2",
                             on_compile=lambda n, s: calls.append((n, s)))
        xs = [jnp.ones((4,)), jnp.ones((8,)), jnp.ones((16,))]
        barrier = threading.Barrier(8)

        def worker():
            barrier.wait()
            for x in xs * 5:
                watch(x)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        # exactly one compile per distinct shape signature, no matter how
        # the 8 threads interleaved (the old cache-size diff miscounted here)
        assert watch.compiles == len(xs)
        assert len(calls) == len(xs)
        assert len({s for _n, s in calls}) == len(xs)

    def test_dispatch_gap_is_per_thread(self, profiled_run):
        """A dispatch issued from a different thread than the previous one
        must re-arm the gap clock, not record the cross-thread interval."""
        import threading
        import time as _time
        gw, _prof, _reqs = profiled_run
        eng = gw.engine
        eng._t_dev_end = _time.perf_counter() - 10.0   # 10 s ago, main thread
        eng._dispatch_tid = threading.get_ident()
        before_idle = eng.stats.tick_gap_ms_sum
        before_overlap = eng.stats.tick_gap_overlap_ms_sum
        out = {}

        def other_thread():
            out["r"] = eng._dispatch(lambda: 1)
        t = threading.Thread(target=other_thread)
        t.start()
        t.join(timeout=30)
        assert out["r"] == 1
        # the 10 s cross-thread gap is NOT attributed to either ledger
        assert eng.stats.tick_gap_ms_sum == before_idle
        assert eng.stats.tick_gap_overlap_ms_sum == before_overlap
        # …but a same-thread follow-up records a (small) gap again
        def same_thread_twice():
            eng._dispatch(lambda: 1)
            eng._dispatch(lambda: 2)
        t2 = threading.Thread(target=same_thread_twice)
        t2.start()
        t2.join(timeout=30)
        gained = (eng.stats.tick_gaps + eng.stats.tick_gaps_overlap)
        assert gained > 0

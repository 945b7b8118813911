"""The trace read by the program's own names (``chipbench/trace_names.py``)
and the three readers built on it."""
import json
import time

import pytest

from chipbench import peaks, trace_names, trace_reduce
from chipbench.modelcfg import ROOT, load_config

CHIP_TRACE = str(ROOT / "testdata" / "small_v5e.xplane.pb")
NEW_READERS = ("ternary_proj_roofline", "lm_head_roofline",
               "admission_device_share")


def _reader(name):
    from chipbench.harness import _load_module
    return _load_module(ROOT / "metrics" / f"{name}.py").read


def test_xplane_schema_reads_metadata_a_profile_data_hides():
    planes = trace_names.load(CHIP_TRACE)
    dev = next(p for p in planes if p.name == "/device:TPU:0")
    ops = {ln.name: ln for ln in dev.lines}[trace_reduce.OPS_LINE].events
    kernel = next(e for e in ops if "paged_flash_decode" in e.name)
    assert kernel.stats["tf_op"].startswith("jit(<lambda>)/")
    assert "program_id" in kernel.stats
    runs = {ln.name: ln for ln in dev.lines}[trace_reduce.MODULES_LINE].events
    assert sorted(e.stats["run_id"] for e in runs) == [15, 16, 17, 18, 19, 20]
    host = [e for p in planes if p.name == "/host:CPU" for ln in p.lines
            for e in ln.events if e.name == trace_names.ENQUEUE]
    assert {e.stats["run_id"] for e in host} <= set(range(15, 21)) and host


def test_scopes_of_a_chip_trace_and_the_existing_keys_unchanged():
    before = trace_reduce.reduce_file(CHIP_TRACE)
    red = trace_names.reduce_file(CHIP_TRACE)
    for k, v in before.items():
        assert red[k] == v, k
    # a trace of a program with no scopes: ops listed by base name, each at
    # its self time, which for an op with nothing inside it is its time
    table = red["scopes"]["jit__lambda"]
    assert table["paged_flash_decode"] == pytest.approx(
        before["ops"]["paged_flash_decode"])
    # the kernel's framework path has a component named after it
    named = trace_names.reduce_planes(trace_names.load(CHIP_TRACE),
                                      scopes=("paged_flash_decode",))
    assert named["scopes"]["jit__lambda"]["paged_flash_decode"] == \
        pytest.approx(before["ops"]["paged_flash_decode"])
    assert red["spans"] == {} and red["by_span"] == {}


def _ev(name, s, e, **stats):
    return trace_names.Event(name, s, e, stats)


def _planes(host, ops, modules):
    """Host lines {id: events}, device ops and module runs."""
    return [trace_names.Plane("/host:CPU", [
                trace_names.Line(i, f"t{i}", evs) for i, evs in host.items()]),
            trace_names.Plane("/device:TPU:0", [
                trace_names.Line(1, trace_reduce.OPS_LINE, ops),
                trace_names.Line(2, trace_reduce.MODULES_LINE, modules)])]


def test_self_time_innermost_scope_and_window():
    D = "jit(_decode_fn)/while/body/"
    ops = [_ev("%while.1 = ...", 1000, 9000, program_id=7, tf_op=D[:-1]),
           _ev("%fusion.2 = ...", 2000, 4000, program_id=7,
               tf_op=D + "lm_head/ternary_proj/dot_general:"),
           _ev("%copy.3 = ...", 4000, 5000, program_id=7, tf_op=D + "copy:"),
           _ev("%fusion.4 = ...", 5000, 7000, program_id=7,
               tf_op=D + "attn/jit(k)/pallas_call:"),
           _ev("%sort.5 = ...", 9500, 12000, program_id=8, tf_op="sort:")]
    mods = [_ev("jit__decode_fn(7)", 1000, 9000, run_id=1),
            _ev("jit__sample_fn(8)", 9500, 12000, run_id=2)]
    host = {1: [_ev(trace_reduce.WINDOW, 0, 11000)]}
    red = trace_names.reduce_planes(_planes(host, ops, mods))
    assert red["scopes"]["jit__decode_fn"] == pytest.approx(
        {"while": 3000e-9, "ternary_proj": 2000e-9, "copy": 1000e-9,
         "attn": 2000e-9})
    # clipped to the window: 9500..11000 of the sampler's sort
    assert red["scopes"]["jit__sample_fn"] == pytest.approx(
        {"sort": 1500e-9})
    assert trace_names.scope_of(D + "lm_head/x:") == "lm_head"
    assert trace_names.scope_of(D + "copy:") is None


def test_by_span_ties_runs_to_the_spans_open_at_their_launch():
    """Run 1 is enqueued on the dispatch thread's own line inside
    serve.admit ⊃ serve.prefill; run 2 on a queue thread, inside an event
    that flows (``_c`` from ``_p``) from the runtime thread's execute, which
    lies inside a call that flows from the dispatch thread's call inside
    serve.decode; the run named 99 has no launch on record; run 4
    straddles the window's end."""
    dispatch = [_ev("serve.admit", 100, 900), _ev("serve.prefill", 200, 800),
                _ev(trace_names.ENQUEUE, 300, 310, run_id=1),
                _ev("serve.decode", 1000, 1500),
                _ev("PJRT_LoadedExecutable_Execute linkage", 1100, 1110,
                    _p=55),
                _ev("serve.sample", 1600, 1700),
                _ev(trace_names.ENQUEUE, 1650, 1660, run_id=4)]
    runtime = [_ev("PJRT_LoadedExecutable_Execute", 1105, 1200, _c=55),
               _ev("tpu::System::Execute", 1106, 1140, _p=66),
               _ev(trace_names.ENQUEUE, 1150, 1160, run_id=3)]
    # the runtime's second hop: a queue thread enqueues run 2 inside an
    # event that flows from the runtime thread's execute
    queue = [_ev("IssueSequencedEvent", 1300, 1400, _c=66),
             _ev(trace_names.ENQUEUE, 1310, 1350, run_id=2)]
    host = {1: [_ev(trace_reduce.WINDOW, 0, 5000)] + dispatch, 2: runtime,
            3: queue}
    mods = [_ev("jit__fresh_prefill(1)", 400, 1000, run_id=1),
            _ev("jit__decode_fn(2)", 1200, 3200, run_id=2),
            _ev("jit__x(3)", 3200, 3300, run_id=99),
            _ev("jit__sample_fn(4)", 4800, 5400, run_id=4)]
    ops = [_ev("%f.1 = ...", 400, 1000)]
    red = trace_names.reduce_planes(_planes(host, ops, mods))
    assert red["by_span"] == pytest.approx(
        {"serve.admit": 600e-9, "serve.prefill": 600e-9,
         "serve.decode": 2000e-9, "serve.sample": 200e-9})
    assert red["spans"]["serve.admit"] == [pytest.approx(800e-9), 1]
    assert red["spans"]["serve.decode"] == [pytest.approx(500e-9), 1]


def test_idle_gaps_prefer_serve_spans():
    host = {1: [_ev(trace_reduce.WINDOW, 0, 10000),
                _ev("serve.emit", 1000, 2500),
                _ev("serve.wait_device", 2500, 3000),
                _ev("backlog.token", 0, 10000)],
            2: [_ev("$queue.py:154 get", 1000, 2200),
                _ev("$threading.py acquire", 6000, 8000)]}
    ops = [_ev("%f.1 = ...", 0, 1000), _ev("%f.2 = ...", 3000, 6000),
           _ev("%f.3 = ...", 9000, 10000)]
    red = trace_names.reduce_planes(_planes(host, ops, []))
    assert red["idle_gaps"] == [
        ("backlog.token", pytest.approx(3000e-9)),
        ("serve.emit", pytest.approx(2000e-9))]
    # with no serve span over a gap it is named as before
    plain = trace_reduce._label(6000, 9000, [("$threading.py acquire", 6000,
                                              8000)])
    assert plain == "$threading.py acquire"


def _ctx(red):
    dims = load_config(ROOT / "configs" / "bitnet-2b.json")["dims"]
    tokens = [(0.5 + i * 1e-3, 300, i % 4 != 0) for i in range(640)]
    return {"dims": dims, "trace": red, "peaks": peaks.peaks_for("TPU v5 lite"),
            "counters": {"ticks": 10, "tokens_out": 600, "slots": 64},
            "records": {"tokens": tokens},
            "window": ((0.0, 100.0), (3.0, 103.0)),
            "engine": {"adapter_rank": 8, "tenants": 16}}


def test_new_readers_on_a_synthetic_trace():
    red = {"devices": 1, "busy_s": 2.5, "window_s": 3.0,
           "modules": {"jit__decode_fn": 2.0}, "module_runs": {
               "jit__decode_fn": 10, "jit__sample_fn": 10},
           "ops": {}, "op_calls": {},
           "scopes": {"jit__decode_fn": {"ternary_proj": 0.2, "lm_head": 0.05,
                                         "attn": 1.0},
                      "jit__fresh_prefill": {"ternary_proj": 9.0}},
           "by_span": {"serve.admit": 0.25, "serve.decode": 2.0}}
    ctx = _ctx(red)
    d = ctx["dims"]
    w = 1_553_203_200
    io = 30 * 2 * sum(k + n for k, n in [(2560, 2560), (2560, 640),
                                         (2560, 640), (2560, 2560),
                                         (2560, 6912), (6912, 2560)])
    need = max(2 * w * 640 / 197e12, (w // 4 * 10 + io * 640) / 819e9)
    assert _reader("ternary_proj_roofline")(ctx) == pytest.approx(
        100 * need / 0.2)
    h = 2560 * 128256
    need = max(2 * h * 640 / 197e12,
               (h // 4 * 10 + d.vocab * 4 * 640) / 819e9)
    assert _reader("lm_head_roofline")(ctx) == pytest.approx(
        100 * need / 0.05)
    assert _reader("admission_device_share")(ctx) == pytest.approx(10.0)


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_read_nothing_without_the_programs_names(name):
    assert _reader(name)(_ctx(None)) is None
    # what a program without scopes or spans leaves: the reduction alone
    bare = {"devices": 1, "busy_s": 2.5, "window_s": 3.0,
            "modules": {"jit__decode_fn": 2.0},
            "module_runs": {"jit__decode_fn": 10}, "ops": {}, "op_calls": {}}
    assert _reader(name)(_ctx(bare)) is None
    empty = dict(bare, scopes={}, spans={}, by_span={})
    assert _reader(name)(_ctx(empty)) is None


def test_engine_spans_in_a_cpu_trace(tmp_path):
    """A few ticks of a tiny engine behind the async runtime, traced with
    ``jax.profiler`` on the CPU: the dispatch thread's phases, the backlog
    thread's events and the clients' bind waits are all in the trace."""
    import jax
    from repro.launch.serve import build_engine
    from repro.serving import RequestSpec
    from repro.serving.gateway import Gateway
    from repro.serving.runtime import AsyncServeRuntime
    eng = build_engine("bitnet-2b", "tiny", slots=2, max_len=64,
                       prefill="batched", kv="paged", page=8)
    with AsyncServeRuntime(Gateway(eng), depth=1) as rt:
        rt.submit([1, 2, 3], RequestSpec(max_new_tokens=2)).result(120)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                tickets = [rt.submit(list(range(1, 5 + i)),
                                     RequestSpec(max_new_tokens=4))
                           for i in range(3)]
                for t in tickets:
                    t.result(120)
                time.sleep(0.05)
        finally:
            jax.profiler.stop_trace()
    red = trace_names.reduce_file(trace_reduce.latest_xplane(str(tmp_path)))
    spans = red["spans"]
    for name in ("serve.decode", "serve.sample", "serve.admit",
                 "serve.kv_write", "serve.prefill", "serve.wait_device",
                 "backlog.token", "client.bind"):
        assert name in spans, name
        assert spans[name][1] >= 1 and spans[name][0] >= 0
    assert spans["serve.kv_write"][1] == spans["serve.prefill"][1] == 3
    json.dumps(red)

"""A configuration joins the benchmark by files of its own: it names its
reference module, its engine block passes through to the program, and the
readers of its cells see the whole run (the configuration, the engine's
stats at both ends of the window, the trace by the program's names)."""
import dataclasses
import json
import time

import pytest

from chipbench import harness, peaks, trace_names, trace_reduce, traffic
from chipbench.harness import Run, run_cell
from chipbench.modelcfg import ROOT, load_config, reference
from chipbench.reference import model as gqa

BENCH = json.loads((ROOT.parent / "BENCHMARK.json").read_text())
TINY = ROOT / "tests" / "tiny"
CELL = next(c for c in BENCH["workloads"]
            if c["name"] == "bitnet2b-decode-tenants")
CHIP_TRACE = str(ROOT / "testdata" / "small_v5e.xplane.pb")
SEED = 2 ** 32 + 21
SERVED = [ROOT / "configs" / "bitnet-2b.json"] + [
    TINY / f"{n}.json" for n in ("bitnet-2b", "starcoder2-7b",
                                 "bitnet-2b-recording",
                                 "bitnet-2b-wrong-seed")]


def _tiny_run(config, trace=False, bench=BENCH):
    import jax
    return run_cell(bench, CELL, SEED, 2.0, trace, jax.devices(),
                    time.perf_counter(), preset="tiny",
                    config=load_config(TINY / f"{config}.json"),
                    traffic=traffic.load(TINY / "closed.json"))


@pytest.mark.parametrize("path", SERVED, ids=lambda p: p.parent.name + "/"
                         + p.stem)
def test_configuration_names_a_reference_that_keeps_the_contract(path):
    cfg = load_config(path)
    ref = reference(cfg["reference"])
    for fn in ("dims_of", "served_gaps", "logits"):
        assert callable(getattr(ref, fn)), fn
    dims = cfg["dims"]
    assert hash(dims) == hash(ref.dims_of(cfg["model"]))
    with pytest.raises(AttributeError):
        dims.vocab = 1
    assert dims.vocab == cfg["model"]["vocab_size"]
    assert dims.layers == cfg["model"]["num_hidden_layers"]


def test_a_configuration_without_a_reference_is_refused(tmp_path):
    cfg = json.loads((TINY / "bitnet-2b.json").read_text())
    del cfg["reference"]
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    with pytest.raises(KeyError, match="reference"):
        load_config(tmp_path / "cfg.json")


def test_a_block_the_gqa_reference_lacks_loads_through_its_own_module():
    cfg = load_config(TINY / "windowed-silu.json")
    dims = cfg["dims"]
    assert (dims.layers, dims.vocab, dims.window, dims.act) == \
        (2, 2048, 64, "silu")
    with pytest.raises(KeyError):
        gqa.dims_of(cfg["model"])


@pytest.mark.usefixtures("own_compile_cache")
def test_engine_block_passes_through_to_build_engine(monkeypatch):
    import repro.launch.serve as serve

    class Built(Exception):
        pass

    seen = {}

    def build_engine(arch, preset, **kw):
        seen.update(kw, arch=arch, preset=preset)
        raise Built

    monkeypatch.setattr(serve, "build_engine", build_engine)
    cfg = load_config(TINY / "bitnet-2b.json")
    cfg["engine"] = dict(cfg["engine"], prefill_chunk=32, n_pages=40)
    run = Run(BENCH, CELL, SEED, 2.0, False, 0.0, preset="tiny", config=cfg,
              traffic=traffic.load(TINY / "closed.json"))
    with pytest.raises(Built):
        run.build()
    assert seen == {"arch": "bitnet-2b", "preset": "tiny",
                    "prefill": "batched", "kv": "paged",
                    "seed": SEED % (2 ** 31 - 1), "slots": 4, "max_len": 128,
                    "page": 16, "n_adapters": 3, "adapter_rank": 8,
                    "adapter_budget_kb": 4096, "prefill_chunk": 32,
                    "n_pages": 40}


@pytest.mark.usefixtures("own_compile_cache")
@pytest.mark.parametrize("key", ["no_such_setting", "kv"])
def test_an_engine_key_the_program_does_not_take_fails_at_set_up(key):
    cfg = load_config(TINY / "bitnet-2b.json")
    cfg["engine"] = dict(cfg["engine"], **{key: 1})
    run = Run(BENCH, CELL, SEED, 2.0, False, 0.0, preset="tiny", config=cfg,
              traffic=traffic.load(TINY / "closed.json"))
    with pytest.raises(TypeError, match=key):
        run.build()


@pytest.mark.usefixtures("own_compile_cache")
@pytest.mark.parametrize("config,correct", [("bitnet-2b-recording", True),
                                            ("bitnet-2b-wrong-seed", False)])
def test_run_decides_correct_by_the_reference_its_configuration_names(
        config, correct):
    ref = reference(load_config(TINY / f"{config}.json")["reference"])
    calls = list(getattr(ref, "CALLS", []))
    out = _tiny_run(config)
    check = out["checks"]["max_logit_gap"]
    assert out["correct"] is correct, check
    assert (check["value"] <= check["limit"]) is correct
    if correct:
        assert len(ref.CALLS) == len(calls) + 1
        assert ref.CALLS[-1][0] == SEED % (2 ** 31 - 1)


@pytest.mark.usefixtures("own_compile_cache")
def test_readers_see_the_configuration_the_engine_stats_and_the_named_trace(
        monkeypatch, tmp_path):
    """A traced tiny run on the CPU, whose reader takes the decode steps
    from the engine's stats and the kernel's time from a scope table. The
    CPU's trace holds the program's spans but no device op, so the chip's
    small trace stands in for what the readers get."""
    from repro.serving.engine import EngineStats
    chip = trace_names.reduce_file(CHIP_TRACE)
    seen = {}
    real_reduce, real_load = Run.reduce_trace, harness.load_module

    def reduce_trace(self):
        seen["cpu"] = real_reduce(self)
        return chip

    def load_module(path):
        mod = real_load(path)
        read = mod.read
        mod.read = lambda ctx: read(seen.setdefault("ctx", ctx))
        return mod

    monkeypatch.setattr(Run, "reduce_trace", reduce_trace)
    monkeypatch.setattr(harness, "load_module", load_module)
    monkeypatch.setattr(harness, "METRICS", TINY / "metrics")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    v5e = peaks.peaks_for("TPU v5 lite")
    monkeypatch.setattr(harness.peaks, "peaks_for", lambda kind: v5e)
    name = "flash_ms_per_decode_step"
    bench = dict(BENCH, per_layer=[{
        "name": name, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "output_tok_s", "workloads": [CELL["name"]]}])
    out = _tiny_run("bitnet-2b", trace=True, bench=bench)
    assert out["correct"]
    ctx = seen["ctx"]
    assert ctx["config"]["name"] == "bitnet-2b-tiny"
    assert ctx["config"]["engine"] is ctx["engine"]
    fresh = EngineStats()
    fields = {f.name for f in dataclasses.fields(fresh) if isinstance(
        getattr(fresh, f.name), (int, float, dict))}
    s0, s1 = ctx["stats"]["start"], ctx["stats"]["end"]
    assert set(s0) == set(s1) == fields
    assert {"decode_steps", "phase_ms"} <= fields
    # the counters readers had are the same window's deltas
    assert ctx["counters"]["ticks"] == s1["ticks"] - s0["ticks"] > 0
    assert ctx["counters"]["tokens_out"] == s1["tokens_out"] - s0["tokens_out"]
    steps = s1["decode_steps"] - s0["decode_steps"]
    assert 0 < steps <= ctx["counters"]["ticks"]
    # the harness reduced its own trace by the program's names
    assert {"scopes", "spans", "by_span"} <= set(seen["cpu"])
    assert "serve.decode" in seen["cpu"]["spans"]
    kernel = trace_reduce.total(chip["ops"], "paged_flash_decode")
    assert out["metrics"][name]["value"] == pytest.approx(1e3 * kernel / steps)

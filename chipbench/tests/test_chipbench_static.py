"""The benchmark's files, generator, counts, peaks and trace reduction."""
import json
import os
import re
import statistics
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import counts, peaks, trace_reduce, traffic
from chipbench.modelcfg import ROOT, dims_of, load_config

REPO = ROOT.parent
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [c["name"] for c in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _cell(name):
    return next(c for c in BENCH["workloads"] if c["name"] == name)


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_its_files_by_name(name):
    cell = _cell(name)
    conf = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    cfg = load_config(REPO / conf["file"])
    assert cfg["name"] == conf["name"]
    assert cfg["reduced"] == conf["reduced"]
    tr = traffic.load(ROOT / "traffic" / f"{cell['traffic']}.json")
    assert tr["loop"] in ("closed", "open")
    assert tr["prompt_len"]["max"] + tr["output_len"]["max"] \
        <= cfg["engine"]["max_len"]
    check = json.loads((ROOT / "checks" / f"{name}.json").read_text())
    assert check["limit"] > 0
    metrics = [m for m in BENCH["per_layer"] + BENCH["end_to_end"]
               if name in m.get("workloads", [name])]
    assert any(m["name"] == "setup_s" for m in metrics)
    for m in BENCH["per_layer"]:
        if name in m.get("workloads", [name]):
            src = (ROOT / "metrics" / f"{m['name']}.py").read_text()
            assert "def read(ctx)" in src
            assert "import repro" not in src and "from repro" not in src


def test_benchmark_file_keeps_to_its_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in BENCH[k]}) == len(BENCH[k])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
    assert 1 <= BENCH["run_seconds"] <= 51
    for cell in BENCH["workloads"]:
        assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200


def test_yardstick_modules_import_nothing_of_the_program():
    for path in ROOT.rglob("*.py"):
        if path.name in ("harness.py", "run.py") or "tests" in path.parts:
            continue
        src = path.read_text()
        assert not re.search(r"^\s*(import|from)\s+repro\b", src, re.M), path


@pytest.mark.parametrize("name", ["decode-tenants"])
def test_traffic_is_deterministic_by_seed(name):
    tr = traffic.load(ROOT / "traffic" / f"{name}.json")
    tenants = 16 if tr.get("adapter_share") else 0
    a = traffic.take(traffic.requests(tr, 2 ** 33 + 5, 1000, tenants), 300)
    b = traffic.take(traffic.requests(tr, 2 ** 33 + 5, 1000, tenants), 300)
    c = traffic.take(traffic.requests(tr, 7, 1000, tenants), 300)
    key = lambda rs: [(r.prompt.tolist(), r.max_new, r.tenant, r.due)
                      for r in rs]
    assert key(a) == key(b)
    assert key(a) != key(c)


@pytest.mark.parametrize("name", ["decode-tenants"])
def test_traffic_follows_its_distributions(name):
    tr = traffic.load(ROOT / "traffic" / f"{name}.json")
    tenants = 16 if tr.get("adapter_share") else 0
    n = traffic.GRID
    reqs = traffic.take(traffic.requests(tr, 11, 1000, tenants), n)
    for key, vals in (("prompt_len", [len(r.prompt) for r in reqs]),
                      ("output_len", [r.max_new for r in reqs])):
        spec = tr[key]
        assert min(vals) >= spec["min"] and max(vals) <= spec["max"]
        assert abs(statistics.median(vals) - spec["median"]) <= 2
        # one pass over the grid: every seed offers the same lengths
        other = traffic.take(traffic.requests(tr, 12, 1000, tenants), n)
        got = sorted(len(r.prompt) if key == "prompt_len" else r.max_new
                     for r in other)
        assert got == sorted(vals)
    share = sum(r.tenant is not None for r in reqs) / n
    assert abs(share - tr.get("adapter_share", 0.0)) < 1e-3
    if tenants:
        used = {r.tenant for r in reqs if r.tenant is not None}
        assert used == set(range(tenants))
    if tr["loop"] == "open":
        mean_gap = reqs[-1].due / n
        assert abs(mean_gap * tr["rate_hz"] - 1) < 0.02


def test_residual_start_staggers_first_requests():
    reqs = [traffic.Req(i, np.zeros(4, np.int32), 100, None) for i in range(4)]
    traffic.residual_start(reqs)
    assert [r.max_new for r in reqs] == [25, 50, 75, 100]


def test_hand_counts():
    bit = load_config(ROOT / "configs" / "bitnet-2b.json")["dims"]
    # StarCoder2-7B at its published widths (the code-completion cell that
    # would run it is left for a later change)
    sc2 = dims_of({"num_hidden_layers": 32, "hidden_size": 4608,
                   "num_attention_heads": 36, "num_key_value_heads": 4,
                   "head_dim": 128, "intermediate_size": 18432,
                   "vocab_size": 49152, "hidden_act": "gelu_pytorch_tanh",
                   "tie_word_embeddings": False, "rope_theta": 1e5,
                   "rms_norm_eps": 1e-5})
    assert counts.linear_params(bit) == 1_553_203_200     # 1.55 B
    assert counts.head_params(bit) == 328_335_360         # 0.33 B, tied
    assert counts.kv_bytes_per_token(bit) == 38_400
    assert counts.kv_bytes_per_token(sc2) == 32_768
    assert counts.linear_params(sc2) == 6_945_767_424     # 6.95 B
    # 2-bit: BitNet 0.47 GB of weights, StarCoder2 about 1.85 GB
    assert round(counts.packed_weight_bytes(bit) / 1e9, 2) == 0.47
    assert round(counts.packed_weight_bytes(sc2) / 1e9, 2) == 1.85
    # one decode token at context 1: 2 FLOPs a weight, plus attention
    assert counts.decode_token_flops(bit, 1) == 2 * (
        1_553_203_200 + 328_335_360) + 30 * 4 * 20 * 128
    f, b = counts.flash_decode_call(bit, [64, 64])
    assert f == 4 * 20 * 128 * 128
    assert b == 2 * 5 * 128 * 128 + 2 * 2560 * 6


def test_peaks_are_keyed_by_device_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_trace_reduction_of_a_chip_trace():
    red = trace_reduce.reduce_file(
        str(ROOT / "testdata" / "small_v5e.xplane.pb"))
    assert red["devices"] == 1 and not red["marked"]
    assert 0 < red["busy_s"] <= red["window_s"]
    kernel = trace_reduce.total(red["ops"], "paged_flash_decode")
    assert 0 < kernel < red["busy_s"]
    assert red["op_calls"]["paged_flash_decode"] == 3
    assert sum(red["module_runs"].values()) == 6
    assert red["idle_gaps"] and all(s > 0 for _, s in red["idle_gaps"])


def _plane(name, lines):
    ev = lambda n, s, e: SimpleNamespace(name=n, start_ns=s, duration_ns=e - s)
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=ln, events=[ev(*e) for e in evs])
        for ln, evs in lines.items()])


def test_trace_reduction_is_clipped_to_the_marked_window():
    """Device work recorded while the profiler starts or stops, outside
    the window's mark, is left out; work that straddles an edge counts
    only inside it."""
    planes = [
        _plane("/host:CPU", {"python3": [
            ("start_trace", 0, 1000), (trace_reduce.WINDOW, 1000, 11000),
            ("write_prefill", 4000, 6000), ("stop_trace", 11000, 13000)]}),
        _plane("/device:TPU:0", {
            "XLA Ops": [("%fusion.1 = f32[8]", 0, 900),
                        ("%paged_flash_decode.2 = f32[8]", 500, 3000),
                        ("%fusion.3 = f32[8]", 7000, 12000)],
            "XLA Modules": [("jit__decode_fn(1)", 0, 3000),
                            ("jit__decode_fn(2)", 7000, 12000)]})]
    red = trace_reduce.reduce_planes(planes)
    assert red["marked"] and red["devices"] == 1
    assert red["window_s"] == pytest.approx(10000e-9)
    assert red["busy_s"] == pytest.approx(6000e-9)
    assert red["ops"] == pytest.approx({"paged_flash_decode": 2000e-9,
                                        "fusion": 4000e-9})
    assert red["op_calls"] == {"paged_flash_decode": 1, "fusion": 1}
    assert red["modules"]["jit__decode_fn"] == pytest.approx(6000e-9)
    assert red["idle_gaps"] == [("write_prefill", pytest.approx(4000e-9))]


def test_union_of_intervals():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [(0, 3), (5, 8)]


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "run.py"), "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _reader(name):
    from chipbench.harness import _load_module
    return _load_module(ROOT / "metrics" / f"{name}.py").read


def _ctx(red):
    dims = load_config(ROOT / "configs" / "bitnet-2b.json")["dims"]
    tokens = [(0.5 + i * 1e-3, 300, i % 4 != 0) for i in range(640)]
    return {"dims": dims, "trace": red, "peaks": peaks.peaks_for("TPU v5 lite"),
            "counters": {"ticks": 10, "tokens_out": 600, "slots": 64},
            "records": {"tokens": tokens},
            "window": ((0.0, 100.0), (3.0, 103.0)),
            "engine": {"adapter_rank": 8, "tenants": 16}}


def test_metric_readers_on_a_synthetic_trace():
    red = {"devices": 1, "busy_s": 2.4, "window_s": 3.0,
           "modules": {"jit__decode_fn": 1.0, "jit__fresh_prefill": 0.1},
           "ops": {"paged_flash_decode": 0.2, "batched_lora_matmul": 0.01},
           "op_calls": {"paged_flash_decode": 300,
                        "batched_lora_matmul": 600}}
    ctx = _ctx(red)
    assert _reader("device_idle_share.decode")(ctx) == pytest.approx(20.0)
    assert _reader("batch_occupancy")(ctx) == pytest.approx(93.75)
    dims = ctx["dims"]
    flops = 640 * counts.decode_token_flops(dims, 300) \
        + 480 * counts.adapter_flops_per_token(dims, 8)
    assert _reader("decode_mfu")(ctx) == pytest.approx(
        100 * flops / 197e12)
    f, b = counts.flash_decode_call(dims, [300] * 640)
    assert _reader("paged_flash_decode_roofline")(ctx) == pytest.approx(
        100 * max(30 * f / 197e12, 30 * b / 819e9) / 0.2)
    assert 0 < _reader("batched_lora_matmul_roofline")(ctx) < 100


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]
                                  if m["source"] == "device_trace"])
def test_trace_readers_read_nothing_without_a_trace(name):
    assert _reader(name)(_ctx(None)) is None
    empty = {"devices": 0, "busy_s": 0.0, "window_s": 3.0, "modules": {},
             "ops": {}, "op_calls": {}}
    assert _reader(name)(_ctx(empty)) is None

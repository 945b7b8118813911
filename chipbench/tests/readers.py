"""What the metric readers' tests share: a reader found by name as the
harness finds it, and the context the harness hands it, built around a
trace reduction, for a BitNet-2B window of 640 tokens at context 300, three
of four with an adapter, over 10 engine ticks of 64 slots."""
from chipbench import peaks
from chipbench.modelcfg import ROOT, load_config, load_module


def reader(name, directory=ROOT / "metrics"):
    return load_module(directory / f"{name}.py").read


def context(red):
    cfg = load_config(ROOT / "configs" / "bitnet-2b.json")
    tokens = [(0.5 + i * 1e-3, 300, i % 4 != 0) for i in range(640)]
    start = {"ticks": 5, "tokens_out": 100, "decode_steps": 4}
    end = {"ticks": 15, "tokens_out": 700, "decode_steps": 14}
    return {"dims": cfg["dims"], "trace": red,
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "counters": {"ticks": 10, "tokens_out": 600, "slots": 64},
            "records": {"tokens": tokens},
            "window": ((0.0, 100.0), (3.0, 103.0)),
            "engine": cfg["engine"], "config": cfg,
            "stats": {"start": start, "end": end}}

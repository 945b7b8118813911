"""A whole run of the cell at a tiny size on the CPU, past the look for a
chip, under both loop kinds the generator drives: sound, ``correct`` comes
out true; with the timed path broken underneath (every served token
altered where the sampler produces it), it comes out false."""
import json
import time

import pytest

from chipbench import traffic
from chipbench.harness import run_cell
from chipbench.modelcfg import ROOT, load_config

BENCH = json.loads((ROOT.parent / "BENCHMARK.json").read_text())
TINY = ROOT / "tests" / "tiny"
CELL = "bitnet2b-decode-tenants"


pytestmark = pytest.mark.usefixtures("own_compile_cache")


def _run(loop, seed):
    import jax
    cell = next(c for c in BENCH["workloads"] if c["name"] == CELL)
    return run_cell(BENCH, cell, seed, 2.0, False, jax.devices(),
                    time.perf_counter(), preset="tiny",
                    config=load_config(TINY / "bitnet-2b.json"),
                    traffic=traffic.load(TINY / f"{loop}.json"))


@pytest.mark.parametrize("loop", ["closed", "open"])
@pytest.mark.parametrize("fault", [False, True], ids=["sound", "token"])
def test_run_decides_correct(loop, fault, monkeypatch):
    if fault:
        from repro.serving.engine import ServeEngine
        sample = ServeEngine._sample_fn

        def altered(self, logits, *args, **kw):
            return (sample(self, logits, *args, **kw) + 1) % 2048

        monkeypatch.setattr(ServeEngine, "_sample_fn", altered)
    out = _run(loop, 2 ** 32 + 9)
    check = out["checks"]["max_logit_gap"]
    assert out["correct"] is (not fault), check
    assert (check["value"] > check["limit"]) is fault
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    names = {m["name"] for m in BENCH["end_to_end"]
             if CELL in m.get("workloads", [CELL])}
    assert set(out["metrics"]) == names
    assert all(v["value"] > 0 for v in out["metrics"].values())

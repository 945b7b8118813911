"""Run one cell of the benchmark once, on the chips of this machine.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, metrics and bounds are in ``BENCHMARK.json`` at the
root of the checkout; a cell's traffic is ``chipbench/traffic/<traffic>.json``,
its configuration the file ``BENCHMARK.json`` names, and the limit of its
correctness check ``chipbench/checks/<workload>.json``. Progress goes to
standard error; the last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` (the end-to-end metrics,
or with ``--trace 1`` the per-layer ones), ``device`` and ``checks`` (each
number compared, beside its limit). Exits non-zero, printing no result,
where JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; known: {sorted(cells)}",
              file=sys.stderr)
        return 2
    cell = cells[args.workload]

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"chipbench: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 3

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench.harness import run_cell
    result = run_cell(bench, cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell["chips"]], T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

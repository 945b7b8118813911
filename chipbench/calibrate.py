"""Read the two ends of a cell's correctness limit on the chip, over
several seeds in one process (the compile cache makes every seed after the
first cheap). Each seed is one run of the cell with the fp8 control in the
program's place, decided by the harness's own comparison (it has to come
out not correct); the same run reads the program's gaps too.

    python chipbench/calibrate.py --workload <name> --seconds <s> --seeds 11 12 13

Prints one line per seed and, last, a JSON object with every seed's
readings: the mean and widest gap of the served tokens below the
reference's best logit, and of the tokens the control puts first at the
same positions. The benchmark's own runs never run the control.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(c for c in bench["workloads"] if c["name"] == args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("calibrate: needs the cell's TPU chips", file=sys.stderr)
        return 3
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench.harness import Run
    readings = []
    for seed in args.seeds:
        run = Run(bench, cell, seed, args.seconds, False, time.perf_counter(),
                  control=True)
        out = run.run(devices[:cell["chips"]])
        g = run.gaps
        row = {"seed": seed, "control_correct": out["correct"],
               "checks": out["checks"],
               "program_mean": float(g["program"].mean()),
               "control_mean": float(g["control"].mean()),
               "program_widest": float(g["program"].max()),
               "control_widest": float(g["control"].max()),
               "tokens": int(g["program"].size),
               "attempted": out["attempted"], "failed": out["failed"]}
        readings.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload, "readings": readings}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

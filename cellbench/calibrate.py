"""Read, on the chip, the numbers that the limits are set from.

    python3 -m cellbench.calibrate --workload <name> --seeds 1,2,3 \\
        --control-seeds 1,2,3 --seconds 8 --out chiprun_out/calib.jsonl

One process runs the cell on every seed with a short window at the cell's
own load and prints what ``correct`` compares (the lower readings). On the
control seeds it also puts the control (the reference in fp8) and, for a
training cell, the half-batch fault in the program's place (the upper
readings). It changes no limit: a person sets them, by PERF.md's rule.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="cellbench.calibrate")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)

    from cellbench import run

    cell, _, compiles, loop = run.prepare(a.workload)
    controls = {int(s) for s in a.control_seeds.split(",") if s}
    with open(a.out, "a") as f:
        for seed in (int(s) for s in a.seeds.split(",")):
            t = time.perf_counter()
            out = loop.run(cell, seed, a.seconds, None, t, compiles,
                           control=seed in controls)
            line = {"workload": a.workload, "seed": seed,
                    "readings": out["readings"],
                    "end_to_end": out["end_to_end"],
                    "attempted": out["attempted"], "failed": out["failed"],
                    "setup_s": out["setup_s"],
                    "seconds": time.perf_counter() - t}
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())

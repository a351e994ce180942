"""Find the knee of an open-loop mix, once, on the chip.

    python3 -m cellbench.sweep --workload gpt2m.serve-prefill \\
        --rates 16,20,24,28,32,36 --seconds 12 --seed 5

One process offers the cell's own traffic at each rate in turn and prints
the tails, the misses and how the admission queue grew over the window:
the knee is the highest rate whose queue does not grow. The cell then
offers a fixed share of it, stated in its traffic file; nothing in a run
searches for a rate.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="cellbench.sweep")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--seed", type=int, default=5)
    a = ap.parse_args(argv)

    from cellbench import run

    cell, _, compiles, loop_serve = run.prepare(a.workload)
    for rate in (float(r) for r in a.rates.split(",")):
        cell.traffic["rate_rps"] = rate
        out = loop_serve.run(cell, a.seed, a.seconds, None,
                             time.perf_counter(), compiles)
        depth = out["facts"]["queue_depth"]
        third = max(1, len(depth) // 3)
        occ = out["facts"]["occupancy"]
        print(json.dumps({
            "rate_rps": rate, **out["end_to_end"],
            "attempted": out["attempted"], "failed": out["failed"],
            "queue_first_third": sum(depth[:third]) / third,
            "queue_last_third": sum(depth[-third:]) / third,
            "queue_max": max(depth), "ticks": len(depth),
            "occupancy_mean": sum(occ) / len(occ),
            "lag_p90_ms": sorted(out["facts"]["lag_s"])[
                int(0.9 * len(out["facts"]["lag_s"]))] * 1e3}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Offered-load sweep of an open-loop cell, to find its knee once.

    python3 bench/sweep.py --workload <cell> --rates 2,3,4 --seconds 30 --seed 1

Serves the cell at each arrival rate in turn, in one process, and prints
one JSON line per rate: tokens/s, the TTFT and TPOT tails, and how many
requests due in the window were still waiting for their first token when
it closed (a backlog that grows with the window's length means the rate
is past the knee). The cell's traffic file keeps the rate chosen from it.
"""

from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(run.ROOT, args.workload)
    dev, _, clock = run.start(cell)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic["rate_per_s"] = rate
        served = run.serve(cell, args.seed, args.seconds, False, dev, clock)
        w = served.window
        first = run.endtoend.first_token_times(w)
        late = sum(1 for r in w.due if r not in first or first[r] > w.t_close)
        print(json.dumps({
            "rate_per_s": rate, "due": len(w.due),
            "waiting_at_close": late,
            "output_tok_s": run.endtoend.output_tok_s(w),
            "ttft_p50_ms": run.endtoend.p50(run.endtoend.ttft_ms(w)),
            "ttft_p90_ms": run.endtoend.p90(run.endtoend.ttft_ms(w)),
            "tpot_p90_ms": run.endtoend.p90(run.endtoend.tpot_ms(w)),
            "steps": len(served.steps)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

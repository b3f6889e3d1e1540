"""Readings that a cell's correctness limits are set from, and the
control judged by them.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, in one process (the programs compile once): serve the cell
as ``run.py`` does, with a window of ``--seconds``, then run the
reference over the same sample of served requests together with the
control (the reference in float8, ``bench/reference/core.py``). Prints
one JSON line per seed: the program's widest and mean served-token gaps
(lower readings) and the control's at the same positions (upper
readings), each side judged by the workload file's committed limits as
``run.py`` judges a run. The last line sums the seeds up: per number,
the largest program reading, the smallest control reading and the limit,
and whether the program passed and the control failed on every seed.
The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(run.ROOT, args.workload)
    dev, _, clock = run.start(cell)
    limits = cell.workload["limits"]
    chunk = cell.workload["serving"]["chunk_size"]
    seeds = [int(s) for s in args.seeds.split(",")]
    program_ok, control_failed = [], []
    lower = {name: 0.0 for name in limits}
    upper = {name: float("inf") for name in limits}
    for seed in seeds:
        served = run.serve(cell, seed, args.seconds, False, dev, clock)
        picked = run.sample(served.outputs, served.longest, seed)
        gaps = run.logit_gaps(cell.config, seed, served.outputs, picked,
                              chunk=chunk, control=True)
        program = run.judge(limits, gaps)
        control = run.judge(limits, gaps, prefix="control_")
        program_ok.append(run.passes(program))
        control_failed.append(not run.passes(control))
        for name in limits:
            lower[name] = max(lower[name], program[name]["value"])
            upper[name] = min(upper[name], control[name]["value"])
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            **gaps,
            "correct": program_ok[-1],
            "control_correct": not control_failed[-1],
            "compared_tokens": sum(len(served.outputs[r][1])
                                   for r in picked),
            "output_tok_s": run.endtoend.output_tok_s(served.window),
            "window_tokens": served.window.tokens_inside(),
            "peak_bytes": served.peak_bytes,
            "compiles_in_window": served.compiles_in_window}), flush=True)
    print(json.dumps({
        "workload": cell.name, "seeds": len(seeds),
        "numbers": {name: {"lower": lower[name], "upper": upper[name],
                           "limit": limits[name]} for name in limits},
        "program_correct_on_every_seed": all(program_ok),
        "control_not_correct_on_every_seed": all(control_failed)}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The audit's two readings on the chip: the program's, and its control's.

    python3 bench/control.py --workload farm5.bulk --seconds 5 --seeds 11 12 13

For each seed, one run of the cell at its own size; the launches the
audit sampled are compared twice against the reference in the
configuration's precision: as the program served them, and as the
reference in the configuration's lower control precision (float8 for a
bfloat16 core) computes them from the same states, put in the program's
place.  The program has to pass and the control has to fail.  One JSON
line per seed.  The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import run as bench_run, serve, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    devs, peaks = bench_run.device_info(cell.chips, True)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    keys = ("kernel_word_mismatch", "state_mismatch_lanes",
            "delivered_word_mismatch", "delivered_words_compared",
            "launches_audited", "state_max_abs_diff", "unresolved_draws")
    for seed in args.seeds:
        res, checks, nums = bench_run.run(
            cell, seed, args.seconds, False, devs, peaks,
            control=cell.config["control_precision"])
        ctl = nums["control"]
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "program_correct": res["correct"],
            "control_correct": serve.passed(nums["control_checks"]),
            "program": {k: nums[k] for k in keys},
            "control": {k: ctl[k] for k in keys}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Sweep an open-loop cell's offered rate on the chip, once, to find the
highest rate the farm sustains without a growing backlog.

    python3 bench/sweep.py --workload farm5.zipf_open --seconds 5 --rates 5000 10000 20000

Each rate is one run of the cell in this process (the mix's ``rate_per_s``
replaced).  A backlog grows when the draws due in the window's last
quarter wait much longer than those due in its first quarter; the sweep
prints both medians, p50, p99 and the audit's verdict per rate.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path[:0] = [str(pathlib.Path(__file__).resolve().parents[1] / "src"),
                str(pathlib.Path(__file__).resolve().parents[1])]

from bench import run as bench_run, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=101)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    import numpy as np
    cell = spec.cell(args.workload)
    devs, peaks = bench_run.device_info(cell.chips, True)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    for i, rate in enumerate(args.rates):
        cell.mix["rate_per_s"] = rate
        res, checks, nums = bench_run.run(cell, args.seed + i, args.seconds,
                                          False, devs, peaks)
        lat, due = nums["latency_ms"], nums["due"]
        q = np.quantile(due, [0.25, 0.75])
        first = float(np.median(lat[due <= q[0]]))
        last = float(np.median(lat[due >= q[1]]))
        print(json.dumps({"rate_per_s": rate, "correct": res["correct"],
                          "draws": res["attempted"], "failed": res["failed"],
                          "p50_ms": float(np.percentile(lat, 50)),
                          "p99_ms": float(np.percentile(lat, 99)),
                          "first_quarter_p50_ms": first,
                          "last_quarter_p50_ms": last}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

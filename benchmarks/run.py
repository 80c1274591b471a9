"""Benchmark driver — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows (benchmarks.common.emit)."""
import argparse
import sys
import traceback


def main() -> None:
    import functools

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset (e.g. table1,fig5)")
    ap.add_argument("--profile", action="store_true",
                    help="dump per-stage flush wall times "
                         "(plan/stack/launch/absorb) for suites that "
                         "drive the serving path (farm)")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (farm, fig3_design_space, fig4_cost_curves,
                            fig5_pareto, table1_opcounts, table2_training,
                            table3_dse, throughput)
    suites = {
        "table1": table1_opcounts.run,
        "table2": table2_training.run,
        "table3": table3_dse.run,
        "fig3": fig3_design_space.run,
        "fig4": fig4_cost_curves.run,
        "fig5": fig5_pareto.run,
        "throughput": throughput.run,
        "throughput_fused": throughput.run_fused,
        "farm": functools.partial(farm.run_farm, profile=args.profile),
    }
    selected = args.only.split(",") if args.only else list(suites)
    print("name,us_per_call,derived")
    failures = []
    for name in selected:
        try:
            suites[name]()
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"FAILED suites: {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()

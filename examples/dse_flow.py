"""Design-space exploration flow across ANN sizes (paper Figs. 3 & 5):
sweep all candidate microarchitectures for 3-4-3 / 3-8-3 / 3-16-3, print the
Pareto fronts in both compute-unit modes, and emit generated cores for the
three paper-style user options.

Run:  PYTHONPATH=src python examples/dse_flow.py
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).parent.parent / "src"))

from repro.core.ann import AnnConfig, extract_parameters, train
from repro.core.chaotic import make_dataset
from repro.core.codegen import generate_core
from repro.core.dse import (CostModel, LatencyModel, enumerate_candidates,
                            pareto_front, select)


def main():
    lm, cm = LatencyModel.fit(), CostModel.fit()
    print("fitted Eq.8 coefficients (b3..b0) per (unit, dtype):")
    for k, v in lm.coeffs.items():
        print(f"  {k}: {[f'{c:.3e}' for c in v]}")

    for h in (4, 8, 16):
        print(f"\n=== 3-{h}-3 design space ===")
        for unit in ("mxu", "vpu"):
            cands = enumerate_candidates(3, h, units=(unit,))
            front = pareto_front(cands, lm, cm)
            label = {"mxu": "MXU (DSP analogue)", "vpu": "VPU (LUT analogue)"}[unit]
            print(f"  {label}: {len(cands)} candidates, "
                  f"front = {[(f'P{c.p}', f'{cost/1024:.0f}KiB', f'{lat:.3f}cyc') for c, cost, lat in front[:5]]}")

    print("\n=== generate the three user options for 3-8-3 ===")
    ds = make_dataset("chen", n_samples=30_000)
    params, _ = train(AnnConfig(hidden=8), ds, epochs=150, lr=3e-3)
    ex = extract_parameters(params)
    out = pathlib.Path("results/generated_cores")
    for mode, p in (("min_latency", None), ("lowest_cost", None), ("pareto", 2)):
        c = select(3, 8, mode, p=p, latency_model=lm, cost_model=cm)
        name = f"chen_383_{mode}" + (f"_p{p}" if p is not None else "")
        pkg = generate_core(name, out, params=ex, candidate=c,
                            scale=ds.scale, offset=ds.offset,
                            latency_model=lm, cost_model=cm)
        print(f"  {mode:12s} -> P={c.p} {c.compute_unit}/{c.dtype_name} "
              f"=> {pkg}")
    print("\ndse_flow complete.")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

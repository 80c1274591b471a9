"""HENNC quickstart — the paper's full flow in one script:

  1. software phase: generate Chen-system dataset (RK-4), train the 3-8-3
     ANN, report Table-II metrics;
  2. hardware phase: design-space exploration with the Eq.8/9 estimators,
     pick the three user options (min-latency / lowest-cost / Pareto-P);
  3. code generation: emit the selected core + testbench, run the testbench;
  4. use the core as a PRNG and run the NIST SP 800-22 subset.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import importlib
import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).parent.parent / "src"))

from repro.core.ann import AnnConfig, extract_parameters, train
from repro.core.chaotic import make_dataset
from repro.core.codegen import generate_core
from repro.core.dse import CostModel, LatencyModel, pareto_front, \
    enumerate_candidates, select
from repro.prng import run_nist_subset
from repro.prng.stream import ChaoticStream


def main():
    print("=== 1. software phase: train the oscillator ANN (Chen) ===")
    ds = make_dataset("chen", n_samples=50_000)
    cfg = AnnConfig(dim=3, hidden=8, activation="relu")
    params, hist = train(cfg, ds, epochs=200, lr=3e-3, verbose=True)
    m = hist["test_metrics"]
    print(f"  metrics: MSE={m['mse']:.2e} MAE={m['mae']:.4f} "
          f"RMSE={m['rmse']:.4f} R2={m['r2']:.6f}")
    print(f"  (paper Table II, ReLU: MSE=3.1e-4, R2=0.99999)")

    print("\n=== 2. hardware phase: design space exploration ===")
    lm, cm = LatencyModel.fit(), CostModel.fit()
    cands = enumerate_candidates(3, 8)
    front = pareto_front(cands, lm, cm)
    print(f"  {len(cands)} candidates, Pareto front:")
    for c, cost, lat in front:
        print(f"    P={c.p} {c.compute_unit}/{c.dtype_name}: "
              f"{cost / 1024:.0f} KiB VMEM, {lat:.4f} cyc/stream-sample")
    fast = select(3, 8, "min_latency", latency_model=lm, cost_model=cm)
    cheap = select(3, 8, "lowest_cost", latency_model=lm, cost_model=cm)
    print(f"  min-latency solution: {fast}")
    print(f"  lowest-cost solution: {cheap}")

    print("\n=== 3. generate the hardware core + run its testbench ===")
    out = pathlib.Path("results/generated_cores")
    pkg = generate_core("chen_383_quickstart", out,
                        params=extract_parameters(params), candidate=fast,
                        scale=ds.scale, offset=ds.offset,
                        latency_model=lm, cost_model=cm)
    print(f"  emitted {pkg}")
    # in this process: a second process could not reach a chip this one holds
    sys.path.insert(0, str(out))
    testbench = importlib.import_module(f"{pkg.name}.testbench")
    assert testbench.run(), "testbench failed"

    print("\n=== 4. PRNG: NIST SP 800-22 subset on emitted words ===")
    stream = ChaoticStream.from_trained(extract_parameters(params))
    words = np.asarray(stream.bits(40_000))
    for name, res in run_nist_subset(words).items():
        print(f"  {name:22s} p={res['p_value']:.4f} "
              f"{'PASS' if res['passed'] else 'FAIL'}")
    print("\nquickstart complete.")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

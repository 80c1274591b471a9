"""Heterogeneous oscillator farm demo — the full multi-system flow:

  1. train one ANN oscillator per chaotic system (registry-cached, so the
     committed weights under results/weights/ make this instant);
  2. DSE-select a solution per system and emit a core per system
     (``generate_farm``) — including the 4-D hyperchaotic Lorenz;
  3. serve all cores behind one ``OscillatorFarm``: per-core routing,
     with compatible cores GANG-SCHEDULED into one stacked-weight launch
     per flush (the four 3-D cores share a launch; the 4-D hyperchaotic
     core launches alone);
  4. verify farm transparency (standalone service == farmed service) and
     farm-wide snapshot/restore with requests in flight.

Run:  PYTHONPATH=src python examples/farm_demo.py
"""
import json
import pathlib
import sys
import tempfile

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).parent.parent / "src"))

import jax.numpy as jnp  # noqa: E402

from repro.core.codegen import generate_farm  # noqa: E402
from repro.core.dse import Candidate  # noqa: E402
from repro.serve.farm import OscillatorFarm  # noqa: E402
from repro.serve.prng_service import PRNGService  # noqa: E402


def main():
    out = pathlib.Path(tempfile.mkdtemp(prefix="hennc_farm_"))
    print("=== 1+2. train + DSE + codegen, one core per system ===")
    cores = generate_farm(out, mode="pareto", p=1)
    for name, pkg in cores.items():
        sol = json.loads((pkg / "solution.json").read_text())
        c = sol["candidate"]
        print(f"  {name:12s} I={c['i_dim']} H={c['h_dim']} "
              f"P={c['p']} {c['compute_unit']}/"
              f"{'bf16' if c['dtype_bytes'] == 2 else 'f32'} "
              f"t_block={c['t_block']} unroll={c['unroll']}")

    print("\n=== 3. one farm, gang-scheduled launches ===")
    farm = OscillatorFarm.from_generated(out)
    for core in farm.cores:
        farm.register(core, "alice", seed=11)
        farm.register(core, "bob", seed=22)
    for core in farm.cores:
        farm.request(core, "alice", 1000)
        farm.request(core, "bob", 500)
    served = farm.flush()
    # one stacked launch for the compatible 3-D group + one solo launch
    # for the incompatible 4-D core — not one launch per core
    assert farm.launches == 2, farm.launches
    assert farm.gang_launches == 1
    print(f"  {len(farm.cores)} cores served in {farm.launches} launches "
          f"({farm.gang_launches} gang)")
    for core in sorted(served):
        w = served[core]["alice"]
        ones = np.unpackbits(w.view(np.uint8)).mean()
        print(f"  {core:12s} alice={w.size} bob={served[core]['bob'].size} "
              f"words, monobit={ones:.4f}, head={w[:3]}")

    print("\n=== 4a. farm transparency: standalone == farmed ===")
    sol = json.loads((cores["hyperlorenz"] / "solution.json").read_text())
    cand = Candidate(**sol["candidate"])
    params = dict(np.load(cores["hyperlorenz"] / "weights.npz"))
    solo = PRNGService(params, lanes_per_client=128, config=cand,
                       dtype=jnp.dtype(cand.dtype_name))
    solo.register("alice", seed=11)
    assert np.array_equal(solo.draw("alice", 1000),
                          served["hyperlorenz"]["alice"]), "transparency broken!"
    print("  hyperlorenz/alice: bit-identical standalone vs farmed")

    print("\n=== 4b. snapshot with requests in flight ===")
    farm.request("chen", "bob", 750)            # queued, not yet flushed
    snap = farm.snapshot()
    a = farm.flush()["chen"]["bob"]
    farm2 = OscillatorFarm.from_generated(out)
    farm2.restore(snap)
    b = farm2.flush()["chen"]["bob"]
    assert np.array_equal(a, b), "pending draw lost across snapshot!"
    print(f"  chen/bob: {a.size} queued words survived snapshot/restore")

    print("\n=== 5. demand-shaped gang planning ===")
    # one hot tenant, everyone else cold: the planner shapes the launch to
    # demand (ragged row maps / a split) instead of padding the whole gang
    # to the hot tenant's row count.
    hot, *cold = farm.cores
    farm.request(hot, "alice", 64 * 128)
    for core in cold:
        farm.request(core, "alice", 512)
    farm.flush()
    print(f"  skewed flush decisions so far: {farm.plan_decisions}")

    print(f"\n{len(farm.cores)} cores ({sum(1 for _ in farm.cores)} systems, "
          f"incl. one 4-D hyperchaotic), {farm.launches} launches total "
          f"({farm.gang_launches} gang-scheduled).")
    print("farm demo complete.")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

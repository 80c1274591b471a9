"""Hardware-core generation (paper §III-B.3).

The paper emits two C++ files per selected solution: the synthesizable HLS
core and a co-simulation testbench.  The TPU analogue emits two Python files
per selected solution:

  <name>/core.py       frozen kernel instantiation: the chosen Candidate's
                       block shapes/unroll/compute-unit baked in as constants,
                       weights loaded from the bundled weights.npz
  <name>/testbench.py  validation: kernel vs pure-jnp oracle (co-simulation),
                       one-step fidelity vs the RK-4 oracle, and a monobit
                       randomness check of the emitted PRNG words
  <name>/weights.npz   extracted network parameters (paper §III-A hand-off)
  <name>/solution.json the selected design point + estimated metrics

Generated modules are self-contained (import only jax + repro) and are
exercised by tests/test_codegen.py exactly as Vitis co-simulation would be.
The emitted ``generate_bits`` draws through the fused ``ops.chaotic_bits``
launch (word_offset + final state), so a generated core is bit-compatible
with the serving stack; ``generate_farm`` emits one such core per chaotic
system for ``repro.serve.farm.OscillatorFarm``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
from typing import Dict

import numpy as np

from repro.atomicio import atomic_write_text
from repro.core.dse import (Candidate, CostModel, LatencyModel,
                            measure_candidate, select)

_CORE_TEMPLATE = '''"""Auto-generated by repro.core.codegen — do not edit.

HENNC chaotic oscillator core: {system} {i_dim}-{h_dim}-{i_dim} ANN,
solution P={p} ({compute_unit}, {dtype_name}), s_block={s_block},
t_block={t_block}, unroll={unroll}.
"""
import pathlib

import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

_HERE = pathlib.Path(__file__).parent
_W = dict(np.load(_HERE / "weights.npz"))

SYSTEM = "{system}"
I_DIM, H_DIM = {i_dim}, {h_dim}
N_NODES = {n_nodes}
S_BLOCK, T_BLOCK, UNROLL = {s_block}, {t_block}, {unroll}
COMPUTE_UNIT, DTYPE = "{compute_unit}", jnp.{dtype_name}
ACTIVATION = "{activation}"
SCALE = np.asarray({scale!r}, dtype=np.float32)
OFFSET = np.asarray({offset!r}, dtype=np.float32)


def params():
    return {{k: jnp.asarray(v) for k, v in _W.items()}}


def generate(x0, n_steps, *, interpret=None):
    """Run the frozen oscillator core. x0: (S, I) normalized seeds.

    Routed through ``ops.chaotic_trajectory`` so a lattice core (weights
    carrying ``lattice_meta``/``coupling``) dispatches its block-coupled
    kernel path with no change to the generated surface.
    """
    if interpret is None:
        interpret = ops.resolve_backend() == "pallas_interpret"
    backend = "pallas_interpret" if interpret else "pallas"
    return ops.chaotic_trajectory(
        params(), jnp.asarray(x0, DTYPE), n_steps, activation=ACTIVATION,
        backend=backend, s_block=S_BLOCK, t_block=T_BLOCK, unroll=UNROLL,
        compute_unit=COMPUTE_UNIT)


def generate_bits(x0, n_steps, word_offset=0, *, backend="auto"):
    """Fused, resumable PRNG draw: ((n_steps // 2, S) uint32 words,
    (S, I) final state).

    Draws through the same fused ``ops.chaotic_bits`` launch that
    ``repro.serve`` batches clients onto (the float trajectory never
    leaves VMEM); ``word_offset`` + the returned state resume the exact
    word sequence of one long draw, chunk-size invariantly.
    """
    return ops.chaotic_bits(
        params(), jnp.asarray(x0, DTYPE), n_steps, word_offset,
        activation=ACTIVATION, backend=backend, s_block=S_BLOCK,
        t_block=T_BLOCK, unroll=UNROLL, compute_unit=COMPUTE_UNIT)
'''

_TESTBENCH_TEMPLATE = '''"""Auto-generated validation testbench (co-simulation analogue)."""
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).parent.parent))
import {name} as core  # noqa: E402


def run(verbose=True):
    from repro.kernels import ops
    from repro.core.ann import one_step_reference  # noqa: F401
    p = core.params()
    key = jax.random.PRNGKey(0)
    x0 = (jax.random.uniform(key, (core.S_BLOCK, core.I_DIM),
                             dtype=jnp.float32) - 0.5).astype(core.DTYPE)

    # 1) kernel vs oracle, short horizon (pre-divergence window; bf16's
    # ~8e-3 rounding is amplified ~2x/step by the chaotic map, so the
    # comparable window is shorter than f32's).  The ref backend routes
    # scalar cores to the independent x @ w oracle and lattice cores to
    # the bitwise-exact block-coupled oracle — one testbench for both.
    T = 3 if core.DTYPE == jnp.bfloat16 else 8
    got = core.generate(x0, T)
    want = ops.chaotic_trajectory(p, x0, T, activation=core.ACTIVATION,
                                  backend="ref",
                                  compute_unit=core.COMPUTE_UNIT)
    tol = 1.5e-1 if core.DTYPE == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)

    # 2) bounded trajectories over a long horizon (oscillator is stable)
    long = core.generate(x0, 512)
    assert bool(jnp.all(jnp.isfinite(long))), "trajectory diverged"
    assert float(jnp.max(jnp.abs(long))) < 10.0, "trajectory left attractor box"

    # 3) fused PRNG words are resumable: two chunked draws (state +
    # word_offset threaded through) equal one long draw, bit for bit
    words, _ = core.generate_bits(x0, 2048)
    w_a, mid = core.generate_bits(x0, 1024)
    w_b, _ = core.generate_bits(mid, 1024, word_offset=512)
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(w_a), np.asarray(w_b)], axis=0),
        np.asarray(words))

    # 4) monobit randomness of emitted words
    ones = int(np.unpackbits(np.asarray(words).view(np.uint8)).sum())
    total = words.size * 32
    frac = ones / total
    assert abs(frac - 0.5) < 0.01, f"monobit bias {{frac}}"
    if verbose:
        print(f"TESTBENCH PASS: maxerr(T={{T}})="
              f"{{float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))):.3g}}"
              f" monobit={{frac:.4f}} resumable=yes")
    return True


if __name__ == "__main__":
    run()
'''


def generate_core(name: str, out_dir: str | pathlib.Path, *,
                  params: Dict[str, np.ndarray], candidate: Candidate,
                  system: str = "chen", activation: str = "relu",
                  scale=None, offset=None,
                  latency_model: LatencyModel | None = None,
                  cost_model: CostModel | None = None) -> pathlib.Path:
    """Emit the selected solution as an importable package. Returns its path."""
    out = pathlib.Path(out_dir) / name
    out.mkdir(parents=True, exist_ok=True)

    # Atomic publish, same discipline as the weight registry: a core being
    # re-generated may be imported by a concurrent serving process, which
    # must see the previous complete file or the new one, never a torn mix.
    tmp = out / f".weights.tmp{os.getpid()}.npz"
    np.savez(tmp, **{k: np.asarray(v, np.float32) for k, v in params.items()})
    os.replace(tmp, out / "weights.npz")

    scale = [1.0] * candidate.i_dim if scale is None else list(np.asarray(scale, np.float64))
    offset = [0.0] * candidate.i_dim if offset is None else list(np.asarray(offset, np.float64))

    atomic_write_text(out / "__init__.py", _CORE_TEMPLATE.format(
        system=system, i_dim=candidate.i_dim, h_dim=candidate.h_dim,
        p=candidate.p, compute_unit=candidate.compute_unit,
        dtype_name=candidate.dtype_name, s_block=candidate.s_block,
        t_block=candidate.t_block, unroll=candidate.unroll,
        n_nodes=candidate.n_nodes,
        activation=activation, scale=scale, offset=offset))

    atomic_write_text(out / "testbench.py",
                      _TESTBENCH_TEMPLATE.format(name=name))

    lm = latency_model or LatencyModel.fit()
    cm = cost_model or CostModel.fit()
    meas = measure_candidate(candidate)
    atomic_write_text(out / "solution.json", json.dumps({
        "candidate": dataclasses.asdict(candidate),
        "estimated": {
            "latency_per_stream_cycles": lm.predict(
                candidate.i_dim, candidate.h_dim, candidate.p,
                candidate.compute_unit, candidate.dtype_bytes),
            "vmem_bytes": cm.predict(
                candidate.i_dim, candidate.h_dim, candidate.p,
                candidate.compute_unit, candidate.dtype_bytes),
        },
        "measured_model": meas,
        "system": system,
        "activation": activation,
    }, indent=2))
    return out


def generate_farm(out_dir: str | pathlib.Path, systems=None, *,
                  mode: str = "pareto", p: int | None = None,
                  overrides: Dict[str, Dict] | None = None,
                  latency_model: LatencyModel | None = None,
                  cost_model: CostModel | None = None
                  ) -> Dict[str, pathlib.Path]:
    """Emit one core per chaotic system: the heterogeneous oscillator farm.

    For each system the flow is the paper's, end to end: trained weights
    come from the registry (``repro.prng.stream.trained_oscillator``, disk
    cached), the solution is DSE-selected for that system's (I, H) — so a
    4-D system genuinely explores a different design space than the 3-D
    ones — and ``generate_core`` freezes it into an importable package
    named after the system.  The emitted cores are what
    ``repro.serve.farm.OscillatorFarm.from_generated`` serves.

    Args:
      systems: iterable of system names; defaults to every registered
        system.  Lattice specs (``"chen@ring8"``) are accepted: the
        registry derives the block-expanded bundle, DSE selects with the
        lattice cost arms (``n_nodes`` threaded through), and the package
        is emitted under the identifier-safe name ``chen_ring8`` (its
        weights carry ``coupling``/``lattice_meta``, so the generated
        module and the farm dispatch the block-coupled kernel path).
      mode/p: the ``select`` user option applied per system.
      overrides: per-system {"mode": ..., "p": ...} replacing the defaults,
        so one farm can mix user options (e.g. a min-latency Chen next to a
        lowest-cost Rössler).
    Returns: {system name: generated package path}.
    """
    from repro.core.ann import lattice_meta_tuple
    from repro.core.chaotic import SYSTEMS
    from repro.prng.stream import trained_oscillator

    systems = tuple(SYSTEMS) if systems is None else tuple(systems)
    lm = latency_model or LatencyModel.fit()
    cm = cost_model or CostModel.fit()
    cores: Dict[str, pathlib.Path] = {}
    for name in systems:
        bundle = trained_oscillator(name)
        params = {k: bundle[k]
                  for k in ("w1", "b1", "w2", "b2", "coupling",
                            "lattice_meta") if k in bundle}
        i_dim, h_dim = params["w1"].shape
        n_nodes = 1
        if "lattice_meta" in params:
            n_nodes = lattice_meta_tuple(np.asarray(params["lattice_meta"]))[0]
        o = (overrides or {}).get(name, {})
        cand = select(i_dim, h_dim, o.get("mode", mode), p=o.get("p", p),
                      latency_model=lm, cost_model=cm, n_nodes=n_nodes)
        cores[name] = generate_core(
            name.replace("@", "_"), out_dir, params=params, candidate=cand,
            system=name, scale=bundle["scale"], offset=bundle["offset"],
            latency_model=lm, cost_model=cm)
    return cores

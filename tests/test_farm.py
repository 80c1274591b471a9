"""Heterogeneous oscillator farm: train -> DSE -> codegen -> serve.

Covers the farm acceptance surface: ``generate_farm`` emits a runnable
core per system (testbenches pass, including the 4-D hyperchaotic one),
generated cores draw through the fused ``ops.chaotic_bits`` path
bit-identically to the serving stack, and ``OscillatorFarm`` routing is
transparent (a client's words are identical standalone vs farmed).
"""
import json
import os
import pathlib
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.chaotic import SYSTEMS, get_system
from repro.core.dse import Candidate
from repro.prng.stream import _lineage_counter, _round_rows, _splitmix_seeds
from repro.serve.farm import OscillatorFarm
from repro.serve.prng_service import PRNGService

FARM_SYSTEMS = ("chen", "lorenz", "rossler", "chua", "hyperlorenz")


@pytest.fixture(scope="module")
def farm_dir(tmp_path_factory):
    """One farm generation shared by every test in this module (P=1)."""
    from repro.core.codegen import generate_farm
    out = tmp_path_factory.mktemp("farm")
    cores = generate_farm(out, systems=FARM_SYSTEMS, mode="pareto", p=1)
    assert set(cores) == set(FARM_SYSTEMS)
    return out


def _load_solution(farm_dir, name):
    sol = json.loads((farm_dir / name / "solution.json").read_text())
    return Candidate(**sol["candidate"]), dict(np.load(farm_dir / name / "weights.npz"))


def test_farm_emits_one_core_per_system(farm_dir):
    assert len(FARM_SYSTEMS) >= 4
    for name in FARM_SYSTEMS:
        pkg = farm_dir / name
        for f in ("__init__.py", "testbench.py", "weights.npz", "solution.json"):
            assert (pkg / f).exists(), (name, f)
        cand, params = _load_solution(farm_dir, name)
        dim = get_system(name).dim
        assert cand.i_dim == dim
        assert params["w1"].shape[0] == dim
    # the farm genuinely contains an I=4 design point
    assert _load_solution(farm_dir, "hyperlorenz")[0].i_dim == 4


@pytest.mark.parametrize("name", FARM_SYSTEMS)
def test_farm_testbenches_pass(farm_dir, name):
    """Every emitted core's co-simulation testbench passes stand-alone."""
    env = dict(os.environ)
    env["PYTHONPATH"] = f"src:{farm_dir}:" + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, str(farm_dir / name / "testbench.py")],
                       capture_output=True, text=True, env=env, timeout=560)
    assert r.returncode == 0, (name, r.stderr[-2000:])
    assert "TESTBENCH PASS" in r.stdout


def test_generated_core_bits_match_service(farm_dir):
    """A generated core's fused draw reproduces the serving stack bit for
    bit: seeding, burn-in, and word emission all go through the same
    ``ops.chaotic_bits`` launch."""
    sys.path.insert(0, str(farm_dir))
    try:
        import hyperlorenz as core
        cand, params = _load_solution(farm_dir, "hyperlorenz")
        L, seed, n_words = 128, 77, 700
        svc = PRNGService(params, lanes_per_client=L,
                          backend="pallas_interpret", config=cand,
                          dtype=jnp.dtype(cand.dtype_name))
        svc.register("alice", seed=seed)
        served = svc.draw("alice", n_words)

        # replay through the generated core: same splitmix seeding, same
        # dedicated burn-in launch, same offset-threaded fused draw
        x = _splitmix_seeds(jnp.asarray(_lineage_counter(seed, ()), jnp.uint32),
                            L, core.I_DIM).astype(core.DTYPE)
        _, x = core.generate_bits(x, svc.burn_in, 0, backend="pallas_interpret")
        n_rows = _round_rows(-(-n_words // L), cand.t_block)
        words, _ = core.generate_bits(x, 2 * n_rows, 0,
                                      backend="pallas_interpret")
        np.testing.assert_array_equal(
            np.asarray(words).reshape(-1)[:n_words], served)
    finally:
        sys.path.remove(str(farm_dir))


@pytest.mark.parametrize("name", ["chen", "hyperlorenz"])
def test_farm_client_matches_standalone_service(farm_dir, name):
    """Per system: identical words served standalone vs through the farm."""
    farm = OscillatorFarm.from_generated(farm_dir,
                                         backend="pallas_interpret")
    assert set(farm.cores) == set(FARM_SYSTEMS)
    for core in farm.cores:
        farm.register(core, "alice", seed=5)
    farm.request(name, "alice", 650)
    out = farm.flush()
    assert set(out) == {name}                     # only the active core served

    cand, params = _load_solution(farm_dir, name)
    solo = PRNGService(params, lanes_per_client=128,
                       backend="pallas_interpret", config=cand,
                       dtype=jnp.dtype(cand.dtype_name))
    solo.register("alice", seed=5)
    np.testing.assert_array_equal(out[name]["alice"], solo.draw("alice", 650))


def test_farm_routing_and_errors(farm_dir):
    farm = OscillatorFarm.from_generated(farm_dir, cores=("chen", "lorenz"),
                                         backend="pallas_interpret")
    farm.register("chen", "a", seed=1)
    farm.register("lorenz", "a", seed=1)          # same name, distinct cores
    wa = farm.draw("chen", "a", 300)
    wb = farm.draw("lorenz", "a", 300)
    assert not np.array_equal(wa, wb)             # different oscillators
    with pytest.raises(KeyError):
        farm.draw("ghost_core", "a", 10)
    with pytest.raises(ValueError):
        farm.add_core("chen", _load_solution(farm_dir, "chen")[1])
    with pytest.raises(ValueError):
        # config/dtype/activation are frozen in solution.json
        OscillatorFarm.from_generated(farm_dir, activation="tanh")


def test_farm_snapshot_restore_with_pending(farm_dir):
    """Farm-wide snapshot between request() and flush() keeps the queued
    draws (the service-level `pending` persistence, end to end)."""
    mk = lambda: OscillatorFarm.from_generated(
        farm_dir, cores=("chen", "hyperlorenz"), backend="pallas_interpret")
    farm = mk()
    for core in farm.cores:
        farm.register(core, "c", seed=3)
    farm.draw("chen", "c", 130)
    farm.request("chen", "c", 200)                # in flight at snapshot time
    farm.request("hyperlorenz", "c", 90)
    snap = farm.snapshot()
    a = farm.flush()

    farm2 = mk()
    farm2.restore(snap)
    b = farm2.flush()
    assert set(a) == set(b) == {"chen", "hyperlorenz"}
    for core in a:
        np.testing.assert_array_equal(a[core]["c"], b[core]["c"])
    with pytest.raises(ValueError):
        OscillatorFarm().restore(snap)            # cores must be attached
    extra = OscillatorFarm.from_generated(
        farm_dir, cores=("chen", "hyperlorenz", "lorenz"),
        backend="pallas_interpret")
    with pytest.raises(ValueError):
        extra.restore(snap)                       # ...and none beyond them


def test_committed_bf16_farm_stacked_equals_solo():
    """The committed bf16 3-8 cores at their served blocking (t_block
    256, unroll 8) ride one stacked gang launch, padded and ragged; every
    tenant's words equal the per-core path's, bit for bit."""
    committed = (pathlib.Path(__file__).resolve().parents[1]
                 / "results" / "generated_cores" / "farm")
    cores = ("chen", "chua", "lorenz", "rossler")
    farms = {gang: OscillatorFarm.from_generated(
        committed, cores=cores, gang=gang, backend="pallas_interpret")
        for gang in (True, False)}
    for farm in farms.values():
        for ci, core in enumerate(cores):
            for t in range(2):
                farm.register(core, f"t{t}", seed=10 * ci + t)
    out = {}
    for gang, farm in farms.items():
        for rows in ((32, 32, 32, 32), (32, 8, 16, 8)):
            for core, n in zip(cores, rows):
                for t in range(2):
                    farm.request(core, f"t{t}", 128 * n)
            res = farm.flush()
            for core in cores:
                for t in range(2):
                    out.setdefault((gang, core, t), []).append(
                        res[core][f"t{t}"])
    assert farms[True].layout_launches["stacked"] >= 1
    for core in cores:
        for t in range(2):
            for a, b in zip(out[(True, core, t)], out[(False, core, t)]):
                np.testing.assert_array_equal(a, b)

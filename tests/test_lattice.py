"""Block-coupled oscillator lattices, end to end.

The lattice is the stack's escape from quadratic hardware scaling
(ROADMAP "Coupled-oscillator lattices"): N copies of a base chaotic
system coupled diffusively on a ring/torus, state dim N * d, Jacobian
block-sparse — never a dense N^2 operator.  These tests pin the whole
route: the ODE-level coupling structure, the block-diagonal parameter
expansion, bitwise ref-vs-Pallas identity for BOTH compute units, fork
non-overlap and gang bit-identity at lattice dims, the stacked-layout
VMEM cliff (planner falls back to lane-concat past it), registry-derived
lattice bundles, farm serving next to scalar cores — plus the burn-in
parity identity fixes that rode along in this change.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ann import expand_lattice_params, lattice_meta_tuple
from repro.core.chaotic import (DEFAULT_LATTICE_COUPLING, get_system,
                                lattice, lattice_coupling_matrix,
                                parse_lattice_name)
from repro.core.dse import (VMEM_USABLE, Candidate, select_config,
                            stacked_gang_vmem_bytes)
from repro.kernels import ops

from test_kernels import _mk

N, D, H = 8, 3, 8                 # 8-node chen-shaped ring: I = 24, H = 64
I_LAT, H_LAT = N * D, N * H

# Small-block config keeping interpret-mode kernel bodies cheap to compile
# (trace cost grows ~quadratically with t_block * (I + H) unrolled ops).
CFG = Candidate(i_dim=I_LAT, h_dim=H_LAT, p=0, compute_unit="vpu",
                dtype_bytes=4, t_block=8, unroll=2)
CFG_MXU = Candidate(i_dim=I_LAT, h_dim=H_LAT, p=0, compute_unit="mxu",
                    dtype_bytes=4, t_block=8, unroll=2)


def _base_params(key=0):
    w1, b1, w2, b2, _ = _mk(D, H, 1, key=key)
    return {"w1": w1, "b1": b1, "w2": w2, "b2": b2}


def _lat_params(n_nodes=N, topology="ring", key=0,
                coupling=DEFAULT_LATTICE_COUPLING):
    return expand_lattice_params(_base_params(key), n_nodes=n_nodes,
                                 coupling=coupling, topology=topology)


def _f32(a):
    return np.asarray(jnp.asarray(a, jnp.float32))


# ---------------------------------------------------------------------------
# ODE level: coupling structure
# ---------------------------------------------------------------------------

def test_lattice_coupling_matrix_is_block_sparse_laplacian():
    """C = strength * (A - deg I) (x) I_d: zero row sums (diffusive — a
    synchronized lattice feels no coupling force), symmetric for the ring,
    and only diagonal + nearest-neighbour d x d blocks are nonzero."""
    n, d, s = 6, 3, 0.07
    C = lattice_coupling_matrix(n, d, s)
    assert C.shape == (n * d, n * d)
    np.testing.assert_allclose(C.sum(axis=1), 0.0, atol=1e-6)
    np.testing.assert_allclose(C, C.T, atol=1e-7)
    for a in range(n):
        for b in range(n):
            blk = C[a * d:(a + 1) * d, b * d:(b + 1) * d]
            ring_dist = min((a - b) % n, (b - a) % n)
            if ring_dist == 0:
                np.testing.assert_allclose(blk, -2 * s * np.eye(d),
                                           atol=1e-7)
            elif ring_dist == 1:
                np.testing.assert_allclose(blk, s * np.eye(d), atol=1e-7)
            else:
                assert not blk.any(), f"non-neighbour block ({a},{b}) nonzero"


def test_lattice_ode_is_base_dynamics_plus_coupling():
    sys_ = lattice("chen", 4, coupling=0.05)
    base = get_system("chen")
    assert sys_.dim == 12 and sys_.name == "chen@ring4"
    x = jnp.asarray(np.random.default_rng(0).uniform(-1, 1, 12), jnp.float32)
    C = lattice_coupling_matrix(4, 3, 0.05)
    dyn = jnp.concatenate([base.f(x[i * 3:(i + 1) * 3]) for i in range(4)])
    want = np.asarray(dyn) + C @ np.asarray(x)
    np.testing.assert_allclose(np.asarray(sys_.f(x)), want, rtol=1e-5,
                               atol=1e-5)
    # block-sparse op counts: O(n_nodes), never N^2
    assert sys_.n_mul_dynamic == 4 * base.n_mul_dynamic + 12
    assert sys_.n_add_dynamic == 4 * base.n_add_dynamic + 12 * 2


def test_parse_lattice_name_and_topology_routing():
    assert parse_lattice_name("chen@grid9") == ("chen", "grid", 9)
    assert get_system("chen@ring8").dim == 24
    for bad in ("chen@spiral4", "chen@ring", "chen@4"):
        with pytest.raises(KeyError):
            parse_lattice_name(bad)
    # grid names must build grids (regression: topology was once dropped)
    ring = lattice_coupling_matrix(4, 3, 0.05, "ring")
    grid = lattice_coupling_matrix(4, 3, 0.05, "grid")
    assert not np.array_equal(ring, grid)
    np.testing.assert_allclose(grid.sum(axis=1), 0.0, atol=1e-6)


# ---------------------------------------------------------------------------
# Parameter expansion
# ---------------------------------------------------------------------------

def test_expand_lattice_params_block_diagonal():
    base = _base_params()
    p = _lat_params()
    w1 = np.asarray(p["w1"])
    assert w1.shape == (I_LAT, H_LAT)
    for a in range(N):
        for b in range(N):
            blk = w1[a * D:(a + 1) * D, b * H:(b + 1) * H]
            if a == b:
                np.testing.assert_array_equal(blk, np.asarray(base["w1"]))
            else:
                assert not blk.any()
    np.testing.assert_array_equal(np.asarray(p["b1"]),
                                  np.tile(np.asarray(base["b1"]), N))
    np.testing.assert_array_equal(
        np.asarray(p["coupling"]),
        lattice_coupling_matrix(N, D, DEFAULT_LATTICE_COUPLING))
    got_meta = lattice_meta_tuple(p["lattice_meta"])
    assert got_meta[:3] == (N, D, "ring")
    assert got_meta[3] == pytest.approx(DEFAULT_LATTICE_COUPLING)


def test_expand_lattice_params_validation():
    base = _base_params()
    with pytest.raises(ValueError, match="n_nodes"):
        expand_lattice_params(base, n_nodes=1, coupling=0.05)
    with pytest.raises(ValueError, match="8"):
        # 3 nodes x 3 dims = 9 state rows: not sublane-packable
        expand_lattice_params(base, n_nodes=3, coupling=0.05)


# ---------------------------------------------------------------------------
# Kernel level: ref-vs-Pallas bit-identity, both units, both dtypes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("cfg", [CFG, CFG_MXU], ids=["vpu", "mxu"])
def test_lattice_ref_vs_pallas_bit_identical(dtype, cfg):
    """The lattice oracle scans the kernels' own step closure, so
    ref == Pallas is EXACT for both compute units (not to ulps)."""
    params = _lat_params()
    x0 = _mk(I_LAT, H_LAT, 128, key=5)[4].astype(dtype)
    got = ops.chaotic_trajectory(params, x0, 64,
                                 backend="pallas_interpret", config=cfg)
    want = ops.chaotic_trajectory(params, x0, 64, backend="ref", config=cfg)
    np.testing.assert_array_equal(_f32(got), _f32(want))
    # fused words ride the same trajectory: ref packing == fused kernel
    gw, gs = ops.chaotic_bits(params, x0, 64, backend="pallas_interpret",
                              config=cfg)
    ww, ws = ops.chaotic_bits(params, x0, 64, backend="ref", config=cfg)
    np.testing.assert_array_equal(np.asarray(gw), np.asarray(ww))
    np.testing.assert_array_equal(_f32(gs), _f32(ws))


def test_lattice_grid_topology_bit_identical_and_distinct():
    params_g = _lat_params(n_nodes=8, topology="grid")
    x0 = _mk(I_LAT, H_LAT, 128, key=7)[4]
    got = ops.chaotic_trajectory(params_g, x0, 32,
                                 backend="pallas_interpret", config=CFG)
    want = ops.chaotic_trajectory(params_g, x0, 32, backend="ref",
                                  config=CFG)
    np.testing.assert_array_equal(_f32(got), _f32(want))
    ring = ops.chaotic_trajectory(_lat_params(), x0, 32,
                                  backend="pallas_interpret", config=CFG)
    assert not np.array_equal(_f32(got), _f32(ring))


def test_lattice_mxu_requires_coupling_operand():
    params = _lat_params()
    bare = {k: params[k] for k in ("w1", "b1", "w2", "b2")}
    bare["lattice_meta"] = params["lattice_meta"]
    x0 = _mk(I_LAT, H_LAT, 128, key=3)[4]
    with pytest.raises(KeyError):
        ops.chaotic_trajectory(bare, x0, 32, backend="pallas_interpret",
                               config=CFG_MXU)


# ---------------------------------------------------------------------------
# Stream level: fork non-overlap at lattice dims
# ---------------------------------------------------------------------------

def test_lattice_fork_children_non_overlapping():
    from repro.prng.stream import ChaoticPRNG
    eng = ChaoticPRNG(_lat_params(), n_streams=128, burn_in=16,
                      backend="pallas_interpret", config=CFG)
    assert eng.config.compute_unit == "vpu"
    root = eng.init(seed=1)
    kids = eng.fork(root, 3)
    words = [eng.next_words(k, 2048)[0] for k in kids]
    for a in range(3):
        for b in range(a + 1, 3):
            assert not np.array_equal(words[a], words[b])
            # positionally, independent uniform words agree w.p. 2^-32
            assert np.mean(words[a] == words[b]) < 0.01
    # forking never consumed the parent: its words are fork-invariant
    w_parent, _ = eng.next_words(root, 256)
    w_again, _ = eng.next_words(eng.init(seed=1), 256)
    np.testing.assert_array_equal(w_parent, w_again)


def test_lattice_engine_autoselects_with_n_nodes():
    """Engine-level select_config must see the lattice: the candidate is
    lattice-aware (n_nodes threaded), not a scalar-core config."""
    from repro.prng.stream import ChaoticPRNG
    eng = ChaoticPRNG(_lat_params(), n_streams=128,
                      backend="pallas_interpret")
    assert eng.config.n_nodes == N
    assert eng.config.i_dim == I_LAT


# ---------------------------------------------------------------------------
# Gang level: >= 24-member bit-identity, both layouts
# ---------------------------------------------------------------------------

def test_lattice_stacked_gang_24_members_bit_identical():
    """One sublane-stacked launch of 24 lattice cores (shared coupling
    operand semantics, distinct per-core weights) == 24 solo lattice
    launches, words AND final states, with per-lane word offsets."""
    C, S, n_steps = 24, 128, 64
    plist = [_lat_params(key=k) for k in range(C)]
    gang = {k: jnp.stack([jnp.asarray(p[k]) for p in plist])
            for k in ("w1", "b1", "w2", "b2")}
    gang["coupling"] = jnp.asarray(plist[0]["coupling"])
    gang["lattice_meta"] = jnp.asarray(plist[0]["lattice_meta"])
    x0 = _mk(I_LAT, H_LAT, C * S, key=9)[4].reshape(C, S, I_LAT)
    offs = np.random.default_rng(3).integers(
        0, 10_000, size=(C, S)).astype(np.uint32)
    gw, gs = ops.chaotic_bits_gang_stacked(
        gang, x0, n_steps, jnp.asarray(offs),
        backend="pallas_interpret", config=CFG)
    gw, gs = np.asarray(gw), _f32(gs)
    for ci in range(C):
        w, s = ops.chaotic_bits(plist[ci], x0[ci], n_steps,
                                jnp.asarray(offs[ci]),
                                backend="pallas_interpret", config=CFG)
        np.testing.assert_array_equal(gw[:, ci, :], np.asarray(w))
        np.testing.assert_array_equal(gs[ci], _f32(s))


def test_lattice_concat_gang_mxu_bit_identical():
    """The lane-concat gang on the mxu path shares ONE (I, I) coupling
    operand across the group; words must equal solo mxu launches."""
    C, S, n_steps = 3, 128, 64
    plist = [_lat_params(key=10 + k) for k in range(C)]
    gang = {k: jnp.stack([jnp.asarray(p[k]) for p in plist])
            for k in ("w1", "b1", "w2", "b2")}
    gang["coupling"] = jnp.asarray(plist[0]["coupling"])
    gang["lattice_meta"] = jnp.asarray(plist[0]["lattice_meta"])
    core_map = np.asarray([0, 1, 2], np.int32)
    x0 = _mk(I_LAT, H_LAT, C * S, key=11)[4]
    offs = jnp.zeros(C * S, jnp.uint32)
    gw, gs = ops.chaotic_bits_gang(
        gang, x0, n_steps, offs, core_map=core_map,
        backend="pallas_interpret", config=CFG_MXU)
    for ci in range(C):
        sl = slice(ci * S, (ci + 1) * S)
        w, s = ops.chaotic_bits(plist[ci], x0[sl], n_steps,
                                backend="pallas_interpret", config=CFG_MXU)
        np.testing.assert_array_equal(np.asarray(gw)[:, sl], np.asarray(w))
        np.testing.assert_array_equal(_f32(gs)[sl], _f32(s))


# ---------------------------------------------------------------------------
# Planner: the stacked-layout VMEM cliff
# ---------------------------------------------------------------------------

def test_stacked_vmem_cliff_planner_falls_back_to_concat():
    """Past the core count where one stacked launch exceeds the VMEM
    budget, the planner must stop choosing the sublane-stacked layout
    and fall back to lane-concat — same words, feasible launch."""
    from repro.serve.farm import GangScheduler

    # engineered cliff: wide lanes + deep unroll put one core's resident
    # stack in the tens of MB, so the cliff lands at a handful of cores
    # (the cores share sublane tiles, so it lands past a multiple of 8)
    cand = Candidate(i_dim=I_LAT, h_dim=H_LAT, p=3, compute_unit="vpu",
                     dtype_bytes=4, unroll=8, t_block=256)
    cliff = 1
    while stacked_gang_vmem_bytes(cand, cliff) <= VMEM_USABLE:
        cliff += 1
        assert cliff < 64, "engineered candidate never crossed the budget"
    assert cliff >= 2, "candidate must fit at least one core stacked"

    class _FakeSvc:
        mesh = None
        mesh_axis = "data"

        def __init__(self, c, s):
            self.config = c
            self.pool_x = np.zeros((s, c.i_dim), np.float32)

    def decide(n_cores):
        sched = GangScheduler(planner=True)
        members = [(f"c{i}", _FakeSvc(cand, cand.s_block), 8, None)
                   for i in range(n_cores)]
        return sched._decide(("k",), members, demands=(16,) * n_cores)

    below = decide(cliff - 1)
    assert below["parts"][0]["layout"] == "stacked"
    above = decide(cliff)
    assert above["parts"][0]["layout"] == "concat"


# ---------------------------------------------------------------------------
# Registry + farm serving
# ---------------------------------------------------------------------------

def test_lattice_registry_bundle_derived_from_base():
    """A lattice bundle is a pure function of the base registry entry:
    block-diagonal expansion + tiled normalizers, never retrained or
    persisted separately."""
    from repro.prng.stream import trained_oscillator
    b = trained_oscillator("chen@ring8")
    base = trained_oscillator("chen")
    d, h = base["w1"].shape
    assert b["w1"].shape == (8 * d, 8 * h)
    np.testing.assert_array_equal(b["w1"][:d, :h], base["w1"])
    assert not b["w1"][:d, h:].any()
    np.testing.assert_array_equal(b["scale"], np.tile(base["scale"], 8))
    np.testing.assert_array_equal(b["offset"], np.tile(base["offset"], 8))
    meta = lattice_meta_tuple(b["lattice_meta"])
    assert meta[:3] == (8, d, "ring")
    assert meta[3] == pytest.approx(DEFAULT_LATTICE_COUPLING)
    # RAM-cached: the same object comes back, not a recomputation
    assert trained_oscillator("chen@ring8") is b


def test_farm_serves_lattice_cores_next_to_scalars():
    """Two same-meta lattice cores gang with each other (one stacked
    launch), never with the scalar core; delivered words are bit-identical
    to a gang=False farm."""
    from repro.serve.farm import OscillatorFarm, _compat_key

    scal = _base_params(key=4)
    scal_cfg = Candidate(i_dim=D, h_dim=H, p=0, compute_unit="vpu",
                         dtype_bytes=4, t_block=32, unroll=2)

    def build(gang):
        farm = OscillatorFarm(gang=gang)
        farm.add_core("lat_a", _lat_params(key=1), config=CFG,
                      lanes_per_client=128, backend="pallas_interpret")
        farm.add_core("lat_b", _lat_params(key=2), config=CFG,
                      lanes_per_client=128, backend="pallas_interpret")
        farm.add_core("chen", scal, config=scal_cfg,
                      lanes_per_client=128, backend="pallas_interpret")
        for core in farm.cores:
            farm.register(core, "t", seed=5)
        return farm

    ganged, solo = build(True), build(False)
    keys = {c: _compat_key(ganged.services[c]) for c in ganged.cores}
    assert keys["lat_a"] == keys["lat_b"]
    assert keys["lat_a"] != keys["chen"]

    for _ in range(2):
        for farm in (ganged, solo):
            for core in farm.cores:
                farm.request(core, "t", 4096)
        out_g, out_s = ganged.flush(), solo.flush()
        for core in ganged.cores:
            np.testing.assert_array_equal(out_g[core]["t"],
                                          out_s[core]["t"])
    assert ganged.gang_launches >= 1


# ---------------------------------------------------------------------------
# Burn-in parity identity (the satellite bugfix)
# ---------------------------------------------------------------------------

def test_odd_burn_in_warns_and_records_effective_value():
    from repro.prng.stream import (ChaoticPRNG, effective_burn_in,
                                   registry_fingerprint)
    with pytest.warns(UserWarning, match="rounded up"):
        assert effective_burn_in(15) == 16
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert effective_burn_in(16) == 16
        assert effective_burn_in(0) == 0
    with pytest.raises(ValueError):
        effective_burn_in(-2)

    params = _base_params()
    cfg = Candidate(i_dim=D, h_dim=H, p=0, compute_unit="vpu",
                    dtype_bytes=4, t_block=32, unroll=1)
    with pytest.warns(UserWarning, match="burn_in"):
        odd = ChaoticPRNG(params, n_streams=128, burn_in=15,
                          backend="pallas_interpret", config=cfg)
    even = ChaoticPRNG(params, n_streams=128, burn_in=16,
                       backend="pallas_interpret", config=cfg)
    assert odd.burn_in == 16
    st = odd.init(seed=0)
    assert st.burn_in == 16                 # the stream records what RAN
    w_odd, st2 = odd.next_words(st, 256)
    w_even, _ = even.next_words(even.init(seed=0), 256)
    np.testing.assert_array_equal(w_odd, w_even)
    assert st2.burn_in == 16                # carried through draws

    # the fingerprint distinguishes effective burn-ins — and only those
    assert (registry_fingerprint("chen", 16)
            != registry_fingerprint("chen", 18))
    with pytest.warns(UserWarning):
        same = registry_fingerprint("chen", 15)
    assert same == registry_fingerprint("chen", 16)
    # None keeps legacy stamps byte-stable
    assert registry_fingerprint("chen") == registry_fingerprint("chen")


def test_service_snapshot_burn_in_identity_guard():
    from repro.serve.prng_service import PRNGService

    params = _base_params()
    cfg = Candidate(i_dim=D, h_dim=H, p=0, compute_unit="vpu",
                    dtype_bytes=4, t_block=32, unroll=1)

    def mk(burn_in):
        return PRNGService(params, lanes_per_client=128, burn_in=burn_in,
                           backend="pallas_interpret", config=cfg)

    svc = mk(16)
    svc.register("a", seed=1)
    snap = svc.snapshot()
    assert snap["burn_in"] == 16

    other = mk(18)
    with pytest.raises(ValueError, match="burn"):
        other.restore(snap)

    # round trip on a matching service is exact
    twin = mk(16)
    twin.restore(snap)
    np.testing.assert_array_equal(twin.draw("a", 64), svc.draw("a", 64))

    # legacy snapshots (no burn_in recorded) still restore
    legacy = dict(snap)
    legacy.pop("burn_in")
    mk(18).restore(legacy)

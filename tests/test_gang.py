"""Gang-scheduled farm launches: grouping, bit-identity, resumability.

The gang path's whole contract is "one launch per compatible group, words
bit-identical to the per-core path".  Kernel level: the stacked-weight
gang kernel must reproduce C per-core fused launches lane for lane.  Farm
level: mixed-dtype / mixed-h_dim farms must split into the right groups,
delivered words must match a ``gang=False`` farm bit for bit across
multi-flush traffic, and a snapshot taken mid-gang (requests in flight)
must replay identically — even when restored onto a farm with the other
launch mode, since chunk-invariance makes delivery independent of how
rows are batched into launches.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dse import Candidate
from repro.kernels import ops
from repro.serve.farm import OscillatorFarm, _compat_key
from repro.serve.health import HealthMonitor

from test_kernels import _mk

CAND = Candidate(i_dim=3, h_dim=8, p=1, compute_unit="vpu",
                 dtype_bytes=4, unroll=4, t_block=64)


def _params(i_dim=3, h_dim=8, key=0):
    w1, b1, w2, b2, _ = _mk(i_dim, h_dim, 1, key=key)
    return {"w1": w1, "b1": b1, "w2": w2, "b2": b2}


def _stacked(param_list):
    return {k: jnp.stack([p[k] for p in param_list])
            for k in ("w1", "b1", "w2", "b2")}


# ---------------------------------------------------------------------------
# Kernel level
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gang_kernel_matches_per_core(dtype):
    """One stacked launch == C per-core launches, bit for bit (words AND
    final states), including a slab referenced by two lane blocks."""
    s_block, n_steps = 128, 64
    plist = [_params(key=k) for k in range(3)]
    core_map = np.asarray([0, 2, 1, 2], np.int32)
    s_total = len(core_map) * s_block
    _, _, _, _, x0 = _mk(3, 8, s_total, key=9)
    x0 = x0.astype(dtype)
    rng = np.random.default_rng(3)
    offs = jnp.asarray(rng.integers(0, 10_000, size=s_total), np.uint32)

    gw, gs = ops.chaotic_bits_gang(
        _stacked(plist), x0, n_steps, offs, core_map=core_map,
        backend="pallas_interpret", s_block=s_block, t_block=32, unroll=2)
    assert gw.shape == (n_steps // 2, s_total)
    for g, c in enumerate(core_map):
        sl = slice(g * s_block, (g + 1) * s_block)
        w, s = ops.chaotic_bits(
            plist[c], x0[sl], n_steps, offs[sl],
            backend="pallas_interpret", s_block=s_block, t_block=32,
            unroll=2)
        np.testing.assert_array_equal(np.asarray(gw)[:, sl], np.asarray(w))
        np.testing.assert_array_equal(
            np.asarray(jnp.asarray(gs[sl], jnp.float32)),
            np.asarray(jnp.asarray(s, jnp.float32)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stacked_gang_kernel_matches_per_core(dtype):
    """The sublane-stacked layout (equal pools, one grid cell per lane
    block) is bit-identical to per-core launches too — same FMA order per
    lane, same fold, same whitening."""
    C, S, n_steps = 4, 256, 64
    plist = [_params(key=k) for k in range(C)]
    _, _, _, _, x0 = _mk(3, 8, C * S, key=6)
    x0 = x0.reshape(C, S, 3).astype(dtype)
    rng = np.random.default_rng(8)
    offs = jnp.asarray(rng.integers(0, 10_000, size=(C, S)), np.uint32)

    gw, gs = ops.chaotic_bits_gang_stacked(
        _stacked(plist), x0, n_steps, offs, backend="pallas_interpret",
        s_block=128, t_block=32, unroll=2)
    assert gw.shape == (n_steps // 2, C, S)
    for c in range(C):
        w, s = ops.chaotic_bits(plist[c], x0[c], n_steps, offs[c],
                                backend="pallas_interpret", s_block=128,
                                t_block=32, unroll=2)
        np.testing.assert_array_equal(np.asarray(gw)[:, c], np.asarray(w))
        np.testing.assert_array_equal(
            np.asarray(jnp.asarray(gs[c], jnp.float32)),
            np.asarray(jnp.asarray(s, jnp.float32)))
    # ref backend agrees with per-core ref
    rw, _ = ops.chaotic_bits_gang_stacked(
        _stacked(plist), x0, n_steps, offs, backend="ref")
    for c in range(C):
        w, _ = ops.chaotic_bits(plist[c], x0[c], n_steps, offs[c],
                                backend="ref")
        np.testing.assert_array_equal(np.asarray(rw)[:, c], np.asarray(w))


def test_stacked_gang_kernel_rejects_mxu():
    plist = [_params(key=1), _params(key=2)]
    with pytest.raises(ValueError, match="vpu"):
        ops.chaotic_bits_gang_stacked(
            _stacked(plist), jnp.zeros((2, 128, 3)), 8,
            backend="pallas_interpret", compute_unit="mxu")


def test_gang_ref_backend_matches_per_core_ref():
    """Co-simulation contract carries over: the gang 'ref' backend equals
    per-core 'ref' draws block for block."""
    s_block, n_steps = 128, 32
    plist = [_params(key=k) for k in range(2)]
    core_map = np.asarray([1, 0, 1], np.int32)
    s_total = len(core_map) * s_block
    _, _, _, _, x0 = _mk(3, 8, s_total, key=4)
    rw, rs = ops.chaotic_bits_gang(
        _stacked(plist), x0, n_steps, jnp.uint32(5), core_map=core_map,
        backend="ref", s_block=s_block)
    for g, c in enumerate(core_map):
        sl = slice(g * s_block, (g + 1) * s_block)
        w, s = ops.chaotic_bits(plist[c], x0[sl], n_steps, jnp.uint32(5),
                                backend="ref", s_block=s_block)
        np.testing.assert_array_equal(np.asarray(rw)[:, sl], np.asarray(w))
        np.testing.assert_array_equal(np.asarray(rs[sl]), np.asarray(s))


def test_gang_kernel_rejects_ragged_pool():
    plist = [_params(key=1)]
    with pytest.raises(ValueError, match="s_block multiple"):
        ops.chaotic_bits_gang(
            _stacked(plist), jnp.zeros((100, 3)), 8,
            core_map=np.asarray([0], np.int32),
            backend="pallas_interpret", s_block=128)


# ---------------------------------------------------------------------------
# Farm level
# ---------------------------------------------------------------------------

def _farm(gang, members, lanes=128, **kw):
    """members: (core, params, config, dtype) tuples."""
    farm = OscillatorFarm(gang=gang, **kw)
    for core, params, config, dtype in members:
        farm.add_core(core, params, config=config, dtype=dtype,
                      lanes_per_client=lanes, backend="pallas_interpret")
    return farm


def _compatible_members(n=4):
    return [(f"core{i}", _params(key=10 + i), CAND, None) for i in range(n)]


def test_compat_grouping_splits_mixed_farms():
    """Mixed dtype / h_dim / config cores must NOT share a gang."""
    cand16 = Candidate(i_dim=3, h_dim=16, p=1, compute_unit="vpu",
                       dtype_bytes=4, unroll=4, t_block=64)
    members = [
        ("a", _params(key=1), CAND, None),
        ("b", _params(key=2), CAND, None),                 # gangs with a
        ("c", _params(key=3), CAND, jnp.bfloat16),         # dtype differs
        ("d", _params(3, 16, key=4), cand16, None),        # h_dim differs
    ]
    farm = _farm(True, members)
    keys = {c: _compat_key(farm.services[c]) for c in farm.cores}
    assert keys["a"] == keys["b"]
    assert len({keys["a"], keys["c"], keys["d"]}) == 3

    for c in farm.cores:
        farm.register(c, "t", seed=2)
        farm.request(c, "t", 200)
    out = farm.flush()
    assert set(out) == {"a", "b", "c", "d"}
    # one gang launch for {a, b} + solo launches for c and d
    assert farm.launches == 3
    assert farm.gang_launches == 1

    # every client still gets exactly its per-core words
    solo = _farm(False, members)
    for c in solo.cores:
        solo.register(c, "t", seed=2)
        solo.request(c, "t", 200)
    ref = solo.flush()
    assert solo.launches == 4
    for c in ref:
        np.testing.assert_array_equal(out[c]["t"], ref[c]["t"])


def test_gang_vs_per_core_bit_identical_across_flushes():
    """Multi-flush, multi-client traffic: delivered words never depend on
    the launch mode (gang overdraw is buffered like batching overdraw)."""
    farms = [_farm(g, _compatible_members()) for g in (True, False)]
    for f in farms:
        for core in f.cores:
            f.register(core, "u1", seed=21)
            f.register(core, "u2", seed=22)
    traffic = [
        {"core0": [("u1", 300)], "core1": [("u2", 900)],
         "core2": [("u1", 50)], "core3": [("u2", 130)]},
        {"core0": [("u2", 411)], "core2": [("u1", 222), ("u2", 7)]},
        {"core1": [("u1", 1)], "core3": [("u1", 2048)]},
    ]
    for round_ in traffic:
        outs = []
        for f in farms:
            for core, reqs in round_.items():
                for client, n in reqs:
                    f.request(core, client, n)
            outs.append(f.flush())
        gang_out, solo_out = outs
        assert set(gang_out) == set(solo_out)
        for core in gang_out:
            assert set(gang_out[core]) == set(solo_out[core])
            for client in gang_out[core]:
                np.testing.assert_array_equal(gang_out[core][client],
                                              solo_out[core][client])
    # the whole point: far fewer launches on the gang side
    assert farms[0].launches < farms[1].launches


def test_ragged_pools_gang_via_lane_concat():
    """Cores with DIFFERENT client counts still gang (lane-concat layout
    with a per-block core-id map) and stay bit-identical to per-core."""
    members = _compatible_members(3)
    farms = [_farm(g, members) for g in (True, False)]
    for f in farms:
        f.register("core0", "only", seed=31)          # 128-lane pool
        for core in ("core1", "core2"):               # 256-lane pools
            f.register(core, "u1", seed=32)
            f.register(core, "u2", seed=33)
    for f in farms:
        f.request("core0", "only", 517)
        f.request("core1", "u2", 1024)
        f.request("core2", "u1", 64)
    gang_out, solo_out = (f.flush() for f in farms)
    assert farms[0].gang_launches == 1
    plan = next(iter(farms[0]._sched._plans.values()))
    assert plan["mode"] == "concat"                   # ragged -> lane-concat
    assert set(gang_out) == set(solo_out)
    for core in gang_out:
        for client in gang_out[core]:
            np.testing.assert_array_equal(gang_out[core][client],
                                          solo_out[core][client])
    # equal-size pools keep the cheaper sublane-stacked layout
    eq = _farm(True, _compatible_members(2))
    for core in eq.cores:
        eq.register(core, "t", seed=3)
        eq.request(core, "t", 100)
    eq.flush()
    assert next(iter(eq._sched._plans.values()))["mode"] == "stacked"


def test_gang_dispatch_cache_steady_state():
    """Steady-state traffic replays cached dispatches: distinct (group,
    bucketed rows) keys stop growing."""
    farm = _farm(True, _compatible_members())
    for core in farm.cores:
        farm.register(core, "t", seed=5)
    for _ in range(4):
        for core in farm.cores:
            # exactly one full launch worth: zero overdraw, so every round
            # replays the same bucketed row count
            farm.request(core, "t", 64 * 128)
        farm.flush()
    assert farm.gang_launches == 4
    assert farm.dispatch_misses == 1


def test_gang_snapshot_restore_mid_gang():
    """Snapshot with requests in flight, restore, flush: identical words —
    including restoring onto a farm in the OTHER launch mode."""
    farm = _farm(True, _compatible_members())
    for core in farm.cores:
        farm.register(core, "t", seed=9)
    farm.draw("core1", "t", 100)                  # advance some state first
    for core in farm.cores:
        farm.request(core, "t", 333)              # in flight at snapshot
    snap = farm.snapshot()
    a = farm.flush()

    gang2 = _farm(True, _compatible_members())
    gang2.restore(snap)
    b = gang2.flush()
    solo = _farm(False, _compatible_members())
    solo.restore(snap)
    c = solo.flush()
    assert set(a) == set(b) == set(c)
    for core in a:
        np.testing.assert_array_equal(a[core]["t"], b[core]["t"])
        np.testing.assert_array_equal(a[core]["t"], c[core]["t"])


def test_deadline_deferral_and_auto_flush():
    """Small tenants coalesce: a below-threshold group defers exactly once
    (the deadline), and auto-flush requests park words instead of losing
    them."""
    farm = _farm(True, _compatible_members())
    for core in farm.cores:
        farm.register(core, "t", seed=4)
    farm.request("core0", "t", 10)
    assert farm.flush(max_wait_rows=64) == {}     # 1 row < 64: deferred
    assert farm.launches == 0
    out = farm.flush(max_wait_rows=64)            # overdue: must launch now
    assert out["core0"]["t"].size == 10
    assert farm.launches == 1

    # a second tenant arriving lifts the group over the threshold at once
    farm.request("core0", "t", 20)
    farm.request("core1", "t", 64 * 128)          # 64 rows on its own
    out = farm.flush(max_wait_rows=64)
    assert set(out) == {"core0", "core1"}

    # auto-flush: words are parked, then delivered by the next flush
    auto = _farm(True, _compatible_members(), auto_flush_rows=4)
    solo = _farm(False, _compatible_members())
    for f in (auto, solo):
        for core in f.cores:
            f.register(core, "t", seed=4)
    auto.request("core0", "t", 100, auto_flush=True)   # 1 row < 4: waits
    assert auto.launches == 0
    auto.request("core1", "t", 600, auto_flush=True)   # 5 rows: fires
    assert auto.gang_launches == 1
    out = auto.flush()                                 # delivery only
    assert auto.launches == 1
    solo.request("core0", "t", 100)
    solo.request("core1", "t", 600)
    ref = solo.flush()
    for core in ref:
        np.testing.assert_array_equal(out[core]["t"], ref[core]["t"])


# ---------------------------------------------------------------------------
# Absorb: one host copy per delivered word
# ---------------------------------------------------------------------------

#: clients per core of each launch shape
SHAPES = {"solo": [2], "stacked": [2, 2], "concat": [1, 2]}
WHOLE, LEFTOVER = 4 * 128, 5 * 128 + 3   # 4 rows; 6 rows launched as 8


class _Sampled(HealthMonitor):
    """Keeps a copy of every sample the farm's hook hands it."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.got = {}

    def ingest(self, core, words):
        self.got.setdefault(core, []).append(np.array(words))


def _absorb_farm(shape, **kw):
    members = _compatible_members(len(SHAPES[shape]))
    farm = _farm(True, members, **kw)
    for (core, *_), n in zip(members, SHAPES[shape]):
        for j in range(n):
            farm.register(core, f"u{j}", seed=40 + j)
    return farm


def _record_launches(farm):
    """Wrap each service's absorb: per core, every launch's slab, whether
    it was contiguous, each active tenant's leftover word count, and the
    words concatenated buffers took from it (``words[:, slot]`` row-major,
    appended to the leftover)."""
    seen = {core: [] for core in farm.cores}
    for core, svc in farm.services.items():
        def absorb(words, new_x, n_rows, *, deliver=True, _core=core,
                   _svc=svc, _inner=svc.absorb):
            if n_rows > 0:
                L = _svc.lanes_per_client
                slab = np.array(words)
                active = _svc._active()
                seen[_core].append({
                    "slab": slab,
                    "contiguous": np.asarray(words).flags.c_contiguous,
                    "leftover": {c.name: len(c.buf) for c in active},
                    "gathered": {
                        c.name: slab[:, c.slot * L:(c.slot + 1) * L]
                        .reshape(-1) for c in active}})
            return _inner(words, new_x, n_rows, deliver=deliver)
        svc.absorb = absorb
    return seen


def _serve_rounds(farm, n_words, rounds=3):
    """Every tenant draws ``n_words`` a round, except that in the second
    round only ``u0`` does (the others ride the launch idle)."""
    got = {(core, name): [] for core in farm.cores
           for name in farm.services[core].clients}
    for r in range(rounds):
        for core, name in got:
            if r != 1 or name == "u0":
                farm.request(core, name, n_words)
        for core, by_client in farm.flush().items():
            for name, w in by_client.items():
                got[(core, name)].append(w)
    return got


@pytest.mark.parametrize("n_words", [WHOLE, LEFTOVER],
                         ids=["empty-buffers", "leftover-buffers"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_absorb_delivers_what_concatenated_buffers_delivered(shape, n_words):
    """Delivered words and the leftover buffers are bit-identical to the
    algorithm that concatenated each launch's gathered lanes onto the
    buffer, for solo, stacked-gang and lane-concat launches."""
    farm = _absorb_farm(shape)
    seen = _record_launches(farm)
    got = _serve_rounds(farm, n_words)
    for layout, n in farm.layout_launches.items():
        assert (n > 0) == (shape == layout), layout
    for (core, name), draws in got.items():
        want = np.concatenate([launch["gathered"][name]
                               for launch in seen[core]
                               if name in launch["gathered"]])
        have = np.concatenate(draws)
        assert have.size == n_words * (3 if name == "u0" else 2)
        np.testing.assert_array_equal(have, want[:have.size])
        np.testing.assert_array_equal(farm.services[core].clients[name].buf,
                                      want[have.size:])
        if n_words == WHOLE:
            assert want.size == have.size
        else:
            assert any(launch["leftover"].get(name)
                       for launch in seen[core])


@pytest.mark.parametrize("cap", [256, 300])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_monitor_sample_is_the_slabs_first_words(shape, cap):
    """The hook hands ``ingest`` exactly ``slab.reshape(-1)[:cap]``, in
    row-major order, also for a gang member's strided slab."""
    farm = _absorb_farm(shape)
    monitor = _Sampled(window_words=cap)
    farm.attach_monitor(monitor)
    seen = _record_launches(farm)
    _serve_rounds(farm, LEFTOVER)
    for core, launches in seen.items():
        assert launches and len(monitor.got[core]) == len(launches)
        for launch, sample in zip(launches, monitor.got[core]):
            assert launch["contiguous"] == (shape == "solo")
            np.testing.assert_array_equal(
                sample, launch["slab"].reshape(-1)[:cap])


@pytest.mark.parametrize("n_words", [WHOLE, LEFTOVER],
                         ids=["empty-buffers", "leftover-buffers"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_absorb_counts_each_word_it_copies_once(shape, n_words):
    """``absorb_words_copied``: the words buffered, the leftover words
    copied beside them, and the monitor's sample; with empty buffers,
    just the words buffered and the sample."""
    cap = 256
    farm = _absorb_farm(shape, profile=True)
    farm.attach_monitor(_Sampled(window_words=cap))
    seen = _record_launches(farm)
    _serve_rounds(farm, n_words)
    launches = [launch for ls in seen.values() for launch in ls]
    buffered = sum(w.size for launch in launches
                   for w in launch["gathered"].values())
    leftover = sum(n for launch in launches
                   for n in launch["leftover"].values())
    sampled = sum(min(cap, launch["slab"].size) for launch in launches)
    assert (leftover == 0) == (n_words == WHOLE)
    assert farm.profile_stats["absorb_words_copied"] == (
        buffered + leftover + sampled)

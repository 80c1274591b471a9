"""Self-healing farm: fault injection, retries, breakers, quality rotation.

The whole suite is FakeClock-driven — storms of transient launch
failures, exponential-backoff retries, circuit-breaker quarantines and
standby rotations all run with ZERO real sleeps: fake time advances only
when a test says so, and the supervision layer's backoff routes through
the injected ``Clock`` (enforced repo-wide by the ``backoff-discipline``
rule of ``repro.analysis``).

The headline contracts:

* a transiently failed launch never reached ``absorb()``, so its
  committed demand is still parked at the same absolute stream rows —
  a retried flush serves words **bit-identical** to a never-failed one;
* a core that keeps failing trips its breaker and is quarantined: its
  tenants get a typed ``CoreQuarantined`` (never a hang), its gang
  group re-plans without it, and every OTHER tenant's words stay
  bit-identical to a fault-free run;
* a core whose *served words* go statistically bad (the online NIST
  gate over sampled windows) is quarantined within bounded flushes and
  its standby rotated into the routing slot;
* the journal records quarantines/rotations, so kill-and-replay
  reconstructs the crashed process's DEGRADED topology, not just its
  stream positions.

Launch-fault tests use the fast toy weights with a never-filling sample
window (toy networks are not trained oscillators — their words fail any
honest NIST gate, which is the quality monitor doing its job, not noise
to silence).  Quality-gate tests use the trained registry weights, whose
streams pass; only the FaultPlan's poisoned sampling fails them.
"""
import asyncio

import numpy as np
import pytest

from repro.prng.stream import default_params
from repro.serve.admission import AdmissionController
from repro.serve.async_frontend import AsyncOscillatorFarm
from repro.serve.clock import FakeClock
from repro.serve.farm import OscillatorFarm
from repro.serve.faults import FaultPlan, InjectedFault
from repro.serve.health import CoreQuarantined, HealthMonitor
from repro.serve.journal import replay_journal

from test_async_frontend import CAND, _farm, _params, _run

# Launch-fault tests: a window this large never fills from test traffic,
# so the quality gate stays silent and only launch supervision is on
# trial (toy test weights would fail any honest NIST gate).
BIG_WINDOW = 1 << 20


def _health(**kw):
    kw.setdefault("window_words", BIG_WINDOW)
    kw.setdefault("backoff_base_ms", 5.0)
    return HealthMonitor(**kw)


async def _drive(fc, futs, rounds=400, step_s=0.05):
    """Pump the loop + fake time until every future settles."""
    for _ in range(rounds):
        await asyncio.sleep(0)
        fc.advance(step_s)
        if all(f.done() for f in futs):
            return
    raise AssertionError(
        f"futures never settled: "
        f"{sum(1 for f in futs if not f.done())} still pending")


def _trained_farm(n_cores=3, clock=None, faults=None, standby_for=()):
    """Trained-registry cores (words pass the online gate) — the quality
    monitor only condemns what the FaultPlan poisons."""
    params = default_params(system="chen")
    farm = OscillatorFarm(gang=True, clock=clock, faults=faults)
    for i in range(n_cores):
        farm.add_core(f"core{i}", params, lanes_per_client=128)
        farm.register(f"core{i}", "t", seed=40)
    for core in standby_for:
        farm.add_standby(core, params, lanes_per_client=128)
    return farm


def _trained_solo_words(rounds, n_words=300):
    """Reference stream: one trained core served solo from registration."""
    params = default_params(system="chen")
    farm = OscillatorFarm(gang=False)
    farm.add_core("c", params, lanes_per_client=128)
    farm.register("c", "t", seed=40)
    return [farm.draw("c", "t", n_words) for _ in range(rounds)]


# ---------------------------------------------------------------------------
# FaultPlan: deterministic, replayable schedules
# ---------------------------------------------------------------------------

def _schedule(plan, launches=64):
    out = []
    for _ in range(launches):
        try:
            plan.on_launch(["a", "b"])
            out.append(False)
        except InjectedFault:
            out.append(True)
    return out


def test_fault_plan_same_seed_same_schedule():
    a = _schedule(FaultPlan(seed=7, transient_rate=0.3))
    b = _schedule(FaultPlan(seed=7, transient_rate=0.3))
    assert a == b and any(a)
    assert _schedule(FaultPlan(seed=8, transient_rate=0.3)) != a


def test_fault_plan_draw_per_launch_regardless_of_outcome():
    # the schedule depends only on the launch SEQUENCE: capping injected
    # faults must not shift later draws
    full = _schedule(FaultPlan(seed=7, transient_rate=0.3))
    capped_plan = FaultPlan(seed=7, transient_rate=0.3, max_transients=2)
    capped = _schedule(capped_plan)
    k = [i for i, hit in enumerate(full) if hit][1]
    assert capped[:k + 1] == full[:k + 1]
    assert capped_plan.injected["transient"] == 2
    assert not any(capped[k + 1:])


def test_fault_plan_scoping_and_arming():
    plan = FaultPlan(seed=0, transient_rate=1.0, transient_cores={"x"})
    plan.on_launch(["a", "b"])                    # not eligible: no x
    with pytest.raises(InjectedFault):
        plan.on_launch(["a", "x"])
    plan.disarm()
    plan.on_launch(["x"])                         # disarmed: no injection
    plan.arm()
    pers = FaultPlan(persistent_cores={"p"})
    with pytest.raises(InjectedFault) as ei:
        pers.on_launch(["a", "p"])
    assert ei.value.cores == ("p",) and ei.value.persistent
    pers.heal("p")
    pers.on_launch(["a", "p"])                    # healed
    with pytest.raises(ValueError):
        FaultPlan(transient_rate=1.5)


def test_failed_sync_flush_leaves_demand_parked_bit_exact():
    """A failed launch never absorbs: the SAME flush retried serves the
    same words — the bit-identity-by-construction the retry loop rests
    on, shown on the bare sync farm."""
    faults = FaultPlan(persistent_cores={"core0"})
    farm = _farm(n_cores=1, faults=faults)
    clean = _farm(n_cores=1)
    farm.request("core0", "t", 500)
    clean.request("core0", "t", 500)
    with pytest.raises(InjectedFault):
        farm.flush()
    assert farm.services["core0"].rows_needed() > 0   # demand still parked
    faults.heal("core0")
    out = farm.flush()
    ref = clean.flush()
    assert np.array_equal(out["core0"]["t"], ref["core0"]["t"])


# ---------------------------------------------------------------------------
# HealthMonitor policy units
# ---------------------------------------------------------------------------

def test_backoff_capped_exponential_with_bounded_jitter():
    h = HealthMonitor(backoff_base_ms=5.0, backoff_cap_ms=40.0,
                      backoff_jitter=0.25, seed=3)
    for attempt, base in ((1, 5.0), (2, 10.0), (3, 20.0), (4, 40.0),
                          (5, 40.0), (9, 40.0)):
        ms = h.backoff_ms(attempt)
        assert base <= ms <= base * 1.25, (attempt, ms)
    with pytest.raises(ValueError):
        h.backoff_ms(0)
    # seeded: two monitors replay the identical jitter sequence
    a = HealthMonitor(seed=11)
    b = HealthMonitor(seed=11)
    assert [a.backoff_ms(i) for i in (1, 2, 3)] == \
           [b.backoff_ms(i) for i in (1, 2, 3)]


def test_breaker_counts_consecutive_failures_only():
    h = HealthMonitor(breaker_threshold=3)
    assert h.note_launch_failure(["a", "b"]) == []
    assert h.note_launch_failure(["a"]) == []
    h.note_launch_success(["a"])                  # streak broken
    assert h.note_launch_failure(["a"]) == []
    assert h.note_launch_failure(["a"]) == []
    assert h.note_launch_failure(["a", "b"]) == ["a"]   # a: 3rd consecutive
    assert h.consecutive_failures("b") == 2
    assert h.stats["breaker_trips"] == 1


def test_monitor_windows_pop_exactly_and_memory_is_bounded():
    h = HealthMonitor(window_words=256)
    rng = np.random.default_rng(0)
    h.ingest("c", rng.integers(0, 2**32, 200, dtype=np.uint32))
    assert h.evaluate() == {}                     # window not full yet
    h.ingest("c", rng.integers(0, 2**32, 200, dtype=np.uint32))
    assert h.buffered_words("c") == 400
    verdicts = h.evaluate()                       # healthy words: no verdict
    assert verdicts == {}
    assert h.buffered_words("c") == 400 - 256     # rest carried over
    for _ in range(100):
        h.ingest("c", rng.integers(0, 2**32, 10_000, dtype=np.uint32))
    assert h.buffered_words("c") <= 2 * 256       # hard memory bound
    h.reset("c")
    assert h.buffered_words("c") == 0


def test_monitor_hard_failure_condemns_in_one_window():
    h = HealthMonitor(window_words=256)
    rng = np.random.default_rng(0)
    poisoned = rng.integers(0, 2**32, 256, dtype=np.uint32) & np.uint32(
        0xFFFF0000)
    h.ingest("bad", poisoned)
    verdicts = h.evaluate()
    assert "bad" in verdicts
    assert "monobit" in verdicts["bad"]["gate"]["hard_failed_tests"]
    assert h.stats["quality_quarantines"] == 1


# ---------------------------------------------------------------------------
# Supervised front-end: transient retries are invisible in the words
# ---------------------------------------------------------------------------

def test_transient_retries_serve_bit_identical_words():
    results = {}

    async def faulty():
        fc = FakeClock()
        faults = FaultPlan(seed=3, transient_rate=0.5, max_transients=4)
        health = _health(breaker_threshold=10, seed=1)
        farm = _farm(clock=fc, faults=faults)
        async with AsyncOscillatorFarm(farm, clock=fc, offload=False,
                                       health=health) as af:
            futs = [af.submit(f"core{i}", "t", 300) for i in range(3)]
            await _drive(fc, futs)
            results["faulty"] = [f.result() for f in futs]
        assert faults.injected["transient"] > 0
        assert health.stats["retries"] > 0
        assert health.stats["breaker_trips"] == 0

    async def clean():
        fc = FakeClock()
        farm = _farm(clock=fc)
        async with AsyncOscillatorFarm(farm, clock=fc, offload=False) as af:
            futs = [af.submit(f"core{i}", "t", 300) for i in range(3)]
            await _drive(fc, futs)
            results["clean"] = [f.result() for f in futs]

    _run(faulty())
    _run(clean())
    for a, b in zip(results["faulty"], results["clean"]):
        assert np.array_equal(a, b)


def test_retry_budget_exhausted_propagates_to_futures():
    async def go():
        fc = FakeClock()
        faults = FaultPlan(seed=0, transient_rate=1.0, max_transients=None)
        # threshold above the retry budget: the breaker never trips, the
        # budget runs out first and the error reaches the tenants
        health = _health(breaker_threshold=100, max_retries_per_flush=2)
        farm = _farm(n_cores=1, clock=fc, faults=faults)
        async with AsyncOscillatorFarm(farm, clock=fc, offload=False,
                                       health=health) as af:
            fut = af.submit("core0", "t", 100)
            await _drive(fc, [fut])
            assert isinstance(fut.exception(), InjectedFault)
            assert health.stats["retries"] == 2
            assert len(af.flush_errors) >= 1
    _run(go())


@pytest.mark.parametrize("err", [
    NotImplementedError("Unimplemented primitive in Pallas TPU lowering"),
    ValueError("Shape mismatch in input, indices and output"),
], ids=["lowering", "shape"])
def test_build_error_propagates_without_retry_or_quarantine(err):
    """An error raised while building a launch fails the same way on
    every retry: it reaches the tenants at once, is never retried, and
    never counts against a core's breaker."""
    async def go():
        fc = FakeClock()
        health = _health(breaker_threshold=1)
        farm = _farm(n_cores=1, clock=fc)

        def refuse(*a, **kw):
            raise err

        farm.flush = refuse
        async with AsyncOscillatorFarm(farm, clock=fc, offload=False,
                                       health=health) as af:
            fut = af.submit("core0", "t", 100)
            await _drive(fc, [fut])
            assert fut.exception() is err
        assert health.stats["retries"] == 0
        assert health.stats["launch_failures"] == 0
        assert not farm.quarantined
    _run(go())


# ---------------------------------------------------------------------------
# Circuit breaker: quarantine, re-planned gang, typed errors
# ---------------------------------------------------------------------------

def test_breaker_quarantines_core_and_group_replans_without_it():
    healthy_words = {}

    async def storm():
        fc = FakeClock()
        faults = FaultPlan(persistent_cores={"core1"})
        health = _health(breaker_threshold=3)
        farm = _farm(clock=fc, faults=faults)
        async with AsyncOscillatorFarm(farm, clock=fc, offload=False,
                                       health=health) as af:
            f_bad = af.submit("core1", "t", 100)
            f_ok = [af.submit(f"core{i}", "t", 100) for i in (0, 2)]
            await _drive(fc, f_ok + [f_bad])
            err = f_bad.exception()
            assert isinstance(err, CoreQuarantined)
            assert err.core == "core1" and not err.rotated
            assert farm.quarantined == frozenset({"core1"})
            assert health.stats["breaker_trips"] == 1
            healthy_words["storm"] = [f.result() for f in f_ok]
            # fail-fast at submit for the dead core, typed
            with pytest.raises(CoreQuarantined):
                af.submit("core1", "t", 10)
            # the re-planned group (core0+core2) keeps serving
            f2 = [af.submit(f"core{i}", "t", 50) for i in (0, 2)]
            await _drive(fc, f2)
            assert all(f.exception() is None for f in f2)

    async def clean():
        fc = FakeClock()
        farm = _farm(clock=fc)
        async with AsyncOscillatorFarm(farm, clock=fc, offload=False) as af:
            futs = [af.submit(f"core{i}", "t", 100) for i in (0, 2)]
            await _drive(fc, futs)
            healthy_words["clean"] = [f.result() for f in futs]

    _run(storm())
    _run(clean())
    for a, b in zip(healthy_words["storm"], healthy_words["clean"]):
        assert np.array_equal(a, b)


def test_quarantine_without_standby_shrinks_admission_ceiling():
    async def go():
        fc = FakeClock()
        faults = FaultPlan(persistent_cores={"core1"})
        health = _health(breaker_threshold=2)
        adm = AdmissionController(max_queued_rows=300, clock=fc)
        farm = _farm(clock=fc, faults=faults)
        async with AsyncOscillatorFarm(farm, clock=fc, offload=False,
                                       health=health, admission=adm) as af:
            assert adm.current_ceiling == 300
            fut = af.submit("core1", "t", 100)
            await _drive(fc, [fut])
            assert isinstance(fut.exception(), CoreQuarantined)
            # 2 of 3 cores healthy: the ceiling shrinks with capacity
            assert adm.capacity_factor == pytest.approx(2 / 3)
            assert adm.current_ceiling == 200
            assert adm.stats()["capacity_factor"] == pytest.approx(2 / 3)
    _run(go())


# ---------------------------------------------------------------------------
# Online quality gate: poisoned sampling -> quarantine + rotation
# ---------------------------------------------------------------------------

def test_poisoned_core_rotates_within_three_flushes_bit_exact():
    rotated_words = []

    async def go():
        fc = FakeClock()
        faults = FaultPlan(poison={"core0"})
        health = HealthMonitor(window_words=256)
        adm = AdmissionController(max_queued_rows=10_000, clock=fc)
        farm = _trained_farm(clock=fc, faults=faults,
                             standby_for=("core0",))
        async with AsyncOscillatorFarm(farm, clock=fc, offload=False,
                                       health=health, admission=adm) as af:
            rotated_at = None
            for round_ in range(4):
                futs = [af.submit(f"core{i}", "t", 300) for i in range(3)]
                await _drive(fc, futs)
                for i, f in enumerate(futs):
                    assert f.exception() is None, (round_, i, f.exception())
                rotated_words.append(futs[0].result())
                if rotated_at is None and farm.rotations.get("core0") == 1:
                    rotated_at = round_ + 1
            # the acceptance bound: quarantined + rotated within 3 flushes
            assert rotated_at is not None and rotated_at <= 3
            assert health.stats["quality_quarantines"] == 1
            assert farm.quarantined == frozenset()       # rotation lifted it
            assert adm.capacity_factor == 1.0            # capacity restored

    _run(go())
    # Bit-identity across the rotation: the rounds before it match the
    # original core served solo; the rounds after match the STANDBY
    # served solo from registration (same seed, row 0) — delivered words
    # were never corrupted (only the monitor's samples were).
    n = len(rotated_words)
    for split in range(n + 1):
        ref = _trained_solo_words(split) + _trained_solo_words(n - split)
        if all(np.array_equal(a, b) for a, b in zip(rotated_words, ref)):
            assert 0 < split <= 3    # rotation actually happened mid-run
            return
    raise AssertionError("rotated-core words match no rotation point")


def test_standby_samples_clean_after_rotation():
    """Poison binds to the PHYSICAL service: after rotation the monitor
    sees the standby's honest words and never re-condemns the slot."""
    async def go():
        fc = FakeClock()
        faults = FaultPlan(poison={"core0"})
        health = HealthMonitor(window_words=256)
        farm = _trained_farm(n_cores=1, clock=fc, faults=faults,
                             standby_for=("core0",))
        async with AsyncOscillatorFarm(farm, clock=fc, offload=False,
                                       health=health) as af:
            for _ in range(6):
                fut = af.submit("core0", "t", 300)
                await _drive(fc, [fut])
                assert fut.exception() is None
            assert farm.rotations.get("core0") == 1   # exactly one rotation
            assert health.stats["quality_quarantines"] == 1
            assert faults.injected["corrupted_samples"] > 0
    _run(go())


# ---------------------------------------------------------------------------
# The storm acceptance test: transients + a poisoned core, all at once
# ---------------------------------------------------------------------------

def test_storm_every_admitted_request_bit_identical_to_solo():
    served = []     # (round, core_index, words) for every resolved future

    async def go():
        fc = FakeClock()
        # seed chosen so the 10% coin actually lands at least once in
        # this short run (seeded schedule: same seed, same storm)
        faults = FaultPlan(seed=2, transient_rate=0.10, poison={"core0"})
        health = HealthMonitor(window_words=256, breaker_threshold=5,
                               backoff_base_ms=5.0)
        farm = _trained_farm(clock=fc, faults=faults,
                             standby_for=("core0",))
        async with AsyncOscillatorFarm(farm, clock=fc, offload=False,
                                       health=health) as af:
            for round_ in range(5):
                futs = [af.submit(f"core{i}", "t", 300) for i in range(3)]
                await _drive(fc, futs)
                for i, f in enumerate(futs):
                    # under this storm every request resolves (transients
                    # retry, the poisoned core rotates) — a CoreQuarantined
                    # here would also be acceptable per the contract, but
                    # must then be typed
                    if f.exception() is not None:
                        assert isinstance(f.exception(), CoreQuarantined)
                        continue
                    served.append((round_, i, f.result()))
            assert farm.rotations.get("core0") == 1
        assert faults.injected["transient"] > 0
        assert faults.injected["corrupted_samples"] > 0

    _run(go())
    # every served word bit-identical to a fault-free solo run of the
    # same per-round demand (core0: try all rotation split points)
    rounds = 5
    solo = {i: _trained_solo_words(rounds) for i in (1, 2)}
    for round_, i, words in served:
        if i == 0:
            continue
        assert np.array_equal(words, solo[i][round_]), (round_, i)
    core0 = [(r, w) for r, i, w in served if i == 0]
    n0 = len(core0)
    for split in range(rounds + 1):
        ref = _trained_solo_words(split) + _trained_solo_words(rounds - split)
        got = [np.array_equal(w, ref[r]) for r, w in core0]
        if all(got):
            return
    raise AssertionError("core0 storm words match no rotation split")


# ---------------------------------------------------------------------------
# Kill-and-replay reconstructs the DEGRADED topology from the journal
# ---------------------------------------------------------------------------

def test_kill_and_replay_reconstructs_quarantine_and_rotation(tmp_path):
    jpath = tmp_path / "storm.journal"
    params = default_params(system="chen")
    live_tail = {}

    async def serve_through_storm():
        fc = FakeClock()
        faults = FaultPlan(persistent_cores={"core1"}, poison={"core0"})
        health = HealthMonitor(window_words=256, breaker_threshold=2)
        # bare cores: EVERY registration goes through the front-end so
        # the journal alone can rebuild the client set
        farm = OscillatorFarm(gang=True, clock=fc, faults=faults)
        for i in range(3):
            farm.add_core(f"core{i}", params, lanes_per_client=128)
        farm.add_standby("core0", params, lanes_per_client=128)
        async with AsyncOscillatorFarm(farm, clock=fc, offload=False,
                                       health=health, journal=jpath) as af:
            for i in range(3):
                af.register(f"core{i}", "j", seed=77)
            f_bad = af.submit("core1", "j", 100)
            futs = [af.submit(f"core{i}", "j", 300) for i in (0, 2)]
            await _drive(fc, futs + [f_bad])
            assert isinstance(f_bad.exception(), CoreQuarantined)
            for _ in range(2):       # poisoned core0 rotates along the way
                futs = [af.submit(f"core{i}", "j", 300) for i in (0, 2)]
                await _drive(fc, futs)
                assert all(f.exception() is None for f in futs)
            assert farm.quarantined == frozenset({"core1"})
            assert farm.rotations.get("core0") == 1
            # the continuation a correct replay must reproduce
            live_tail["core0"] = farm.draw("core0", "j", 128)
            live_tail["core2"] = farm.draw("core2", "j", 128)

    _run(serve_through_storm())

    # a NEW process: same cores + the same standby, journal only
    reborn = OscillatorFarm(gang=True)
    for i in range(3):
        reborn.add_core(f"core{i}", params, lanes_per_client=128)
    reborn.add_standby("core0", params, lanes_per_client=128)
    summary = replay_journal(reborn, jpath)
    # two quarantine events (core1 by breaker; core0 by quality gate,
    # then lifted by its rotation) and one rotation
    assert summary["quarantines"] == 2 and summary["rotations"] == 1
    assert reborn.quarantined == frozenset({"core1"})
    assert reborn.rotations == {"core0": 1}
    with pytest.raises(CoreQuarantined):
        reborn.draw("core1", "j", 10)
    for core in ("core0", "core2"):
        assert np.array_equal(reborn.draw(core, "j", 128), live_tail[core])


# ---------------------------------------------------------------------------
# S4: restore(replan) composed with a quarantined/rotated topology
# ---------------------------------------------------------------------------

def _quarantined_snapshot(params):
    """A farm mid-life: core1 quarantined, core0 already rotated once."""
    farm = OscillatorFarm(gang=True)
    for i in range(3):
        farm.add_core(f"core{i}", params, lanes_per_client=128)
        farm.register(f"core{i}", "t", seed=40)
    farm.add_standby("core0", params, lanes_per_client=128)
    for i in range(3):
        farm.draw(f"core{i}", "t", 200)
    farm.quarantine("core0", reason="drill")
    farm.rotate("core0")
    farm.draw("core0", "t", 100)
    farm.quarantine("core1", reason="dead")
    snap = farm.snapshot()
    tail = {c: farm.draw(c, "t", 64) for c in ("core0", "core2")}
    return snap, tail


def test_restore_preserves_quarantine_set_and_rotations():
    params = default_params(system="chen")
    snap, tail = _quarantined_snapshot(params)
    assert snap["quarantined"] == ["core1"]
    assert snap["rotations"] == {"core0": 1}
    target = OscillatorFarm(gang=True)
    for i in range(3):
        target.add_core(f"core{i}", params, lanes_per_client=128)
    target.add_standby("core0", params, lanes_per_client=128)
    target.restore(snap)
    assert target.quarantined == frozenset({"core1"})
    assert target.rotations == {"core0": 1}
    with pytest.raises(CoreQuarantined):
        target.request("core1", "t", 10)
    for c in ("core0", "core2"):
        assert np.array_equal(target.draw(c, "t", 64), tail[c])


def test_restore_refuses_to_unrotate():
    params = default_params(system="chen")
    snap, _ = _quarantined_snapshot(params)
    target = OscillatorFarm(gang=True)
    for i in range(3):
        target.add_core(f"core{i}", params, lanes_per_client=128)
        target.register(f"core{i}", "t", seed=40)
    target.add_standby("core0", params, lanes_per_client=128)
    target.add_standby("core1", params, lanes_per_client=128)
    target.quarantine("core1", reason="x")
    target.rotate("core1")           # rotation the snapshot never saw
    with pytest.raises(ValueError, match="un-rotate"):
        target.restore(snap)


def test_restore_replan_across_device_counts_keeps_quarantine():
    """The S4 composition: a snapshot of a DEGRADED sharded farm restores
    onto an unsharded farm with ``on_topology_mismatch='replan'`` —
    quarantine set and rotation count survive, streams continue
    bit-exactly (device-count-invariant words)."""
    import jax
    from jax.sharding import Mesh
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 host devices — run under "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=2")
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    params = default_params(system="chen")
    farm = OscillatorFarm(gang=True)
    for i in range(2):
        farm.add_core(f"core{i}", params, lanes_per_client=128, mesh=mesh)
        farm.register(f"core{i}", "t", seed=40)
    farm.add_standby("core0", params, lanes_per_client=128, mesh=mesh)
    farm.draw("core0", "t", 200)
    farm.quarantine("core0", reason="drill")
    farm.rotate("core0")
    farm.quarantine("core1", reason="dead")
    snap = farm.snapshot()
    tail = farm.draw("core0", "t", 64)

    unsharded = OscillatorFarm(gang=True)
    for i in range(2):
        unsharded.add_core(f"core{i}", params, lanes_per_client=128)
    unsharded.add_standby("core0", params, lanes_per_client=128)
    with pytest.raises(ValueError, match="topology"):
        unsharded.restore(snap)                     # refuse by default
    unsharded.restore(snap, on_topology_mismatch="replan")
    assert unsharded.quarantined == frozenset({"core1"})
    assert unsharded.rotations == {"core0": 1}
    assert np.array_equal(unsharded.draw("core0", "t", 64), tail)

"""A flush dispatches every launch before it fetches any.

``OscillatorFarm.flush`` dispatches each launch of the flush in launch
order, then fetches and absorbs each in the same order; the first
launch's copy to the host starts at its dispatch, each next one's once
the copy before it has landed.  The words and states are those of the
per-core ``PRNGService.flush``, bit for bit, whatever the number of
launches; the tracer counts every fetch (``fetches``) and those that
began while a later launch of the flush was already dispatched
(``fetches_overlapped``).

The farm: four 3-8-3 cores that gang (one stacked launch) and one 4-16-4
core alone, two tenants each; with ``gang=False``, five launches.
"""
import jax
import numpy as np
import pytest
from jax._src.array import ArrayImpl

from repro.core.dse import Candidate
from repro.serve.farm import OscillatorFarm
from repro.serve.prng_service import PRNGService

from test_kernels import _mk

GANG = Candidate(i_dim=3, h_dim=8, p=1, compute_unit="vpu",
                 dtype_bytes=4, unroll=4, t_block=64)
SOLO = Candidate(i_dim=4, h_dim=16, p=1, compute_unit="vpu",
                 dtype_bytes=4, unroll=4, t_block=64)
#: core name -> (kind, key of its weights); the 3-8-3 cores gang
CORES = {"chen": (GANG, 10), "chua": (GANG, 11), "lorenz": (GANG, 12),
         "rossler": (GANG, 13), "hyper": (SOLO, 14)}
CLIENTS = {"t0": 60, "t1": 61}
LANES = 128
#: words each tenant asks for, flush by flush (some rows buffered over)
DRAWS = [(300, 200), (100, 420), (700, 64)]


def _params(cand, key):
    w1, b1, w2, b2, _ = _mk(cand.i_dim, cand.h_dim, 1, key=key)
    return {"w1": w1, "b1": b1, "w2": w2, "b2": b2}


def five_core_farm(gang=True, **kw):
    """Four ganging 3-8-3 cores and one 4-16-4 core, two tenants each."""
    farm = OscillatorFarm(gang=gang, **kw)
    for core, (cand, key) in CORES.items():
        farm.add_core(core, _params(cand, key), config=cand,
                      lanes_per_client=LANES, backend="pallas_interpret")
        for client, seed in CLIENTS.items():
            farm.register(core, client, seed=seed)
    return farm


def twin_services():
    """The per-core path: one ``PRNGService`` a core, flushed alone."""
    out = {}
    for core, (cand, key) in CORES.items():
        svc = PRNGService(_params(cand, key), lanes_per_client=LANES,
                          config=cand, backend="pallas_interpret")
        for client, seed in CLIENTS.items():
            svc.register(client, seed=seed)
        out[core] = svc
    return out


def _request_all(target, draws):
    for core in CORES:
        for client, n in zip(CLIENTS, draws):
            target(core, client, n)


@pytest.mark.parametrize("gang,launches", [(True, 2), (False, 5)])
def test_a_flush_serves_the_per_core_words_and_counts_its_overlap(
        gang, launches):
    farm = five_core_farm(gang=gang, profile=True)
    twins = twin_services()
    for draws in DRAWS:
        _request_all(farm.request, draws)
        _request_all(lambda core, client, n: twins[core].request(client, n),
                     draws)
        before = farm.launches, dict(farm.profile_stats)
        got = farm.flush()
        assert farm.launches - before[0] == launches
        st = farm.profile_stats
        fetched = st["fetches"] - before[1]["fetches"]
        overlapped = (st["fetches_overlapped"]
                      - before[1]["fetches_overlapped"])
        assert (fetched, overlapped) == (launches, launches - 1)
        for core, svc in twins.items():
            want = svc.flush()
            assert set(got[core]) == set(want) == set(CLIENTS)
            for client in CLIENTS:
                np.testing.assert_array_equal(got[core][client],
                                              want[client])
    for core, svc in twins.items():
        np.testing.assert_array_equal(
            np.asarray(farm.services[core].pool_x), np.asarray(svc.pool_x))


@pytest.mark.parametrize("profile", [True, False])
def test_the_second_launch_is_dispatched_before_the_first_is_fetched(
        monkeypatch, profile):
    """Traced or not: dispatch, dispatch, then each fetch in launch order;
    the first copy starts at its dispatch, the second once the first
    fetch has its words."""
    farm = five_core_farm(profile=profile)
    events = []
    tr = farm.tracer
    launched, fetch = tr.launched, tr.fetch
    copy = ArrayImpl.copy_to_host_async

    def spy_launched(mesh, axis, state):
        events.append("dispatch")
        return launched(mesh, axis, state)

    def spy_fetch(words, **kw):
        events.append("fetch")
        return fetch(words, **kw)

    def spy_copy(self):
        events.append("copy")
        return copy(self)

    monkeypatch.setattr(tr, "launched", spy_launched)
    monkeypatch.setattr(tr, "fetch", spy_fetch)
    monkeypatch.setattr(ArrayImpl, "copy_to_host_async", spy_copy)
    _request_all(farm.request, DRAWS[0])
    farm.flush()
    assert events == ["dispatch", "copy", "dispatch", "fetch", "copy",
                      "fetch"]


def test_a_one_launch_flush_counts_no_overlap():
    farm = five_core_farm(profile=True)
    farm.request("hyper", "t0", 500)
    farm.flush()
    farm.draw("chen", "t1", 300)          # the service's own flush
    st = farm.profile_stats
    assert farm.launches == 2
    assert (st["fetches"], st["fetches_overlapped"]) == (2.0, 0.0)


def test_an_untraced_farm_serves_the_traced_farms_words():
    traced, untraced = five_core_farm(profile=True), five_core_farm()
    _request_all(traced.request, DRAWS[1])
    _request_all(untraced.request, DRAWS[1])
    a, b = traced.flush(), untraced.flush()
    assert untraced.profile_stats is None
    for core in CORES:
        for client in CLIENTS:
            np.testing.assert_array_equal(a[core][client], b[core][client])
    assert all(isinstance(w, np.ndarray) and not isinstance(w, jax.Array)
               for per_core in b.values() for w in per_core.values())

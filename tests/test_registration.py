"""Tenant registration that scales: O(T) host work, a fixed number of
compiles, and every stream exactly what it was.

``PRNGService.register`` records a tenant; its lane block is seeded,
burned in and appended to the pool the next time the pool is read, all
joining blocks in one launch.  Lanes evolve independently, so a tenant's
words and state are those of a service that registered it alone, and
those the service gave when each tenant was burned in and concatenated
on its own (the goldens below were taken from that implementation).
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve.prng_service import PRNGService

from test_kernels import _mk

#: sha256 prefixes of the pool after registering 64 tenants, of every
#: tenant's words from one flush, and of the pool after it, and the first
#: words of the first and last tenant, from the per-tenant implementation.
GOLDEN = {
    "float32": {"state0": "435df8970735a7b9", "words": "ee7e05e6010cdaf2",
                "state1": "90887667892af0bd",
                "t00": [1788875268, 1922341805, 3512556999],
                "t63": [2566113352, 3699616735, 453144149]},
    "bfloat16": {"state0": "00a4a501be5ff8d5", "words": "f6afca0ed9dd5167",
                 "state1": "ba00f47abbb32d85",
                 "t00": [107911810, 2858667151, 107911810],
                 "t63": [2858667151, 107911810, 107911810]},
}


@pytest.fixture(scope="module")
def params():
    w1, b1, w2, b2, _ = _mk(3, 8, 1)
    return {"w1": w1, "b1": b1, "w2": w2, "b2": b2}


def _service(params, **kw):
    return PRNGService(params, lanes_per_client=128,
                       backend="pallas_interpret", **kw)


def _digest(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]


def _draw_all(svc, n_tenants):
    for i in range(n_tenants):
        svc.request(f"t{i:02d}", 256 + 128 * (i % 3))
    got = svc.flush()
    return [got[f"t{i:02d}"] for i in range(n_tenants)]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_64_tenants_give_the_words_they_gave_before(params, dtype):
    svc = _service(params, dtype=jnp.dtype(dtype))
    for i in range(64):
        svc.register(f"t{i:02d}", seed=1000 + 7 * i)
    want = GOLDEN[dtype]
    assert _digest(svc.pool_x) == want["state0"]
    words = _draw_all(svc, 64)
    assert [int(w) for w in words[0][:3]] == want["t00"]
    assert [int(w) for w in words[63][:3]] == want["t63"]
    assert _digest(np.concatenate(words)) == want["words"]
    assert _digest(svc.pool_x) == want["state1"]


def test_each_tenant_matches_a_service_of_its_own(params):
    svc = _service(params)
    for i in range(64):
        svc.register(f"t{i:02d}", seed=1000 + 7 * i)
    words = _draw_all(svc, 64)
    pool = np.asarray(svc.pool_x)
    for i in range(64):
        alone = _service(params)
        alone.register(f"t{i:02d}", seed=1000 + 7 * i)
        mine = alone.draw(f"t{i:02d}", 256 + 128 * (i % 3))
        np.testing.assert_array_equal(mine, words[i])
        np.testing.assert_array_equal(np.asarray(alone.pool_x),
                                      pool[i * 128:(i + 1) * 128])


def test_snapshot_and_replay_after_registration_continue_bit_exactly(params):
    svc = _service(params)
    for i in range(8):
        svc.register(f"t{i}", seed=50 + i)
    snap = svc.snapshot()
    restored = _service(params)
    restored.restore(snap)
    for s in (svc, restored):
        s.register("late", seed=99)          # joins after the restore too
    for name in ("t3", "late", "t3"):
        np.testing.assert_array_equal(svc.draw(name, 600),
                                      restored.draw(name, 600))
    # a fresh service replays t3 to its position and continues with it
    replayed = _service(params)
    for i in range(8):
        replayed.register(f"t{i}", seed=50 + i)
    c = svc.clients["t3"]
    replayed.replay_client("t3", row=c.row, buf_words=len(c.buf))
    np.testing.assert_array_equal(replayed.draw("t3", 900),
                                  svc.draw("t3", 900))


def _compiles_to_register(params, n_tenants: int) -> int:
    """Compiles from registering ``n_tenants`` one by one until the pool
    is read."""
    seen = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        svc = _service(params)
        for i in range(n_tenants):
            svc.register(f"t{i}", seed=3 * i + n_tenants)
        jax.block_until_ready(svc.pool_x)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    return len(seen)


def test_registration_compiles_do_not_grow_with_the_tenant_count(params):
    # pool sizes no other test uses, after one registration that compiles
    # the programs whose shapes do not depend on the pool size
    _compiles_to_register(params, 9)
    n19 = _compiles_to_register(params, 19)
    n71 = _compiles_to_register(params, 71)
    assert n19 == n71
    assert 0 < n71 <= 32

"""PRNG serving engine: batched multi-client launches, determinism,
resumability, and the sharded stream-pool path."""
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.prng.stream import ChaoticPRNG
from repro.serve.prng_service import PRNGService

from test_kernels import _mk


@pytest.fixture(scope="module")
def params():
    w1, b1, w2, b2, _ = _mk(3, 8, 1)
    return {"w1": w1, "b1": b1, "w2": w2, "b2": b2}


def _service(params, **kw):
    return PRNGService(params, lanes_per_client=128,
                       backend="pallas_interpret", **kw)


def test_eight_clients_one_launch(params):
    svc = _service(params)
    for i in range(8):
        svc.register(f"c{i}", seed=100 + i)
    for i in range(8):
        svc.request(f"c{i}", 400 + 31 * i)
    out = svc.flush()
    assert svc.launches == 1
    assert {k: v.size for k, v in out.items()} == {
        f"c{i}": 400 + 31 * i for i in range(8)}
    # all streams distinct
    heads = [tuple(v[:16]) for v in out.values()]
    assert len(set(heads)) == 8


def test_client_matches_standalone_stream(params):
    """A served stream == a standalone engine with the same seed/config."""
    svc = _service(params)
    for i in range(8):
        svc.register(f"c{i}", seed=40 + i)
    for i in range(8):
        svc.request(f"c{i}", 700)
    out = svc.flush()
    eng = ChaoticPRNG(params, n_streams=128, backend="pallas_interpret",
                      config=svc.config)
    alone, _ = eng.next_words(eng.init(seed=43), 700)
    np.testing.assert_array_equal(out["c3"], alone)


def test_stream_independent_of_cotenants_and_batching(params):
    svc_a = _service(params)
    svc_a.register("x", seed=7)
    for i in range(5):
        svc_a.register(f"noise{i}", seed=i)
    svc_a.request("x", 200)
    svc_a.request("noise2", 5000)          # forces a much larger launch
    first = svc_a.flush()["x"]
    rest = svc_a.draw("x", 800)

    svc_b = _service(params)
    svc_b.register("x", seed=7)
    whole = svc_b.draw("x", 1000)
    np.testing.assert_array_equal(np.concatenate([first, rest]), whole)


def test_snapshot_restore_resumes_bit_exactly(params):
    svc = _service(params)
    for i in range(3):
        svc.register(f"c{i}", seed=i)
    svc.draw("c1", 333)
    snap = svc.snapshot()
    a = svc.draw("c1", 500)
    svc2 = _service(params)
    svc2.restore(snap)
    b = svc2.draw("c1", 500)
    np.testing.assert_array_equal(a, b)
    assert svc2.launches == svc.launches  # both did one post-snapshot launch


def test_snapshot_between_request_and_flush_keeps_pending(params):
    """Regression: a snapshot taken after request() but before flush() must
    carry the queued draw — restore() used to silently drop it."""
    svc = _service(params)
    svc.register("a", seed=1)
    svc.register("b", seed=2)
    svc.draw("a", 120)
    svc.request("a", 250)                  # in flight
    svc.request("b", 75)
    snap = svc.snapshot()
    out_a = svc.flush()

    svc2 = _service(params)
    svc2.restore(snap)
    assert svc2.clients["a"].pending == 250
    assert svc2.clients["b"].pending == 75
    out_b = svc2.flush()
    assert set(out_a) == set(out_b) == {"a", "b"}
    for name in out_a:
        np.testing.assert_array_equal(out_a[name], out_b[name])


def test_snapshot_restores_outbox_and_pending_roundtrip(params):
    """draw() for one client parks a co-tenant's served words in the outbox;
    snapshot/restore must preserve both outbox and pending invariants."""
    svc = _service(params)
    svc.register("a", seed=1)
    svc.register("b", seed=2)
    svc.request("a", 300)
    svc.draw("b", 200)                     # a's words now parked in outbox
    snap = svc.snapshot()
    svc2 = _service(params)
    svc2.restore(snap)
    a1 = svc.flush()["a"]
    a2 = svc2.flush()["a"]
    np.testing.assert_array_equal(a1, a2)
    solo = _service(params)
    solo.register("a", seed=1)
    np.testing.assert_array_equal(a1, solo.draw("a", 300))


def test_register_duplicate_raises(params):
    svc = _service(params)
    svc.register("a", seed=0)
    with pytest.raises(ValueError):
        svc.register("a", seed=1)


def test_default_seeds_are_per_client(params):
    """Clients registered without a seed must not share a stream."""
    svc = _service(params)
    svc.register("alice")
    svc.register("bob")
    svc.request("alice", 200)
    svc.request("bob", 200)
    out = svc.flush()
    assert not np.array_equal(out["alice"], out["bob"])


def test_idle_clients_frozen(params):
    """Idle clients neither buffer overdraw nor advance their streams."""
    svc = _service(params)
    svc.register("busy", seed=1)
    svc.register("idle", seed=2)
    for _ in range(3):
        svc.draw("busy", 3000)
    idle = svc.clients["idle"]
    assert len(idle.buf) == 0 and idle.row == 0
    # the idle client's stream is untouched by the co-tenant's draws
    solo = _service(params)
    solo.register("idle", seed=2)
    np.testing.assert_array_equal(svc.draw("idle", 500),
                                  solo.draw("idle", 500))


def test_idle_rollback_compiles_nothing_per_idle_count(params):
    """Freezing idle clients is one program per pool shape: once a flush
    with idle clients has run, flushes with other counts of idle clients
    compile nothing, and each idle client is still frozen."""
    svc = _service(params)
    for i in range(6):
        svc.register(f"c{i}", seed=40 + i)
    compiles = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    def flush_first(k):
        for i in range(k):
            svc.request(f"c{i}", 512)
        svc.flush()

    flush_first(5)
    flush_first(6)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for k in (4, 1, 3, 2):
            flush_first(k)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []
    assert [svc.clients[f"c{i}"].row for i in range(6)] == [24, 20, 16, 12,
                                                            8, 4]


def test_draw_never_drops_cotenant_requests(params):
    """A draw()-triggered flush parks other clients' served words in the
    outbox instead of discarding them; a later flush delivers them."""
    svc = _service(params)
    svc.register("a", seed=1)
    svc.register("b", seed=2)
    svc.request("a", 300)
    got_b = svc.draw("b", 200)         # serves a's request too
    assert got_b.size == 200
    got_a = svc.flush()["a"]           # a's words arrive, not dropped
    solo = _service(params)
    solo.register("a", seed=1)
    np.testing.assert_array_equal(got_a, solo.draw("a", 300))


def test_draw_after_own_request_returns_only_new_words(params):
    svc = _service(params)
    svc.register("a", seed=1)
    svc.request("a", 150)
    got = svc.draw("a", 100)           # must be words 150..250, not 0..250
    assert got.size == 100
    solo = _service(params)
    solo.register("a", seed=1)
    whole = solo.draw("a", 250)
    np.testing.assert_array_equal(got, whole[150:])
    np.testing.assert_array_equal(svc.flush()["a"], whole[:150])


def test_small_draw_does_not_pay_full_time_block(params):
    """A 10-word request must not compute/buffer a whole autotuned time
    block (t_block=256 would mean 128 rows = 16k words for one client);
    small launches shrink to the next power of two of the needed rows."""
    svc = _service(params)
    svc.register("a", seed=1)
    got = svc.draw("a", 10)
    assert got.size == 10
    assert len(svc.clients["a"].buf) <= 4 * svc.lanes_per_client - 10
    # and the small-draw stream still matches a large-draw replay
    solo = _service(params)
    solo.register("a", seed=1)
    np.testing.assert_array_equal(got, solo.draw("a", 2000)[:10])


def test_zero_and_negative_draws(params):
    svc = _service(params)
    svc.register("a", seed=0)
    z = svc.draw("a", 0)
    assert z.shape == (0,) and z.dtype == np.uint32
    assert svc.launches == 0               # zero draw must not launch
    with pytest.raises(ValueError):
        svc.draw("a", -1)
    with pytest.raises(KeyError):
        svc.draw("ghost", 0)


def test_sharded_pool_matches_unsharded(params):
    """shard_map over the stream axis is exact (single-device mesh here;
    the multi-device case runs in a subprocess below)."""
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    svc_m = _service(params, mesh=mesh)
    svc_u = _service(params)
    for svc in (svc_m, svc_u):
        svc.register("a", seed=1)
        svc.register("b", seed=2)
    np.testing.assert_array_equal(svc_m.draw("a", 400), svc_u.draw("a", 400))


_MULTIDEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro.serve.prng_service import PRNGService

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    params = {"w1": jax.random.normal(ks[0], (3, 8)) * 0.5,
              "b1": jax.random.normal(ks[1], (8,)) * 0.1,
              "w2": jax.random.normal(ks[2], (8, 3)) * 0.5,
              "b2": jax.random.normal(ks[3], (3,)) * 0.1}
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
    kw = dict(lanes_per_client=128, backend="pallas_interpret")
    svc_m = PRNGService(params, mesh=mesh, **kw)
    svc_u = PRNGService(params, **kw)
    for svc in (svc_m, svc_u):
        for i in range(4):
            svc.register(f"c{i}", seed=i)
    a = svc_m.draw("c2", 600)
    b = svc_u.draw("c2", 600)
    assert np.array_equal(a, b)
    print("SHARDED OK")
""")


def test_sharded_pool_multidevice():
    """4-device shard_map pool == single-device pool, bitwise."""
    r = subprocess.run([sys.executable, "-c", _MULTIDEV_SCRIPT],
                       capture_output=True, text=True, timeout=560,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu", "HOME": "/root"})
    assert r.returncode == 0, (r.stdout[-800:], r.stderr[-3000:])
    assert "SHARDED OK" in r.stdout

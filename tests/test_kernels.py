"""Pallas chaotic-ANN kernel vs the pure-jnp oracle: shape/dtype sweep in
interpret mode (per-kernel allclose requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propshim import given, settings, strategies as st

from repro.kernels.chaotic_ann import chaotic_ann_pallas
from repro.kernels.ops import bits_from_trajectory, chaotic_trajectory
from repro.kernels.ref import chaotic_ann_ref


def _mk(i_dim, h_dim, s, key=0, scale=0.5):
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    w1 = jax.random.normal(ks[0], (i_dim, h_dim)) * scale
    b1 = jax.random.normal(ks[1], (h_dim,)) * 0.1
    w2 = jax.random.normal(ks[2], (h_dim, i_dim)) * scale
    b2 = jax.random.normal(ks[3], (i_dim,)) * 0.1
    x0 = jax.random.normal(ks[4], (s, i_dim)) * 0.5
    return w1, b1, w2, b2, x0


SWEEP = [
    # (I, H, S, T, s_block, t_block, unroll, unit)
    (3, 4, 128, 32, 128, 32, 1, "vpu"),
    (3, 8, 256, 64, 128, 32, 2, "vpu"),
    (3, 16, 256, 64, 256, 64, 4, "vpu"),
    (3, 8, 256, 64, 256, 32, 1, "mxu"),
    (4, 8, 384, 48, 128, 16, 4, "mxu"),
    (6, 32, 128, 32, 128, 32, 8, "vpu"),
    (2, 4, 512, 16, 256, 16, 16, "vpu"),
]


@pytest.mark.parametrize("i,h,s,t,sb,tb,un,unit", SWEEP)
def test_kernel_matches_ref_sweep(i, h, s, t, sb, tb, un, unit):
    w1, b1, w2, b2, x0 = _mk(i, h, s)
    got = chaotic_ann_pallas(w1, b1, w2, b2, x0, n_steps=t, s_block=sb,
                             t_block=tb, unroll=un, compute_unit=unit,
                             interpret=True)
    want = chaotic_ann_ref(w1, b1, w2, b2, x0, t)
    assert got.shape == want.shape == (t, s, i)
    # chaotic divergence amplifies fp reordering ~exp(λt) (λ up to ~2/step
    # for random weights); only a short prefix is bitwise-comparable.
    np.testing.assert_allclose(np.asarray(got[:4]), np.asarray(want[:4]),
                               atol=5e-4)
    assert bool(jnp.all(jnp.isfinite(got)))


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 5e-5), (jnp.bfloat16, 5e-2)])
def test_kernel_dtypes(dtype, atol):
    w1, b1, w2, b2, x0 = _mk(3, 8, 128)
    x0 = x0.astype(dtype)
    got = chaotic_ann_pallas(w1, b1, w2, b2, x0, n_steps=16, s_block=128,
                             t_block=16, interpret=True)
    want = chaotic_ann_ref(w1, b1, w2, b2, x0, 16)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got[:4], np.float32),
                               np.asarray(want[:4], np.float32), atol=atol)


def test_kernel_non_divisible_streams_padded():
    """S not a multiple of s_block: padding streams must not leak."""
    w1, b1, w2, b2, x0 = _mk(3, 8, 200)
    got = chaotic_ann_pallas(w1, b1, w2, b2, x0, n_steps=8, s_block=128,
                             t_block=8, interpret=True)
    want = chaotic_ann_ref(w1, b1, w2, b2, x0, 8)
    assert got.shape == (8, 200, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)


def test_kernel_nonpow2_tblock_padding():
    """n_steps not a multiple of t_block."""
    w1, b1, w2, b2, x0 = _mk(3, 8, 128)
    got = chaotic_ann_pallas(w1, b1, w2, b2, x0, n_steps=25, s_block=128,
                             t_block=16, interpret=True)
    want = chaotic_ann_ref(w1, b1, w2, b2, x0, 25)
    assert got.shape == (25, 128, 3)
    np.testing.assert_allclose(np.asarray(got[:4]), np.asarray(want[:4]), atol=5e-4)


@settings(max_examples=15, deadline=None)
@given(
    i=st.integers(2, 6), h=st.sampled_from([4, 8, 12, 16]),
    t=st.sampled_from([4, 8, 16]),
    unit=st.sampled_from(["vpu", "mxu"]),
    act=st.sampled_from(["relu", "tanh", "sigmoid"]),
)
def test_kernel_property_sweep(i, h, t, unit, act):
    """Property: for any tiny (I,H), activation and unit, the kernel equals
    the oracle over a short horizon."""
    w1, b1, w2, b2, x0 = _mk(i, h, 128, key=i * 31 + h)
    got = chaotic_ann_pallas(w1, b1, w2, b2, x0, n_steps=t, s_block=128,
                             t_block=t, activation=act, compute_unit=unit,
                             interpret=True)
    want = chaotic_ann_ref(w1, b1, w2, b2, x0, t, act)
    np.testing.assert_allclose(np.asarray(got[:4]), np.asarray(want[:4]),
                               atol=1e-4)


def test_ops_backend_selection():
    w1, b1, w2, b2, x0 = _mk(3, 8, 128)
    params = {"w1": w1, "b1": b1, "w2": w2, "b2": b2}
    a = chaotic_trajectory(params, x0, 16, backend="ref")
    b = chaotic_trajectory(params, x0, 16, backend="pallas_interpret",
                           s_block=128, t_block=16)
    np.testing.assert_allclose(np.asarray(a[:4]), np.asarray(b[:4]), atol=5e-5)


def test_bits_deterministic_and_balanced():
    w1, b1, w2, b2, x0 = _mk(3, 8, 256)
    params = {"w1": w1, "b1": b1, "w2": w2, "b2": b2}
    traj = chaotic_trajectory(params, x0, 512, backend="ref")
    bits1 = bits_from_trajectory(traj)
    bits2 = bits_from_trajectory(traj)
    assert bits1.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(bits1), np.asarray(bits2))
    ones = np.unpackbits(np.asarray(bits1).view(np.uint8)).mean()
    assert abs(ones - 0.5) < 0.02


def _rounding_cases(kind):
    """f32 bit patterns: random words, exact and near ties of both parities
    of the kept bit, and the specials (+-0, +-inf, NaNs with payloads in
    the dropped bits, subnormals, the largest finites that round to inf)."""
    rng = np.random.default_rng(0)
    if kind == "random":
        return rng.integers(0, 2 ** 32, 100_000, dtype=np.uint64).astype(
            np.uint32)
    if kind == "ties":
        hi = rng.integers(0, 2 ** 16, 4096, dtype=np.uint32) << 16
        return np.concatenate([hi | np.uint32(lo) for lo in
                               (0x8000, 0x7FFF, 0x8001, 0x0000, 0xFFFF)])
    return np.array([0x00000000, 0x80000000, 0x7F800000, 0xFF800000,
                     0x7FC00000, 0xFFC00000, 0x7F800001, 0x7F808000,
                     0x7FFFFFFF, 0xFFFF8000, 0x00000001, 0x00008000,
                     0x00018000, 0x007FFFFF, 0x007F8000, 0x80008000,
                     0x00800000, 0x7F7FFFFF, 0x7F7F8000, 0xFF7FFFFF],
                    np.uint32)


@pytest.mark.parametrize("kind", ["random", "ties", "specials"])
def test_round_bf16_bits_equals_reduce_precision(kind):
    """The integer bf16 rounding the kernels use in place of
    ``reduce_precision`` (which Mosaic cannot lower) equals it bit for
    bit, in the kernel's own (jitted) and in eager form."""
    from repro.kernels.chaotic_ann import round_bf16_bits
    v = jnp.asarray(_rounding_cases(kind).view(np.float32))
    want = np.asarray(jax.lax.reduce_precision(v, 8, 7)).view(np.uint32)
    for fn in (round_bf16_bits, jax.jit(round_bf16_bits)):
        got = np.asarray(fn(v)).view(np.uint32)
        np.testing.assert_array_equal(got, want)


def test_resolve_backend_decides_auto_once():
    from repro.kernels.ops import resolve_backend
    want = "pallas" if jax.default_backend() == "tpu" else "pallas_interpret"
    assert resolve_backend() == resolve_backend("auto") == want
    for b in ("pallas", "pallas_interpret", "ref"):
        assert resolve_backend(b) == b
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("interpret")

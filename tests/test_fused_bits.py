"""Fused bit-extraction kernel vs the unfused trajectory->pack pipeline.

Equivalence contract: for the SAME float trajectory, the in-kernel packing
(fold16 + Weyl + Murmur3) is bit-exact with ``ops.bits_from_trajectory``.
The mxu compute path reproduces the pure-jnp oracle's floats bit-for-bit on
CPU, so there the fused words also equal the all-reference pipeline; the
vpu path's broadcast-FMA ordering differs from the oracle matmul by ~1 ulp,
which chaos amplifies — for it the contract is stated against the unfused
kernel trajectory (same fp order), which is the packing-correctness claim.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.chaotic_ann import chaotic_ann_bits_pallas, chaotic_ann_pallas
from repro.kernels.ops import bits_from_trajectory, chaotic_bits, pack_words
from repro.kernels.ref import chaotic_ann_ref

from test_kernels import SWEEP, _mk


@pytest.mark.parametrize("i,h,s,t,sb,tb,un,unit", SWEEP)
def test_fused_equals_unfused_packing_sweep(i, h, s, t, sb, tb, un, unit):
    """Fused kernel == bits_from_trajectory over its own trajectory, bitwise."""
    w1, b1, w2, b2, x0 = _mk(i, h, s)
    words, final = chaotic_ann_bits_pallas(
        w1, b1, w2, b2, x0, n_steps=t, s_block=sb, t_block=tb, unroll=un,
        compute_unit=unit, interpret=True)
    traj = chaotic_ann_pallas(w1, b1, w2, b2, x0, n_steps=t, s_block=sb,
                              t_block=tb, unroll=un, compute_unit=unit,
                              interpret=True)
    assert words.dtype == jnp.uint32 and words.shape == (t // 2, s)
    np.testing.assert_array_equal(np.asarray(words),
                                  np.asarray(bits_from_trajectory(traj)))
    # The final-state output is the resume handle: it must be the last
    # trajectory sample exactly.
    np.testing.assert_array_equal(np.asarray(final), np.asarray(traj[-1]))


@pytest.mark.parametrize("i,h,s,t,sb,tb,un", [
    (3, 8, 256, 64, 256, 32, 1),
    (4, 8, 384, 48, 128, 16, 4),
])
def test_fused_mxu_equals_reference_pipeline(i, h, s, t, sb, tb, un):
    """mxu fused words == bits_from_trajectory(chaotic_ann_ref(...)), bitwise."""
    w1, b1, w2, b2, x0 = _mk(i, h, s)
    words, _ = chaotic_ann_bits_pallas(
        w1, b1, w2, b2, x0, n_steps=t, s_block=sb, t_block=tb, unroll=un,
        compute_unit="mxu", interpret=True)
    ref_words = bits_from_trajectory(chaotic_ann_ref(w1, b1, w2, b2, x0, t))
    np.testing.assert_array_equal(np.asarray(words), np.asarray(ref_words))


def test_vpu_vs_mxu_agreement():
    """vpu and mxu agree on the trajectory (pre-divergence window) and each
    is bit-exact with its own unfused packing; both word streams are
    monobit-balanced (the fp-order 1-ulp difference decorrelates the low
    mantissa bits, so bitwise word equality across units is not a claim)."""
    w1, b1, w2, b2, x0 = _mk(3, 8, 256)
    out = {}
    for unit in ("vpu", "mxu"):
        traj = chaotic_ann_pallas(w1, b1, w2, b2, x0, n_steps=64, s_block=128,
                                  t_block=32, compute_unit=unit, interpret=True)
        words, _ = chaotic_ann_bits_pallas(
            w1, b1, w2, b2, x0, n_steps=64, s_block=128, t_block=32,
            compute_unit=unit, interpret=True)
        np.testing.assert_array_equal(np.asarray(words),
                                      np.asarray(bits_from_trajectory(traj)))
        out[unit] = (np.asarray(traj), np.asarray(words))
    np.testing.assert_allclose(out["vpu"][0][:4], out["mxu"][0][:4], atol=5e-4)
    for unit, (_, words) in out.items():
        ones = np.unpackbits(words.view(np.uint8)).mean()
        assert abs(ones - 0.5) < 0.02, (unit, ones)


def test_word_offset_resumes_weyl_sequence():
    """Chunked draws with carried (state, offset) == one long draw, bitwise."""
    w1, b1, w2, b2, x0 = _mk(3, 8, 128)
    params = {"w1": w1, "b1": b1, "w2": w2, "b2": b2}
    full, _ = chaotic_bits(params, x0, 96, backend="pallas_interpret",
                           s_block=128, t_block=32)
    a, s1 = chaotic_bits(params, x0, 32, backend="pallas_interpret",
                         s_block=128, t_block=32)
    b, s2 = chaotic_bits(params, s1, 64, 16, backend="pallas_interpret",
                         s_block=128, t_block=32)
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(a), np.asarray(b)]), np.asarray(full))


def test_pack_words_matches_bits_from_trajectory():
    w1, b1, w2, b2, x0 = _mk(3, 8, 64)
    traj = chaotic_ann_ref(w1, b1, w2, b2, x0, 32)
    np.testing.assert_array_equal(np.asarray(pack_words(traj, 0)),
                                  np.asarray(bits_from_trajectory(traj)))
    # per-stream offsets: each column continues its own Weyl sequence
    off = jnp.arange(64, dtype=jnp.uint32)
    shifted = pack_words(traj, off)
    assert shifted.shape == (16, 64)
    base = pack_words(traj, 0)
    assert not np.array_equal(np.asarray(shifted), np.asarray(base))
    np.testing.assert_array_equal(np.asarray(shifted[:, 0]),
                                  np.asarray(base[:, 0]))  # offset 0 column


def test_pack_words_packs_any_stream_layout():
    """One stream's (T, I) samples and a stacked (T, C, S, I) pool pack
    as the same streams do in a (T, S, I) pool, offsets included."""
    w1, b1, w2, b2, x0 = _mk(3, 8, 64)
    traj = chaotic_ann_ref(w1, b1, w2, b2, x0, 32)
    off = jnp.arange(64, dtype=jnp.uint32) * 7
    flat = np.asarray(pack_words(traj, off))
    one = pack_words(traj[:, 5], off[5])
    assert one.shape == (16,)
    np.testing.assert_array_equal(np.asarray(one), flat[:, 5])
    stacked = pack_words(traj.reshape(32, 4, 16, 3), off.reshape(4, 16))
    np.testing.assert_array_equal(np.asarray(stacked).reshape(16, 64), flat)
    np.testing.assert_array_equal(
        np.asarray(bits_from_trajectory(traj.reshape(32, 4, 16, 3))),
        np.asarray(pack_words(traj, 0)).reshape(16, 4, 16))


def test_uniform_from_trajectory_signature_and_range():
    """Regression: the dead (ignored) `scale_bits` parameter is gone — the
    signature no longer advertises a knob that does nothing."""
    import inspect
    from repro.kernels import ops
    assert "scale_bits" not in inspect.signature(
        ops.uniform_from_trajectory).parameters
    w1, b1, w2, b2, x0 = _mk(3, 8, 64)
    traj = chaotic_ann_ref(w1, b1, w2, b2, x0, 32)
    u = np.asarray(ops.uniform_from_trajectory(traj))
    assert u.shape == (16, 64)
    assert u.min() >= 0.0 and u.max() < 1.0


def test_fused_backend_dispatch_and_validation():
    w1, b1, w2, b2, x0 = _mk(3, 8, 128)
    params = {"w1": w1, "b1": b1, "w2": w2, "b2": b2}
    with pytest.raises(ValueError):
        chaotic_ann_bits_pallas(w1, b1, w2, b2, x0, n_steps=33, interpret=True)
    words, state = chaotic_bits(params, x0, 32, backend="ref")
    assert words.shape == (16, 128) and state.shape == (128, 3)


def test_fused_bf16_carries_real_entropy():
    """bf16 words come from the bf16 mantissa (bitcast at half width), not
    from a zero-entropy f32 upcast: streams must differ from each other and
    from the pure counter hash, stay bit-exact with the unfused packing,
    and stay balanced."""
    w1, b1, w2, b2, x0 = _mk(3, 8, 128)
    xb = x0.astype(jnp.bfloat16)
    words, state = chaotic_ann_bits_pallas(
        w1, b1, w2, b2, xb, n_steps=64, s_block=128, t_block=32,
        interpret=True)
    assert words.shape == (32, 128)
    assert state.dtype == jnp.bfloat16
    traj = chaotic_ann_pallas(w1, b1, w2, b2, xb, n_steps=64, s_block=128,
                              t_block=32, interpret=True)
    np.testing.assert_array_equal(np.asarray(words),
                                  np.asarray(bits_from_trajectory(traj)))
    w = np.asarray(words)
    # a zero-entropy fold would make every stream's word row identical
    assert np.unique(w, axis=1).shape[1] > 1
    ones = np.unpackbits(w.view(np.uint8)).mean()
    assert abs(ones - 0.5) < 0.05, ones

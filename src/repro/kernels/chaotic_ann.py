"""Pallas TPU kernel: fused ANN-based chaotic oscillator (the HENNC core).

TPU adaptation of the paper's chaotic unit (Fig. 1).  On FPGA the unit is a
MAC array with parallelism ``P`` multipliers; on TPU the throughput unit is a
*block of independent oscillator streams* mapped onto the vector lanes:

  - streams live on the 128-wide lane axis (``s_block`` a multiple of 128),
  - the I/H feature dims live on the 8-deep sublane axis,
  - the oscillator state is carried in a VMEM scratch buffer across the whole
    time grid — the feedback path (output -> next input) never touches HBM,
  - only finished trajectory blocks (t_block steps) are streamed out to HBM.

Two compute-unit modes, mirroring the paper's DSP-vs-LUT choice:
  - ``vpu``: the two tiny matmuls are computed as I (resp. H) broadcast
    fused-multiply-adds over (H, s_block) / (I, s_block) vregs — full lane
    utilization, no MXU padding waste (I, H << 128).
  - ``mxu``: ``jnp.dot`` — contraction dims are MXU-padded to 128; wasteful
    for I=3 but included as a real design-space axis (it wins for large H).

Grid: (S/s_block, T/t_block); the T axis iterates fastest (TPU grids execute
sequentially minor-to-major), so the per-stream-block state scratch is
initialized at t==0 and carried across t blocks.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.core.dse import VMEM_USABLE

LANES = 128
SUBLANES = 8

# Every kernel's scoped-VMEM limit: the one budget the DSE's ``fits_vmem``,
# ``stacked_gang_vmem_bytes`` and the gang planner check against.  Without
# it Mosaic applies its default 16 MiB scoped limit.
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_USABLE)

# Every single-device launch is jitted with its kernel config static.
_launch_jit = functools.partial(
    jax.jit, static_argnames=("n_steps", "s_block", "t_block", "unroll",
                              "activation", "compute_unit", "lattice",
                              "interpret"))


def _activation(name: str):
    return {"relu": jax.nn.relu, "tanh": jnp.tanh, "sigmoid": jax.nn.sigmoid}[name]


def _pad_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _grid_dims(n_nodes: int) -> tuple:
    """Most-square P x Q factorization for grid topology (must match
    ``repro.core.chaotic._grid_shape`` — same operator, two layouts)."""
    p = max(1, int(math.isqrt(n_nodes)))
    while n_nodes % p:
        p -= 1
    return p, n_nodes // p


def _lattice_delta(x, lattice):
    """Diffusive-coupling increment of a block-coupled lattice, as wrapped
    sublane rolls — the VPU form of the block-sparse coupling operator.

    x: (R, ...) with the lattice component on the leading axis and R a
    whole number of ``period = n_nodes * base_dim`` row groups — (I, s)
    for the solo kernel, (I, C, s) for the stacked gang; the node index is
    periodic per group, so ONE formula serves every layout.  Each
    component row r accumulates its graph neighbours:
    ``delta[r] = strength * (sum_neighbours x[r'] - deg * x[r])``, where
    neighbour rows are reached by rolling the whole block by +-stride and
    correcting the ring-wrap rows with an iota mask (1-D iota is illegal
    on TPU; ``broadcasted_iota`` over (R, 1, ...)).  Exactly the same jnp
    expression runs in every kernel AND the ``ref`` backend scan, so the
    coupled step is bitwise identical across all of them.
    """
    n_nodes, base_dim, topology, strength = lattice
    period = n_nodes * base_dim
    r = jax.lax.broadcasted_iota(
        jnp.int32, (x.shape[0],) + (1,) * (x.ndim - 1), 0)
    node = (r % period) // base_dim

    def ring_pair(idx, n_ring, stride):
        prev = jnp.where(idx == 0,
                         jnp.roll(x, -(n_ring - 1) * stride, axis=0),
                         jnp.roll(x, stride, axis=0))
        nxt = jnp.where(idx == n_ring - 1,
                        jnp.roll(x, (n_ring - 1) * stride, axis=0),
                        jnp.roll(x, -stride, axis=0))
        return prev + nxt

    if topology == "ring":
        acc = ring_pair(node, n_nodes, base_dim)
        deg = 2
    else:  # grid: P x Q torus, two nested rings
        pp, qq = _grid_dims(n_nodes)
        acc = (ring_pair(node // qq, pp, qq * base_dim)
               + ring_pair(node % qq, qq, base_dim))
        deg = 4
    eps = jnp.asarray(strength, x.dtype)
    return (acc - deg * x) * eps


def _check_lattice(lattice, i_dim: int, i_pad: int):
    """Validate the static lattice descriptor against the kernel dims."""
    n_nodes, base_dim, _topo, _eps = lattice
    if n_nodes * base_dim != i_dim:
        raise ValueError(f"lattice {n_nodes}x{base_dim} != i_dim {i_dim}")
    if i_pad != i_dim:
        raise ValueError(
            f"lattice state dim {i_dim} must be a whole number of sublanes "
            f"(got padding to {i_pad}); the wrapped-roll coupling cannot "
            f"cross padding rows")


def _round_half(v, dtype):
    """Round an f32 accumulator to a half-width state dtype, non-elidably.

    XLA's allow-excess-precision pass may cancel a bf16 round trip — the
    ``convert(f32->bf16)`` every ``preferred_element_type=f32`` matmul
    boundary emits, feeding the next step's ``convert(bf16->f32)`` — so a
    multi-step kernel body can carry MORE precision between steps than a
    one-step-per-carry scan, silently breaking bitwise kernel/ref identity
    (the carry of a scan is materialized at bf16; a fused body's isn't).
    Rounding on the integer bits (``round_bf16_bits``) cannot be elided,
    so the state rounds exactly once per step everywhere.  f32 states pass
    through untouched.
    """
    if jnp.dtype(dtype) == jnp.bfloat16:
        v = round_bf16_bits(v.astype(jnp.float32))
    return v.astype(dtype)


def round_bf16_bits(v):
    """f32 -> f32 rounded to bf16 precision, on the integer bits.

    Round to nearest, ties to even: add ``0x7FFF`` plus the lowest kept
    mantissa bit, then clear the 16 dropped bits.  Bit for bit equal to
    ``lax.reduce_precision(v, 8, 7)`` — ties, +-inf, overflow to inf and
    subnormals included; NaNs pass through unchanged — but built from
    integer ops, which Mosaic lowers (it has no ``reduce_precision``) and
    no float simplification can remove.
    """
    u = jax.lax.bitcast_convert_type(v, jnp.uint32)
    lsb = (u >> jnp.uint32(16)) & jnp.uint32(1)
    r = (u + jnp.uint32(0x7FFF) + lsb) & jnp.uint32(0xFFFF0000)
    return jnp.where(jnp.isnan(v), v,
                     jax.lax.bitcast_convert_type(r, jnp.float32))


def _dot(a, b):
    """MXU contraction into an f32 accumulator.  f32 operands contract at
    full f32 precision: a TPU's default f32 dot (Mosaic's and XLA's alike)
    takes one bf16 pass, which would serve an f32 core's words from bf16
    products on the chip and match neither interpret mode nor the oracle."""
    prec = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jnp.dot(a, b, precision=prec, preferred_element_type=jnp.float32)


def _make_step(w1, b1, w2, b2, *, activation: str, compute_unit: str,
               i_dim: int, h_dim: int, lattice=None, cpl=None):
    """Shared oscillator update used by every kernel in this module.

    Operates on x of shape (I_pad, s): padded feature rows of the weights are
    zero, so padding never contaminates live rows.

    ``lattice = (n_nodes, base_dim, topology, strength)`` adds the
    block-coupled diffusive term: on mxu it is one more genuine MXU
    contraction with the resident ``cpl`` (I, I) operand; on vpu it is the
    roll-based ``_lattice_delta`` (no matrix ever materialized).  The two
    units produce legitimately different word streams (different fp
    expression trees) — determinism keys on ``compute_unit`` as ever.
    """
    phi = _activation(activation)

    def couple(x):
        if cpl is not None:
            return _round_half(_dot(cpl, x), x.dtype)
        return _lattice_delta(x, lattice)

    def one_step(x):
        if compute_unit == "mxu":
            h = phi(_round_half(_dot(w1.T, x), x.dtype) + b1)
            y = _round_half(_dot(w2.T, h), x.dtype)
            y = y + b2
        else:
            # VPU path: broadcast-FMA over lanes; static unroll over tiny
            # dims.  w1[i] / w2[j] are weight columns laid out outside the
            # kernel (``_pad_params``).
            h = jnp.zeros((w1.shape[1], x.shape[1]), x.dtype)
            for i in range(i_dim):
                h = h + w1[i] * x[i:i + 1, :]
            h = phi(h + b1)
            y = jnp.zeros_like(x)
            for j in range(h_dim):
                y = y + w2[j] * h[j:j + 1, :]
            y = y + b2
        if lattice is not None:
            y = y + couple(x)
        # pin the carry itself: the bf16 add chain after the matmul
        # boundaries is equally subject to excess-precision fusion
        return _round_half(y, y.dtype)

    return one_step


def _prep_lattice(lattice, coupling, compute_unit: str, i_dim: int,
                  i_pad: int, dtype):
    """Shared launch-side lattice validation.

    Returns ``(use_cpl, cplp)``: whether the kernel takes the dense (I, I)
    coupling operand (mxu only — the vpu paths rebuild the operator from the
    static descriptor as wrapped rolls and never materialize a matrix), and
    the dtype-cast operand itself.
    """
    if lattice is None:
        return False, None
    _check_lattice(lattice, i_dim, i_pad)
    if compute_unit != "mxu":
        return False, None
    if coupling is None:
        raise ValueError(
            "mxu lattice launches need the dense coupling operand")
    if coupling.shape != (i_dim, i_dim):
        raise ValueError(f"coupling shape {coupling.shape} != "
                         f"({i_dim}, {i_dim})")
    return True, jnp.asarray(coupling, dtype)


def _pad_params(w1, b1, w2, b2, *, i_pad: int, h_pad: int, dtype,
                compute_unit: str):
    """Zero-padded kernel weight operands, with or without a leading core
    axis: w1 (.., I, H), b1 (.., H), w2 (.., H, I), b2 (.., I) become
    w1 (.., I_pad, H_pad), b1 (.., H_pad, 1), w2 (.., H_pad, I_pad),
    b2 (.., I_pad, 1).  The vpu step reads weights as (rows, 1) columns,
    so for vpu w1/w2 get a trailing unit axis: ``w1[i]`` is the column
    that multiplies state row ``i``.  Mosaic cannot turn a weight row
    into a column inside the kernel, so the layout is made here.
    """
    lead = w1.shape[:-2]

    def pad(a, shape):
        z = jnp.zeros(lead + shape, dtype)
        live = tuple(slice(0, n) for n in a.shape[len(lead):])
        return z.at[(Ellipsis,) + live].set(a.astype(dtype))

    w1p = pad(w1, (i_pad, h_pad))
    b1p = pad(b1[..., None], (h_pad, 1))
    w2p = pad(w2, (h_pad, i_pad))
    b2p = pad(b2[..., None], (i_pad, 1))
    if compute_unit != "mxu":
        w1p, w2p = w1p[..., None], w2p[..., None]
    return w1p, b1p, w2p, b2p


def _whole(a):
    """BlockSpec keeping all of ``a`` resident in every grid cell."""
    return pl.BlockSpec(a.shape, lambda *_: (0,) * a.ndim)


def _carry_loop(x0_ref, state_ref, make_item, *, n_items: int, unroll: int,
                trips=None):
    """The carry loop of one grid cell, shared by every kernel here.

    The state block is initialised from ``x0_ref`` at ``t == 0`` and
    carried in ``state_ref`` across the time grid (the T axis iterates
    fastest).  ``make_item(t)`` builds ``item(x, base, u) -> x``, which
    runs item ``base + u`` of time block ``t`` (the item adds the index
    where its program needs it).  The ``n_items`` items run in
    ``unroll``-wide chunks — one static chunk when there is only one,
    else a ``fori_loop`` over the chunks, or over ``trips(t, n_chunks)``
    of them for a dynamic trip count — and the carry is written back.
    """
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        state_ref[...] = x0_ref[...]

    item = make_item(t)

    def chunk(x, base):
        for u in range(unroll):
            x = item(x, base, u)
        return x

    x = state_ref[...]
    n_chunks = n_items // unroll
    if trips is None and n_chunks == 1:
        x = chunk(x, 0)
    else:
        n = n_chunks if trips is None else trips(t, n_chunks)
        x = jax.lax.fori_loop(0, n, lambda c, x: chunk(x, c * unroll), x)
    state_ref[...] = x


def _kernel(*refs, t_block: int, unroll: int, activation: str,
            compute_unit: str, i_dim: int, h_dim: int, lattice, has_cpl):
    """One (stream-block, time-block) grid cell.

    Ref shapes (per block):
      w1: (I_pad, H_pad)  b1: (H_pad, 1)  w2: (H_pad, I_pad)  b2: (I_pad, 1)
        (vpu: w1/w2 with a trailing unit axis, see ``_pad_params``)
      [cpl: (I_pad, I_pad) — mxu lattice launches only]
      x0: (I_pad, s_block)      out: (t_block, I_pad, s_block)
      state (VMEM scratch): (I_pad, s_block)
    """
    w1_ref, b1_ref, w2_ref, b2_ref, *cpl_ref, x0_ref, out_ref, state_ref = refs

    def make_item(t):
        one_step = _make_step(
            w1_ref[...], b1_ref[...], w2_ref[...], b2_ref[...],
            activation=activation, compute_unit=compute_unit, i_dim=i_dim,
            h_dim=h_dim, lattice=lattice,
            cpl=cpl_ref[0][...] if has_cpl else None)

        def item(x, base, u):
            x = one_step(x)
            out_ref[base + u] = x
            return x
        return item

    _carry_loop(x0_ref, state_ref, make_item, n_items=t_block, unroll=unroll)


@_launch_jit
def chaotic_ann_pallas(w1, b1, w2, b2, x0, coupling=None, *, n_steps: int,
                       s_block: int = 256, t_block: int = 128, unroll: int = 1,
                       activation: str = "relu", compute_unit: str = "vpu",
                       lattice=None, interpret: bool = False):
    """Run the fused oscillator kernel.

    Args:
      w1 (I, H), b1 (H,), w2 (H, I), b2 (I,), x0 (S, I).
      coupling: dense (I, I) diffusive operator — consumed only by mxu
        lattice launches (one extra resident MXU operand).
      n_steps: total steps (padded up to a multiple of t_block internally).
      s_block/t_block/unroll/compute_unit: DSE-searchable microarchitecture.
      lattice: optional static ``(n_nodes, base_dim, topology, strength)``
        descriptor — turns the core into a block-coupled lattice (vpu
        applies the coupling as wrapped sublane rolls, no matrix operand).
    Returns:
      (n_steps, S, I) trajectory matching ``ref.chaotic_ann_ref``.
    """
    i_dim, h_dim = w1.shape
    s_total = x0.shape[0]
    dtype = x0.dtype
    if t_block % unroll:
        raise ValueError(f"t_block {t_block} must be divisible by unroll {unroll}")

    i_pad = _pad_to(max(i_dim, 1), SUBLANES)
    h_pad = _pad_to(max(h_dim, 1), SUBLANES)
    s_pad = _pad_to(s_total, s_block)
    t_pad = _pad_to(n_steps, t_block)
    use_cpl, cplp = _prep_lattice(lattice, coupling, compute_unit,
                                  i_dim, i_pad, dtype)

    inputs = list(_pad_params(w1, b1, w2, b2, i_pad=i_pad, h_pad=h_pad,
                              dtype=dtype, compute_unit=compute_unit))
    if use_cpl:
        inputs.append(cplp)
    in_specs = [_whole(a) for a in inputs]
    # (S, I) -> (I_pad, S_pad): streams on lanes.
    x0p = jnp.zeros((i_pad, s_pad), dtype).at[:i_dim, :s_total].set(x0.T.astype(dtype))

    grid = (s_pad // s_block, t_pad // t_block)
    scratch = [pltpu.VMEM((i_pad, s_block), dtype)]

    in_specs.append(pl.BlockSpec((i_pad, s_block), lambda s, t: (0, s)))
    inputs.append(x0p)

    out = pl.pallas_call(
        functools.partial(_kernel, t_block=t_block, unroll=unroll,
                          activation=activation, compute_unit=compute_unit,
                          i_dim=i_dim, h_dim=h_dim, lattice=lattice,
                          has_cpl=use_cpl),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((t_block, i_pad, s_block), lambda s, t: (t, 0, s)),
        out_shape=jax.ShapeDtypeStruct((t_pad, i_pad, s_pad), dtype),
        scratch_shapes=scratch,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*inputs)

    # (t_pad, I_pad, s_pad) -> (n_steps, S, I)
    return out[:n_steps, :i_dim, :s_total].transpose(0, 2, 1)


# ---------------------------------------------------------------------------
# Fused bit-extraction kernel: the trajectory never leaves VMEM in float form.
# ---------------------------------------------------------------------------

_GOLDEN = 0x9E3779B9          # Weyl increment (2^32 / phi)


def _low_bits(x):
    """The low mantissa bits of every sample of ``x`` as uint32: bf16 is
    bitcast at its own width and masked to its 7 mantissa bits (an upcast
    to f32 would zero the low 16 bits and kill the entropy), f32 keeps
    its low 16 bits."""
    if x.dtype.itemsize == 2:
        u = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
        mask = (1 << jnp.finfo(x.dtype).nmant) - 1
    else:
        u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
        mask = 0xFFFF
    return u & jnp.uint32(mask)


def _fold16(x, i_dim: int):
    """Low-mantissa fold of one oscillator sample block.

    x: (I_pad, s) floats -> (1, s) uint32, the low mantissa bits of each
    live system dimension XOR-folded with odd shifts.  Bit-exact twin of
    the per-sample stage of ``ops.bits_from_trajectory``.
    """
    lo = _low_bits(x)
    folded = lo[0:1, :]
    for i in range(1, i_dim):
        folded = folded ^ (lo[i:i + 1, :] << jnp.uint32(5 * i % 16))
    return folded


def _finalize(w):
    """Murmur3-style avalanche, identical to ``ops.bits_from_trajectory``."""
    w = w ^ (w >> jnp.uint32(16))
    w = w * jnp.uint32(0x85EBCA6B)
    w = w ^ (w >> jnp.uint32(13))
    w = w * jnp.uint32(0xC2B2AE35)
    w = w ^ (w >> jnp.uint32(16))
    return w


def _word_row(one_step, fold, i_dim: int, offs, x, t, rows: int, r):
    """Two oscillator steps from ``x`` -> one whitened uint32 word row,
    shared by every word kernel.

    ``fold(sample, i_dim)`` (``_fold16`` or ``_stacked_fold16``) packs a
    sample's low mantissa bits; the pair is Weyl-whitened by its absolute
    word row ``t * rows + r`` (row ``r`` of time block ``t``, ``rows``
    word rows a block) plus the per-lane ``offs``, then finalized.
    Returns the state after the second step and the word row.
    """
    x1 = one_step(x)
    x2 = one_step(x1)
    word = (fold(x1, i_dim) << jnp.uint32(16)) | fold(x2, i_dim)
    row_idx = offs + (t * rows + r).astype(jnp.uint32)
    return x2, _finalize(word ^ (row_idx * jnp.uint32(_GOLDEN)))


def _bits_kernel(*refs, t_block: int, unroll: int,
                 activation: str, compute_unit: str, i_dim: int, h_dim: int,
                 lattice, has_cpl):
    """One (stream-block, time-block) grid cell of the fused PRNG kernel.

    Per block:
      [cpl:  (I_pad, I_pad) coupling — mxu lattice launches only]
      off:   (1, s_block) uint32  per-stream word-row offset (Weyl counter)
      words: (t_block//2, s_block) uint32  output words
      state: (I_pad, s_block)  output, doubles as the VMEM carry across the
             time grid (same output block revisited for every t), so the
             float trajectory is never written to HBM.
    """
    (w1_ref, b1_ref, w2_ref, b2_ref, *cpl_ref, x0_ref, off_ref, words_ref,
     state_ref) = refs
    rows = t_block // 2

    def make_row(t):
        one_step = _make_step(
            w1_ref[...], b1_ref[...], w2_ref[...], b2_ref[...],
            activation=activation, compute_unit=compute_unit, i_dim=i_dim,
            h_dim=h_dim, lattice=lattice,
            cpl=cpl_ref[0][...] if has_cpl else None)
        offs = off_ref[...]

        def row(x, base, u):
            r = base + u
            x, word = _word_row(one_step, _fold16, i_dim, offs, x, t,
                                rows, r)
            words_ref[pl.ds(r, 1), :] = word
            return x
        return row

    _carry_loop(x0_ref, state_ref, make_row, n_items=rows, unroll=unroll)


def _bits_blocks(n_steps: int, t_block: int, unroll: int):
    """Largest legal (t_block, unroll) not exceeding the requested ones.

    The fused kernel must run *exactly* n_steps (the final state is part of
    the contract), so t_block has to divide n_steps; it must also be even
    (2 samples -> 1 word) and unroll counts word rows, so it must divide
    t_block // 2.
    """
    t_block = max(2, t_block - (t_block % 2))
    tb = math.gcd(t_block, n_steps)
    un = max(1, math.gcd(unroll, tb // 2))
    return tb, un


@_launch_jit
def chaotic_ann_bits_pallas(w1, b1, w2, b2, x0, word_offset=0, coupling=None,
                            *, n_steps: int, s_block: int = 256,
                            t_block: int = 128, unroll: int = 1,
                            activation: str = "relu",
                            compute_unit: str = "vpu",
                            lattice=None, interpret: bool = False):
    """Fused oscillator + bit-extraction: streams PRNG words straight out.

    Runs the same update as ``chaotic_ann_pallas`` but packs the low-mantissa
    bits of each pair of consecutive samples into one uint32 word *inside the
    kernel* (Weyl-whitened + Murmur3-finalized, bit-exact with
    ``ops.bits_from_trajectory``), so only ~1/4 of the trajectory bytes ever
    reach HBM and no second extraction pass is needed.

    Args:
      w1 (I, H), b1 (H,), w2 (H, I), b2 (I,), x0 (S, I).
      word_offset: scalar or (S,) uint32 — the global word-row counter(s) of
        the first emitted row; makes chunked draws resume the exact Weyl
        sequence of one long draw.
      coupling / lattice: see ``chaotic_ann_pallas`` — the same static
        lattice descriptor (and, for mxu, dense operand) turns the core
        into a block-coupled oscillator lattice.
      n_steps: steps to run; must be even (2 samples -> 1 word row).
    Returns:
      words: (n_steps // 2, S) uint32 word rows,
      final_state: (S, I) oscillator state after n_steps (resume handle).
    """
    if n_steps < 2 or n_steps % 2:
        raise ValueError(f"n_steps must be even and >= 2, got {n_steps}")
    i_dim, h_dim = w1.shape
    s_total = x0.shape[0]
    dtype = x0.dtype
    t_block, unroll = _bits_blocks(n_steps, t_block, unroll)

    i_pad = _pad_to(max(i_dim, 1), SUBLANES)
    h_pad = _pad_to(max(h_dim, 1), SUBLANES)
    s_pad = _pad_to(s_total, s_block)
    n_rows = n_steps // 2
    use_cpl, cplp = _prep_lattice(lattice, coupling, compute_unit,
                                  i_dim, i_pad, dtype)

    x0p = jnp.zeros((i_pad, s_pad), dtype).at[:i_dim, :s_total].set(x0.T.astype(dtype))
    off = jnp.asarray(word_offset, jnp.uint32)
    offp = jnp.zeros((1, s_pad), jnp.uint32).at[0, :s_total].set(
        jnp.broadcast_to(off, (s_total,)))

    inputs = list(_pad_params(w1, b1, w2, b2, i_pad=i_pad, h_pad=h_pad,
                              dtype=dtype, compute_unit=compute_unit))
    if use_cpl:
        inputs.append(cplp)
    in_specs = [_whole(a) for a in inputs]
    in_specs += [
        pl.BlockSpec((i_pad, s_block), lambda s, t: (0, s)),  # x0
        pl.BlockSpec((1, s_block), lambda s, t: (0, s)),      # offsets
    ]
    inputs += [x0p, offp]

    grid = (s_pad // s_block, n_steps // t_block)
    words, state = pl.pallas_call(
        functools.partial(_bits_kernel, t_block=t_block, unroll=unroll,
                          activation=activation, compute_unit=compute_unit,
                          i_dim=i_dim, h_dim=h_dim, lattice=lattice,
                          has_cpl=use_cpl),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((t_block // 2, s_block), lambda s, t: (t, s)),
            pl.BlockSpec((i_pad, s_block), lambda s, t: (0, s)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_rows, s_pad), jnp.uint32),
            jax.ShapeDtypeStruct((i_pad, s_pad), dtype),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*inputs)

    return words[:, :s_total], state[:i_dim, :s_total].T


# ---------------------------------------------------------------------------
# Gang-scheduled variant: C compatible networks, ONE launch.
# ---------------------------------------------------------------------------


def _gang_bits_kernel(*refs, t_block: int, unroll: int, activation: str,
                      compute_unit: str, i_dim: int, h_dim: int,
                      ragged: bool, lattice, has_cpl):
    """One (lane-block, time-block) grid cell of the gang PRNG kernel.

    Identical math to ``_bits_kernel`` (state output doubles as the VMEM
    carry across the time grid); the only difference is that the weight
    refs carry a leading length-1 core axis whose block was DMA'd from slab
    ``core_map[g]`` of the stacked weights (scalar-prefetch index map), so
    every lane block computes its own network in the same launch.
    ``cmap_ref`` is the prefetched map itself — consumed by the index maps,
    unused in the body.

    Ragged variant: a second scalar-prefetched map carries the word rows
    each lane block actually owes.  The row loop's trip count becomes
    dynamic — a cell computes only the unroll-chunks covering its block's
    remaining demand and cells wholly past it fall through with the state
    carry untouched — so a ragged gang launch does no overdraw FMA work.
    Word rows past a block's demand are left unwritten (garbage); callers
    slice to the per-block demand.
    """
    refs = list(refs)
    _cmap_ref = refs.pop(0)
    rmap_ref = refs.pop(0) if ragged else None
    w1_ref, b1_ref, w2_ref, b2_ref = refs[:4]
    refs = refs[4:]
    cpl_ref = refs.pop(0) if has_cpl else None
    x0_ref, off_ref, words_ref, state_ref = refs
    g = pl.program_id(0)
    rows = t_block // 2

    def make_row(t):
        one_step = _make_step(
            w1_ref[0], b1_ref[0], w2_ref[0], b2_ref[0],
            activation=activation, compute_unit=compute_unit, i_dim=i_dim,
            h_dim=h_dim, lattice=lattice,
            cpl=cpl_ref[...] if has_cpl else None)
        offs = off_ref[...]

        def row(x, base, u):
            r = base + u
            x, word = _word_row(one_step, _fold16, i_dim, offs, x, t,
                                rows, r)
            words_ref[pl.ds(r, 1), :] = word
            return x
        return row

    def trips(t, n_chunks):
        remaining = jnp.maximum(rmap_ref[g] - t * rows, 0)
        return jnp.minimum((remaining + unroll - 1) // unroll, n_chunks)

    _carry_loop(x0_ref, state_ref, make_row, n_items=rows, unroll=unroll,
                trips=trips if ragged else None)


def gang_effective_rows(row_map, n_steps: int, t_block: int,
                        unroll: int) -> np.ndarray:
    """Word rows each lane block of a ragged gang launch actually computes
    (and therefore the rows its member's state/counters advance by): the
    dynamic row loop skips whole unroll-chunks, so a block's computed rows
    are its ``row_map`` entry rounded up to the post-gcd unroll (the same
    ``_bits_blocks`` collapse the kernel itself applies)."""
    _, un = _bits_blocks(n_steps, t_block, unroll)
    r = np.asarray(row_map, np.int64)
    return np.minimum(-(-r // un) * un, n_steps // 2).astype(np.int32)


@_launch_jit
def chaotic_ann_gang_bits_pallas(w1, b1, w2, b2, x0, core_map, word_offset=0,
                                 row_map=None, coupling=None, *, n_steps: int,
                                 s_block: int = 256,
                                 t_block: int = 128, unroll: int = 1,
                                 activation: str = "relu",
                                 compute_unit: str = "vpu",
                                 lattice=None, interpret: bool = False):
    """Gang-scheduled fused PRNG: C stacked networks, one kernel launch.

    The farm's gang path: weights carry a leading core axis and the pooled
    stream axis is divided into ``s_block``-lane blocks, each homogeneous in
    core.  ``core_map[g]`` names the weight slab of lane block ``g``; it is
    scalar-prefetched so the BlockSpec index maps route each grid cell's
    weight DMA to its own slab (the grouped/ragged-batching trick of MaxText
    -style serving stacks).  Per lane the computation is exactly
    ``chaotic_ann_bits_pallas`` with that lane's core — lanes evolve
    independently, so gang words/states are bit-identical to C per-core
    launches.

    Args:
      w1 (C, I, H), b1 (C, H), w2 (C, H, I), b2 (C, I): stacked weights.
      x0 (S, I): concatenated stream pool; S must equal
        ``len(core_map) * s_block`` (pad each member pool to an s_block
        multiple before concatenating).
      core_map: (n_blocks,) int array, values in [0, C).
      word_offset: scalar or (S,) uint32 per-lane word-row offsets.
      row_map: optional (n_blocks,) int array — word rows each lane block
        owes (demand-shaped launch).  Block ``g`` computes exactly
        ``gang_effective_rows(row_map, ...)[g]`` rows, bit-identical to a
        per-core launch of that many rows; later rows are unwritten
        garbage.  None = every block computes all ``n_steps // 2`` rows.
      coupling / lattice: see ``chaotic_ann_pallas``.  ONE coupling operand
        is shared by every lane block — a gang only admits cores with
        identical lattice meta (the scheduler's compat key), so the shared
        operand is exact, not an approximation.
      n_steps: steps to run; must be even (2 samples -> 1 word row).
    Returns:
      words: (n_steps // 2, S) uint32 word rows,
      final_state: (S, I) oscillator state after each lane's own rows.
    """
    if n_steps < 2 or n_steps % 2:
        raise ValueError(f"n_steps must be even and >= 2, got {n_steps}")
    n_cores, i_dim, h_dim = w1.shape
    s_total = x0.shape[0]
    n_blocks = core_map.shape[0]
    if s_total != n_blocks * s_block:
        raise ValueError(
            f"pool of {s_total} lanes != {n_blocks} core-map blocks x "
            f"s_block {s_block}; pad each member pool to an s_block multiple")
    ragged = row_map is not None
    if ragged and row_map.shape != core_map.shape:
        raise ValueError(f"row_map shape {row_map.shape} != core_map shape "
                         f"{core_map.shape}")
    dtype = x0.dtype
    t_block, unroll = _bits_blocks(n_steps, t_block, unroll)

    i_pad = _pad_to(max(i_dim, 1), SUBLANES)
    h_pad = _pad_to(max(h_dim, 1), SUBLANES)
    n_rows = n_steps // 2
    use_cpl, cplp = _prep_lattice(lattice, coupling, compute_unit,
                                  i_dim, i_pad, dtype)

    x0p = jnp.zeros((i_pad, s_total), dtype
                    ).at[:i_dim, :].set(x0.T.astype(dtype))
    off = jnp.asarray(word_offset, jnp.uint32)
    offp = jnp.broadcast_to(off, (s_total,)).reshape(1, s_total)
    cmap = jnp.asarray(core_map, jnp.int32)

    # Scalar-prefetch arguments: the core-id map always; the per-block row
    # map only for ragged launches (the index maps ignore it).
    scalars = [cmap]
    if ragged:
        scalars.append(jnp.minimum(jnp.asarray(row_map, jnp.int32), n_rows))
    n_sc = len(scalars)

    def _slab(a):
        """Core ``core_map[g]``'s slab of a weight stacked on axis 0."""
        return pl.BlockSpec((1,) + a.shape[1:],
                            lambda g, t, *m: (m[0][g],) + (0,) * (a.ndim - 1))

    inputs = list(_pad_params(w1, b1, w2, b2, i_pad=i_pad, h_pad=h_pad,
                              dtype=dtype, compute_unit=compute_unit))
    in_specs = [_slab(a) for a in inputs]
    if use_cpl:
        in_specs.append(_whole(cplp))          # shared by every lane block
        inputs.append(cplp)
    in_specs += [
        pl.BlockSpec((i_pad, s_block), lambda g, t, *m: (0, g)),   # x0
        pl.BlockSpec((1, s_block), lambda g, t, *m: (0, g)),  # offsets
    ]
    inputs += [x0p, offp]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_sc,
        grid=(n_blocks, n_steps // t_block),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((t_block // 2, s_block), lambda g, t, *m: (t, g)),
            pl.BlockSpec((i_pad, s_block), lambda g, t, *m: (0, g)),
        ],
    )
    words, state = pl.pallas_call(
        functools.partial(_gang_bits_kernel, t_block=t_block, unroll=unroll,
                          activation=activation, compute_unit=compute_unit,
                          i_dim=i_dim, h_dim=h_dim, ragged=ragged,
                          lattice=lattice, has_cpl=use_cpl),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_rows, s_total), jnp.uint32),
            jax.ShapeDtypeStruct((i_pad, s_total), dtype),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*scalars, *inputs)

    return words, state[:i_dim, :].T


# ---------------------------------------------------------------------------
# Stacked gang variant: C equal-shape pools, ONE grid cell per
# (lane-block, time-block) — the whole group's update is a single set of
# vector ops, with the cores on the sublane axis.
# ---------------------------------------------------------------------------


def _stacked_fold16(x, i_dim: int):
    """Fold the live dims of every core at once: (I, C, s) -> (C, s).

    ``x[i]`` is dimension ``i`` of every core, so the fold stays one XOR
    chain of (C, s) values — the same low-mantissa bits, shifts, and order
    per lane as ``_fold16`` on each core alone.
    """
    lo = _low_bits(x)
    folded = lo[0]
    for i in range(1, i_dim):
        folded = folded ^ (lo[i] << jnp.uint32(5 * i % 16))
    return folded


def _make_stacked_step(w1t, b1s, w2t, b2s, *, activation: str,
                       i_dim: int, h_dim: int, lattice=None):
    """Whole-group oscillator update on dimension-major stacked state.

    x: (I, C, s) — ``x[i]`` holds dimension ``i`` of all C cores, one core
    per sublane row.  Weight tables are laid out outside the kernel:
    w1t[i] is (H, C, 1) with w1t[i, j, c] = w1[c, i, j], so
    ``h += w1t[i] * x[i]`` is ONE multiply-add over the whole group, with
    only leading-axis indexing and lane broadcasts (no sublane gathers) —
    the same accumulation order per lane as the per-core VPU path, hence
    bit-identical words.

    Lattice groups reuse ``_lattice_delta``: the node index lives on the
    leading axis, so its wrapped rolls move whole (C, s) slabs and add
    each core's own neighbour rows — the identical jnp expression (and
    values) as the solo kernel, keeping the gang bit-identical per lane.
    """
    phi = _activation(activation)

    def one_step(x):
        h = jnp.zeros((h_dim,) + x.shape[1:], x.dtype)
        for i in range(i_dim):
            h = h + w1t[i] * x[i][None]
        h = phi(h + b1s)
        y = jnp.zeros_like(x)
        for j in range(h_dim):
            y = y + w2t[j] * h[j][None]
        y = y + b2s
        if lattice is not None:
            y = y + _lattice_delta(x, lattice)
        return _round_half(y, y.dtype)

    return one_step


def _gang_stacked_kernel(*refs, t_block: int, unroll: int,
                         activation: str, i_dim: int, h_dim: int,
                         ragged: bool, lattice):
    """One (lane-block, time-block) cell computing ALL C cores at once.

    Ragged variant: an extra (C, 1) row-map input freezes a core's state
    once its own word-row demand is met — the stacked FMA sweep still
    spans the whole group (the stack is one fused op), but a frozen
    core's state stops advancing at exactly its demand, so its final
    state (and word prefix) is bit-identical to a per-core launch of that
    many rows.  Word rows past a core's demand are garbage.
    """
    (w1t_ref, b1_ref, w2t_ref, b2_ref, x0_ref, off_ref, *rmap_ref, words_ref,
     state_ref) = refs
    rows = t_block // 2

    def make_row(t):
        one_step = _make_stacked_step(
            w1t_ref[...], b1_ref[...], w2t_ref[...], b2_ref[...],
            activation=activation, i_dim=i_dim, h_dim=h_dim, lattice=lattice)
        offs = off_ref[...]
        rmap = rmap_ref[0][...] if ragged else None

        def row(x, base, u):
            r = base + u
            x2, word = _word_row(one_step, _stacked_fold16, i_dim, offs,
                                 x, t, rows, r)
            words_ref[pl.ds(r, 1), :, :] = word[None]
            if ragged:
                alive = (t * rows + r) < rmap                # (C, 1) bool
                x2 = jnp.where(alive[None], x2, x)
            return x2
        return row

    _carry_loop(x0_ref, state_ref, make_row, n_items=rows, unroll=unroll)


@_launch_jit
def chaotic_ann_gang_stacked_pallas(w1, b1, w2, b2, x0, word_offset=0,
                                    row_map=None, *,
                                    n_steps: int, s_block: int = 256,
                                    t_block: int = 128, unroll: int = 1,
                                    activation: str = "relu",
                                    compute_unit: str = "vpu",
                                    lattice=None, interpret: bool = False):
    """Gang launch for C equal-shape pools, stacked on the SUBLANE axis.

    Where ``chaotic_ann_gang_bits_pallas`` concatenates pools along the
    lane axis, this variant stacks equal pools: state is (I, C, s_block)
    in one grid cell — dimension-major, the C cores on sublanes — and each
    step is ONE broadcast-FMA sweep over the group, so C networks advance
    for the per-cell cost of one (the paper's parallelism-P MAC array
    applied across *cores*).  Per lane the FMA order, bit fold and
    whitening are the per-core kernel's, so words and final states are
    bit-identical to C ``chaotic_ann_bits_pallas`` launches.

    Args:
      w1 (C, I, H), b1 (C, H), w2 (C, H, I), b2 (C, I): stacked weights.
      x0 (C, S, I): one equal-size pool per core.
      word_offset: scalar or (C, S) uint32 per-lane word-row offsets.
      row_map: optional (C,) int array of per-core word-row demands: core
        ``c``'s state freezes after ``row_map[c]`` rows (no FMA saved), so
        its state and word prefix are bit-identical to a per-core launch
        of that many rows; later rows are garbage.  None = all rows.
    Returns:
      words: (n_steps // 2, C, S) uint32, final_state: (C, S, I).
    """
    if n_steps < 2 or n_steps % 2:
        raise ValueError(f"n_steps must be even and >= 2, got {n_steps}")
    if compute_unit != "vpu":
        # The stacked step IS the broadcast-FMA order; a dot-based (mxu)
        # group must take the lane-concat gang path to stay bit-identical.
        raise ValueError("stacked gang launches support compute_unit='vpu' "
                         "only; use chaotic_ann_gang_bits_pallas for mxu")
    n_cores, i_dim, h_dim = w1.shape
    s_total = x0.shape[1]
    dtype = x0.dtype
    t_block, unroll = _bits_blocks(n_steps, t_block, unroll)

    s_pad = _pad_to(s_total, s_block)
    n_rows = n_steps // 2
    if lattice is not None:
        _check_lattice(lattice, i_dim, i_dim)

    # Weight tables with the core on the sublane axis: w1t[i, j, c, 0] =
    # w1[c, i, j], b1s[j, c, 0] = b1[c, j]; likewise w2t and b2s.
    w1t = w1.astype(dtype).transpose(1, 2, 0)[..., None]
    b1s = b1.astype(dtype).T[..., None]
    w2t = w2.astype(dtype).transpose(1, 2, 0)[..., None]
    b2s = b2.astype(dtype).T[..., None]
    # (C, S, I) -> (I, C, S_pad): dimension-major, streams on lanes.
    x0p = jnp.zeros((i_dim, n_cores, s_pad), dtype).at[
        :, :, :s_total].set(x0.transpose(2, 0, 1).astype(dtype))
    off = jnp.asarray(word_offset, jnp.uint32)
    offp = jnp.zeros((n_cores, s_pad), jnp.uint32).at[:, :s_total].set(
        jnp.broadcast_to(off, (n_cores, s_total)))
    ragged = row_map is not None

    inputs = [w1t, b1s, w2t, b2s]
    in_specs = [_whole(a) for a in inputs]
    in_specs += [
        pl.BlockSpec((i_dim, n_cores, s_block),
                     lambda s, t: (0, 0, s)),                 # x0
        pl.BlockSpec((n_cores, s_block), lambda s, t: (0, s)),  # offsets
    ]
    inputs += [x0p, offp]
    if ragged:
        if np.shape(row_map) != (n_cores,):
            raise ValueError(f"row_map must have shape ({n_cores},), got "
                             f"{np.shape(row_map)}")
        rmapp = jnp.minimum(jnp.asarray(row_map, jnp.int32),
                            n_rows).reshape(n_cores, 1)
        in_specs.append(_whole(rmapp))
        inputs.append(rmapp)

    grid = (s_pad // s_block, n_steps // t_block)
    words, state = pl.pallas_call(
        functools.partial(_gang_stacked_kernel, t_block=t_block,
                          unroll=unroll, activation=activation,
                          i_dim=i_dim, h_dim=h_dim, ragged=ragged,
                          lattice=lattice),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((t_block // 2, n_cores, s_block),
                         lambda s, t: (t, 0, s)),
            pl.BlockSpec((i_dim, n_cores, s_block), lambda s, t: (0, 0, s)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_rows, n_cores, s_pad), jnp.uint32),
            jax.ShapeDtypeStruct((i_dim, n_cores, s_pad), dtype),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*inputs)

    words = words[:, :, :s_total]
    state = state[:, :, :s_total].transpose(1, 2, 0)
    return words, state


# ---------------------------------------------------------------------------
# Device-sharded gang launches: the same gang kernels, with the pooled
# stream axis (and the per-block scalar-prefetch maps) partitioned across a
# named mesh axis.  Weight slabs are replicated — the maxtext-style choice:
# shard the batch-like axis, keep the (tiny) params everywhere.
# ---------------------------------------------------------------------------


def gang_partition_maps(core_map, row_map, *, n_dev: int, n_rows: int):
    """Partition the per-block gang maps across ``n_dev`` devices.

    Pads the block axis with DEAD blocks (core 0, zero row demand) until it
    divides the device count, so every device owns the same number of
    ``s_block``-lane blocks and scalar-prefetches its own contiguous slice
    of the core-id and row maps.  Padding forces the launch ragged — dead
    blocks must compute zero rows — and a ``row_map`` of ``n_rows`` per
    real block reproduces the padded group-max launch exactly, so the
    rounding is free.

    Returns ``(core_map, row_map, pad_blocks)`` as numpy arrays.  Device
    ``d`` consumes ``core_map[d * B:(d + 1) * B]`` with ``B = len(core_map)
    // n_dev`` — exactly the contiguous slice the shard_map inside the
    sharded kernels hands it.
    """
    cmap = np.asarray(core_map, np.int32)
    n_blocks = cmap.shape[0]
    pad = (-n_blocks) % n_dev
    rmap = None if row_map is None else np.asarray(row_map, np.int32)
    if pad == 0:
        return cmap, rmap, 0
    if rmap is None:
        rmap = np.full(n_blocks, n_rows, np.int32)
    return (np.concatenate([cmap, np.zeros(pad, np.int32)]),
            np.concatenate([rmap, np.zeros(pad, np.int32)]), pad)


def _gather_lanes(words: jax.Array, mesh_axis: str) -> jax.Array:
    """One device's words of a sharded launch, all-gathered over
    ``mesh_axis`` on their lane (last) axis inside the launch's program:
    every device returns the whole word slab, so the host copies one
    device buffer instead of assembling one slice per device."""
    return jax.lax.all_gather(words, mesh_axis, axis=words.ndim - 1,
                              tiled=True)


@functools.lru_cache(maxsize=384)
def _sharded_launch(launch, in_specs, state_spec, mesh, mesh_axis, **kw):
    """The one builder of every sharded launch: the single-device
    ``launch`` inside a jitted ``shard_map`` over ``mesh[mesh_axis]``.

    ``in_specs`` has a spec per positional argument of ``launch``, None
    for an optional argument it is not given (the caller passes None,
    which adds no operand), so the specs key the program's operands too.
    Weights, coupling and pool are traced arguments — closed over, they
    would be baked in as constants and recompile every flush — so the
    callable, cached per (launch, specs, mesh, static kernel config
    ``kw``), retraces only for a new launch SHAPE.  Each device runs
    ``launch`` on its own run of lanes; its words are all-gathered into
    one replicated slab (``_gather_lanes``), the state keeps ``state_spec``.
    """
    def local(*args):
        words, state = launch(*args, **kw)
        return _gather_lanes(words, mesh_axis), state

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=(P(), state_spec),
        check_vma=False))


def sharded_launch_builds() -> int:
    """Sharded launch callables built so far in this process, solo and
    gang: the misses of ``_sharded_launch``'s cache.  A launch that
    raises this count built (and will compile) a new program."""
    return _sharded_launch.cache_info().misses


def chaotic_ann_bits_sharded(w1, b1, w2, b2, x0, offsets, coupling=None,
                             *, mesh, mesh_axis: str = "data",
                             n_steps: int, s_block: int = 256,
                             t_block: int = 128, unroll: int = 1,
                             activation: str = "relu",
                             compute_unit: str = "vpu", lattice=None,
                             interpret: bool = False):
    """Solo fused bits launch partitioned across ``mesh[mesh_axis]``.

    The pool's stream axis and its (S,) per-lane word offsets shard on
    the named axis; the weights (and an mxu lattice's coupling operand)
    are replicated.  Each device runs ``chaotic_ann_bits_pallas`` on its
    lanes, so the words are bit-identical to the unsharded launch; they
    come back whole on every device and the final state stays sharded on
    its stream axis (``_sharded_launch``).  The pool must divide the
    device count: ``ops.chaotic_bits`` pads it with dead lanes.
    """
    cpl = None if coupling is None else jnp.asarray(coupling)
    fn = _sharded_launch(
        chaotic_ann_bits_pallas,
        (P(),) * 4 + (P(mesh_axis, None), P(mesh_axis),
                      None if cpl is None else P()),
        P(mesh_axis, None), mesh, mesh_axis, n_steps=n_steps,
        s_block=s_block, t_block=t_block, unroll=unroll,
        activation=activation, compute_unit=compute_unit, lattice=lattice,
        interpret=interpret)
    return fn(w1, b1, w2, b2, x0, offsets, cpl)


def _lane_concat_launch(w1, b1, w2, b2, x0, word_offset, core_map, row_map,
                        coupling, **kw):
    """``chaotic_ann_gang_bits_pallas`` with the offsets before the maps,
    the operand order of its sharded program."""
    return chaotic_ann_gang_bits_pallas(w1, b1, w2, b2, x0, core_map,
                                        word_offset, row_map, coupling, **kw)


def chaotic_ann_gang_bits_sharded(w1, b1, w2, b2, x0, core_map,
                                  word_offset=0, row_map=None, coupling=None,
                                  *, mesh,
                                  mesh_axis: str = "data", n_steps: int,
                                  s_block: int = 256, t_block: int = 128,
                                  unroll: int = 1, activation: str = "relu",
                                  compute_unit: str = "vpu",
                                  lattice=None, interpret: bool = False):
    """Lane-concat gang launch partitioned across ``mesh[mesh_axis]``.

    Weight slabs (and an mxu lattice's coupling operand) are replicated;
    the pooled stream axis and BOTH scalar-prefetch maps shard on the
    named axis, so each device runs the gang kernel on its own run of
    lane blocks with its *own slice* of the maps.  Lanes evolve
    independently and whitening is indexed by absolute per-lane rows, so
    the result is bit-identical to the unsharded gang launch at any
    device count.  The words come back whole on every device; the final
    state stays sharded on its stream axis (``_sharded_launch``).

    The block axis must divide the device count — pad the maps (and the
    pool) with ``gang_partition_maps`` dead blocks first.
    """
    n_dev = int(mesh.shape[mesh_axis])
    cmap = jnp.asarray(core_map, jnp.int32)
    n_blocks = int(cmap.shape[0])
    if n_blocks % n_dev:
        raise ValueError(
            f"{n_blocks} lane blocks do not divide {n_dev} devices on mesh "
            f"axis {mesh_axis!r}; pad the maps with gang_partition_maps")
    s_total = x0.shape[0]
    off = jnp.broadcast_to(jnp.asarray(word_offset, jnp.uint32), (s_total,))
    rmap = None if row_map is None else jnp.asarray(row_map, jnp.int32)
    cpl = None
    if lattice is not None and compute_unit == "mxu":
        if coupling is None:
            raise ValueError(
                "mxu lattice launches need the dense coupling operand")
        cpl = jnp.asarray(coupling)
    lanes = P(mesh_axis)
    fn = _sharded_launch(
        _lane_concat_launch,
        (P(),) * 4 + (P(mesh_axis, None), lanes, lanes,
                      None if rmap is None else lanes,
                      None if cpl is None else P()),
        P(mesh_axis, None), mesh, mesh_axis, n_steps=n_steps,
        s_block=s_block, t_block=t_block, unroll=unroll,
        activation=activation, compute_unit=compute_unit, lattice=lattice,
        interpret=interpret)
    return fn(w1, b1, w2, b2, x0, off, cmap, rmap, cpl)


def chaotic_ann_gang_stacked_sharded(w1, b1, w2, b2, x0, word_offset=0,
                                     row_map=None, *, mesh,
                                     mesh_axis: str = "data", n_steps: int,
                                     s_block: int = 256, t_block: int = 128,
                                     unroll: int = 1,
                                     activation: str = "relu",
                                     compute_unit: str = "vpu",
                                     lattice=None, interpret: bool = False):
    """Sublane-stacked gang launch partitioned across ``mesh[mesh_axis]``.

    The group's equal-size pools shard on the STREAM axis (every device
    keeps all C cores stacked on sublanes, with 1/n_dev of each pool's
    lanes); the weight tables and the (C,) row map are replicated, since
    a core's freeze row is lane-independent.  The words come back whole
    on every device; the final state stays sharded on its stream axis
    (``_sharded_launch``).  The pool size must divide the device count;
    ragged pool sizes take the lane-concat sharded path instead.
    """
    n_dev = int(mesh.shape[mesh_axis])
    n_cores, s_total = x0.shape[0], x0.shape[1]
    if s_total % n_dev:
        raise ValueError(
            f"stacked pool of {s_total} lanes does not divide {n_dev} "
            f"devices on mesh axis {mesh_axis!r}")
    off = jnp.broadcast_to(jnp.asarray(word_offset, jnp.uint32),
                           (n_cores, s_total))
    rmap = None if row_map is None else jnp.asarray(row_map, jnp.int32)
    fn = _sharded_launch(
        chaotic_ann_gang_stacked_pallas,
        (P(),) * 4 + (P(None, mesh_axis, None), P(None, mesh_axis),
                      None if rmap is None else P()),
        P(None, mesh_axis, None), mesh, mesh_axis, n_steps=n_steps,
        s_block=s_block, t_block=t_block, unroll=unroll,
        activation=activation, compute_unit=compute_unit, lattice=lattice,
        interpret=interpret)
    return fn(w1, b1, w2, b2, x0, off, rmap)

"""Public jit'd entry points for the kernels package.

``chaotic_trajectory`` selects the Pallas kernel (interpret-mode on CPU,
compiled on TPU) or the pure-jnp reference, with a uniform (S, I) API.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

import numpy as np

from repro.kernels import ref
from repro.kernels.chaotic_ann import (
    chaotic_ann_bits_pallas, chaotic_ann_bits_sharded,
    chaotic_ann_gang_bits_pallas, chaotic_ann_gang_bits_sharded,
    chaotic_ann_gang_stacked_pallas, chaotic_ann_gang_stacked_sharded,
    chaotic_ann_pallas, gang_effective_rows, gang_partition_maps)

BACKENDS = ("pallas", "pallas_interpret", "ref")


@functools.lru_cache(maxsize=None)
def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(backend: str = "auto") -> str:
    """The backend a launch with ``backend`` runs on — the one place
    ``"auto"`` is decided: the compiled Pallas kernel (``"pallas"``) when
    JAX's default backend is a TPU, the Pallas interpreter otherwise.
    Explicit backends pass through."""
    if backend == "auto":
        return "pallas" if _on_tpu() else "pallas_interpret"
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected 'auto' or "
                         f"one of {BACKENDS}")
    return backend


def is_build_error(e: BaseException) -> bool:
    """Whether ``e`` was raised while building a kernel launch — tracing,
    lowering or compiling it — rather than while running it.

    Building is deterministic: a retry fails the same way, so such an
    error is a fault of the program, never a transient fault of a core.
    Tracing and lowering raise Python errors (a Pallas TPU lowering gap
    is a ``NotImplementedError``, a refused shape a ``ValueError``,
    Mosaic's own checks a ``MosaicError``/``LoweringException``); the
    TPU compiler raises a ``JaxRuntimeError`` that names Mosaic or the
    VMEM it ran out of.
    """
    if isinstance(e, (NotImplementedError, TypeError, ValueError,
                      IndexError)):
        return True
    if {c.__name__ for c in type(e).__mro__} & {"MosaicError",
                                                "LoweringException"}:
        return True
    msg = str(e)
    return (isinstance(e, jax.errors.JaxRuntimeError)
            and any(k in msg for k in ("Mosaic", "vmem", "VMEM")))


def _lattice_args(params: Dict[str, jax.Array], compute_unit: str):
    """Lattice routing from a params dict.

    A block-coupled lattice core carries ``lattice_meta`` (the static
    descriptor) and ``coupling`` (the dense (I, I) operator) next to its
    lattice-expanded ``w1/b1/w2/b2``.  Returns ``(lattice, coupling)`` for
    the kernels — coupling only on the mxu route, where it is a resident
    MXU operand; the vpu kernels rebuild the operator as wrapped rolls.
    Scalar cores return ``(None, None)``.
    """
    if "lattice_meta" not in params:
        return None, None
    from repro.core.ann import lattice_meta_tuple
    lattice = lattice_meta_tuple(np.asarray(params["lattice_meta"]))
    cpl = jnp.asarray(params["coupling"]) if compute_unit == "mxu" else None
    return lattice, cpl


def _launch_kw(params, backend: str, config, n_steps: int, activation: str,
               s_block: int, t_block: int, unroll: int, compute_unit: str):
    """The static kernel arguments of a launch, and its coupling operand.

    A DSE ``Candidate`` given as ``config`` overrides the explicit
    microarchitecture (s_block, t_block, unroll, compute_unit), so the DSE
    output drives the kernel instantiation.  The lattice descriptor
    (``_lattice_args``) and the backend's ``interpret`` flag are added.
    """
    if config is not None:
        s_block, t_block = config.s_block, config.t_block
        unroll, compute_unit = config.unroll, config.compute_unit
    lattice, cpl = _lattice_args(params, compute_unit)
    return dict(n_steps=n_steps, activation=activation, s_block=s_block,
                t_block=t_block, unroll=unroll, compute_unit=compute_unit,
                lattice=lattice,
                interpret=resolve_backend(backend) == "pallas_interpret"), cpl


def _weights(params, c=None):
    """``(w1, b1, w2, b2)`` of a core, or of member ``c`` of a gang."""
    return tuple(params[k] if c is None else params[k][c]
                 for k in ("w1", "b1", "w2", "b2"))


def _ref_trajectory(w, x0, n_steps: int, kw, cpl):
    """The reference trajectory of weights ``w`` from ``x0``: the lattice
    oracle for a lattice core, the plain one otherwise."""
    if kw["lattice"] is not None:
        return ref.chaotic_ann_lattice_ref(
            *w, x0, n_steps, kw["activation"], lattice=kw["lattice"],
            coupling=cpl, compute_unit=kw["compute_unit"])
    return ref.chaotic_ann_ref(*w, x0, n_steps, kw["activation"])


def _ref_member(params, c: int, x0, rows: int, word_offset, kw, cpl):
    """Gang member ``c``'s pool ``x0`` replayed by the reference for its
    first ``rows`` word rows: the words, zero-filled to the launch's
    ``n_steps // 2`` rows, and the state after them."""
    pad = kw["n_steps"] // 2 - rows
    if rows == 0:
        return jnp.zeros((pad, x0.shape[0]), jnp.uint32), x0
    traj = _ref_trajectory(_weights(params, c), x0, 2 * rows, kw, cpl)
    words = pack_words(traj, word_offset)
    return jnp.pad(words, ((0, pad), (0, 0))), traj[-1]


def _n_dev(mesh, mesh_axis: str) -> int:
    """Devices on ``mesh[mesh_axis]``; 1 without a mesh."""
    return 1 if mesh is None else int(mesh.shape[mesh_axis])


def chaotic_trajectory(params: Dict[str, jax.Array], x0: jax.Array, n_steps: int,
                       *, activation: str = "relu", backend: str = "auto",
                       s_block: int = 256, t_block: int = 128, unroll: int = 1,
                       compute_unit: str = "vpu", config=None) -> jax.Array:
    """Generate (n_steps, S, I) oscillator trajectories.

    backend: 'auto' | 'pallas' | 'pallas_interpret' | 'ref'; 'auto' is
    decided by ``resolve_backend``.  config: optional DSE ``Candidate``
    overriding (s_block, t_block, unroll, compute_unit).
    """
    kw, cpl = _launch_kw(params, backend, config, n_steps, activation,
                         s_block, t_block, unroll, compute_unit)
    if backend == "ref":
        return _ref_trajectory(_weights(params), x0, n_steps, kw, cpl)
    return chaotic_ann_pallas(*_weights(params), x0, cpl, **kw)


def _padded_launch(launch, x0: jax.Array, word_offset,
                   pad: int) -> Tuple[jax.Array, jax.Array]:
    """``launch(x, offsets)`` of the pool ``x0`` grown by ``pad`` dead lanes
    (zero state, zero offset) so that it divides a mesh's device count,
    with the offsets broadcast per lane; the dead lanes are sliced off the
    words and the state it returns."""
    s_total = x0.shape[0]
    off = jnp.broadcast_to(jnp.asarray(word_offset, jnp.uint32), (s_total,))
    if pad:
        x0 = jnp.concatenate([x0, jnp.zeros((pad, x0.shape[1]), x0.dtype)])
        off = jnp.concatenate([off, jnp.zeros(pad, jnp.uint32)])
    words, state = launch(x0, off)
    if pad:
        words, state = words[:, :s_total], state[:s_total]
    return words, state


def chaotic_bits(params: Dict[str, jax.Array], x0: jax.Array, n_steps: int,
                 word_offset=0, *, activation: str = "relu",
                 backend: str = "auto", s_block: int = 256,
                 t_block: int = 128, unroll: int = 1,
                 compute_unit: str = "vpu",
                 mesh=None, mesh_axis: str = "data",
                 config=None) -> Tuple[jax.Array, jax.Array]:
    """Fused PRNG draw: (n_steps // 2, S) uint32 words + (S, I) final state.

    The pallas backends use the fused kernel (trajectory never reaches HBM
    as floats); the 'ref' backend packs the reference trajectory with
    ``pack_words`` — the co-simulation contract of tests/test_fused_bits.py.

    ``mesh``/``mesh_axis`` (pallas backends only) shard the pool's stream
    axis across the named device axis; a pool that does not divide the
    device count is padded with dead lanes (zero state, zero offset) and
    the padding is sliced away.  The words come back whole on every
    device (gathered inside the launch's program, so the host copies one
    buffer); the state stays sharded.  The 'ref' oracle ignores the mesh.
    """
    kw, cpl = _launch_kw(params, backend, config, n_steps, activation,
                         s_block, t_block, unroll, compute_unit)
    w = _weights(params)
    if backend == "ref":
        traj = _ref_trajectory(w, x0, n_steps, kw, cpl)
        return pack_words(traj, word_offset), traj[-1]
    if (n_dev := _n_dev(mesh, mesh_axis)) > 1:
        return _padded_launch(
            lambda x, off: chaotic_ann_bits_sharded(
                *w, x, off, cpl, mesh=mesh, mesh_axis=mesh_axis, **kw),
            x0, word_offset, (-x0.shape[0]) % n_dev)
    return chaotic_ann_bits_pallas(*w, x0, word_offset, cpl, **kw)


def chaotic_bits_gang(params: Dict[str, jax.Array], x0: jax.Array,
                      n_steps: int, word_offset=0, *, core_map,
                      row_map=None,
                      activation: str = "relu", backend: str = "auto",
                      s_block: int = 256, t_block: int = 128,
                      unroll: int = 1, compute_unit: str = "vpu",
                      mesh=None, mesh_axis: str = "data",
                      config=None) -> Tuple[jax.Array, jax.Array]:
    """Gang-scheduled fused PRNG draw: C stacked networks, ONE launch
    (``chaotic_ann_gang_bits_pallas``).

    ``params`` carries a leading core axis; ``x0`` is the concatenated
    (S, I) stream pool, each ``s_block``-lane block homogeneous in core,
    and ``core_map[g]`` names the weight slab of block ``g``.  Per lane the
    result is bit-identical to a per-core ``chaotic_bits`` launch with that
    lane's network (tests/test_gang.py).  ``row_map`` (optional, one entry
    per block) makes the launch demand-shaped: block ``g`` computes
    ``gang_effective_rows(row_map, n_steps, t_block, unroll)[g]`` word rows
    and later rows are garbage that callers slice away.

    The 'ref' backend replays each lane block with its own weights
    (``_ref_member``), effective-row rounding included, garbage rows
    zero-filled.  ``mesh``/``mesh_axis`` (pallas backends only) shard the
    pool and both maps on the block axis, padded with dead zero-row blocks
    until it divides the device count (``gang_partition_maps``); the words
    come back whole on every device, the state stays sharded, and the
    'ref' oracle ignores the mesh — sharding must never change the words.
    """
    kw, cpl = _launch_kw(params, backend, config, n_steps, activation,
                         s_block, t_block, unroll, compute_unit)
    n_rows, s_blk = n_steps // 2, kw["s_block"]
    if backend == "ref":
        cmap = [int(c) for c in jnp.asarray(core_map)]
        eff = (gang_effective_rows(row_map, n_steps, kw["t_block"],
                                   kw["unroll"])
               if row_map is not None else np.full(len(cmap), n_rows))
        off = jnp.broadcast_to(jnp.asarray(word_offset, jnp.uint32),
                               (x0.shape[0],))
        blk = [slice(g * s_blk, (g + 1) * s_blk) for g in range(len(cmap))]
        words, states = zip(*(
            _ref_member(params, c, x0[blk[g]], int(eff[g]), off[blk[g]],
                        kw, cpl) for g, c in enumerate(cmap)))
        return jnp.concatenate(words, axis=1), jnp.concatenate(states)
    rmap = None if row_map is None else jnp.asarray(row_map, jnp.int32)
    if (n_dev := _n_dev(mesh, mesh_axis)) > 1:
        cmap_p, rmap_p, pad = gang_partition_maps(core_map, rmap,
                                                  n_dev=n_dev, n_rows=n_rows)
        return _padded_launch(
            lambda x, off: chaotic_ann_gang_bits_sharded(
                *_weights(params), x, cmap_p, off, rmap_p, cpl, mesh=mesh,
                mesh_axis=mesh_axis, **kw),
            x0, word_offset, pad * s_blk)
    return chaotic_ann_gang_bits_pallas(*_weights(params), x0, core_map,
                                        word_offset, rmap, cpl, **kw)


def chaotic_bits_gang_stacked(params: Dict[str, jax.Array], x0: jax.Array,
                              n_steps: int, word_offset=0, *,
                              row_map=None,
                              activation: str = "relu",
                              backend: str = "auto", s_block: int = 256,
                              t_block: int = 128, unroll: int = 1,
                              compute_unit: str = "vpu",
                              mesh=None, mesh_axis: str = "data",
                              config=None) -> Tuple[jax.Array, jax.Array]:
    """Sublane-stacked gang draw for C EQUAL-shape pools: one grid cell
    advances the whole group (``chaotic_ann_gang_stacked_pallas``; vpu
    only — the stacked update is the broadcast-FMA order itself).

    ``params`` carries a leading core axis; ``x0`` is (C, S, I).  ``row_map``
    (optional, (C,)) freezes core ``c``'s state after exactly ``row_map[c]``
    word rows, so its state and word prefix match a per-core launch of
    that many rows; later rows are garbage.  Returns words
    (n_steps // 2, C, S) and final state (C, S, I).

    ``mesh``/``mesh_axis`` (pallas backends only) shard the pools on the
    STREAM axis — every device keeps the full sublane stack with 1/n_dev
    of each pool's lanes, and the pool size must divide the device count.
    The words come back whole on every device; the state stays sharded.
    The 'ref' oracle ignores the mesh.
    """
    kw, cpl = _launch_kw(params, backend, config, n_steps, activation,
                         s_block, t_block, unroll, compute_unit)
    if backend == "ref":
        n_cores, n_rows = x0.shape[0], n_steps // 2
        rows = (np.minimum(np.asarray(row_map, np.int64), n_rows)
                if row_map is not None else np.full(n_cores, n_rows))
        off = jnp.broadcast_to(jnp.asarray(word_offset, jnp.uint32),
                               x0.shape[:2])
        words, states = zip(*(
            _ref_member(params, c, x0[c], int(rows[c]), off[c], kw, cpl)
            for c in range(n_cores)))
        return jnp.stack(words, axis=1), jnp.stack(states)
    rmap = None if row_map is None else jnp.asarray(row_map, jnp.int32)
    if _n_dev(mesh, mesh_axis) > 1:
        return chaotic_ann_gang_stacked_sharded(
            *_weights(params), x0, word_offset, rmap, mesh=mesh,
            mesh_axis=mesh_axis, **kw)
    return chaotic_ann_gang_stacked_pallas(*_weights(params), x0,
                                           word_offset, rmap, **kw)


def uniform_from_trajectory(traj: jax.Array) -> jax.Array:
    """Map trajectory floats in [-1, 1]-ish range to uniform [0, 1) floats by
    keeping the chaotic low-order mantissa bits (the PRNG post-processing
    stage of the paper's Fig. 1 oscillator-as-PRNG usage).

    Uses the top 24 bits so every representable output is strictly < 1.0
    (dividing the full u32 by 2^32 rounds words near 2^32 up to exactly 1.0
    in f32, breaking the half-open-interval contract).
    """
    bits = bits_from_trajectory(traj)
    return (bits >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2 ** -24)


def _fold_low16(traj: jax.Array) -> jax.Array:
    """(..., I) floats -> (...,) uint32: low mantissa bits, I folded in.

    Chaotic trajectories are smooth at the top of the mantissa but the low
    mantissa bits decorrelate in a few steps (positive Lyapunov exponent).
    The I system dimensions are strongly coupled but their low bits differ;
    XOR with odd shifts mixes them.

    For f32 the low 16 bits of the bit pattern are taken.  Half-width
    floats are bitcast at their own width and masked to their mantissa —
    casting bf16 up to f32 first would leave the low 16 bits all zero and
    emit a zero-entropy counter hash.
    """
    if traj.dtype.itemsize == 2:
        u = jax.lax.bitcast_convert_type(traj, jnp.uint16).astype(jnp.uint32)
        mask = (1 << jnp.finfo(traj.dtype).nmant) - 1
        lo = u & jnp.uint32(mask)
    else:
        u = jax.lax.bitcast_convert_type(traj.astype(jnp.float32), jnp.uint32)
        lo = u & jnp.uint32(0xFFFF)
    folded = lo[..., 0]
    for i in range(1, traj.shape[-1]):
        folded = folded ^ (lo[..., i] << jnp.uint32(5 * i % 16))
    return folded


def _finalize_words(words: jax.Array) -> jax.Array:
    """Final avalanche (xorshift-multiply, Murmur3 finalizer style)."""
    words = words ^ (words >> jnp.uint32(16))
    words = words * jnp.uint32(0x85EBCA6B)
    words = words ^ (words >> jnp.uint32(13))
    words = words * jnp.uint32(0xC2B2AE35)
    words = words ^ (words >> jnp.uint32(16))
    return words


def bits_from_trajectory(traj: jax.Array) -> jax.Array:
    """Extract uint32 words from chaotic samples.

    Following the standard chaotic-PRNG recipe, we take the low 16 mantissa
    bits of each f32 sample and pack two consecutive samples per u32 word,
    XOR-folded with a golden-ratio Weyl sequence to whiten residual bias.
    Input (..., I) floats; output (...,) uint32 (I folded in): the words of
    ``pack_words`` from word row 0.
    """
    return pack_words(traj)


def pack_words(traj: jax.Array, word_offset=0) -> jax.Array:
    """Offset-aware reference of the fused kernel's packing stage.

    traj: (T, ..., I) floats with T even.  word_offset: scalar or per-stream
    uint32, the global word-row index of the first packed row.  The offset
    is what lets a chunked, resumable stream reproduce one long draw
    bit-exactly.  Returns (T // 2, ...) uint32.
    """
    folded = _fold_low16(traj)
    # Pack pairs along the leading (time) axis into 32-bit words.
    t = folded.shape[0] // 2
    words = (folded[0:2 * t:2] << jnp.uint32(16)) | folded[1:2 * t:2]
    # Weyl whitening by absolute word row.
    idx = jnp.arange(t, dtype=jnp.uint32).reshape(
        (t,) + (1,) * (words.ndim - 1)) + jnp.asarray(word_offset, jnp.uint32)
    return _finalize_words(words ^ (idx * jnp.uint32(0x9E3779B9)))

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
from repro.kernels.chaotic_ann import (chaotic_ann_bits_pallas,
                                       chaotic_ann_gang_bits_pallas,
                                       chaotic_ann_gang_bits_sharded,
                                       chaotic_ann_gang_stacked_pallas,
                                       chaotic_ann_gang_stacked_sharded,
                                       chaotic_ann_pallas,
                                       gang_effective_rows,
                                       gang_partition_maps)

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


def _kernel_kwargs(config) -> Dict[str, object]:
    """Kernel microarchitecture kwargs from a DSE ``Candidate``."""
    return dict(s_block=config.s_block, t_block=config.t_block,
                unroll=config.unroll, compute_unit=config.compute_unit)


def _lattice_args(params: Dict[str, jax.Array], compute_unit: str):
    """Lattice routing from a params dict.

    A block-coupled lattice core carries two extra keys next to the
    standard (lattice-expanded) ``w1/b1/w2/b2``: ``lattice_meta`` (the
    static descriptor) and ``coupling`` (the dense (I, I) operator).
    Returns ``(lattice, coupling)`` for the kernels — coupling only on the
    mxu route, where it is a resident MXU operand; the vpu kernels rebuild
    the operator from the descriptor as wrapped rolls.
    Scalar cores return ``(None, None)`` and every call site degrades to
    the exact pre-lattice behavior.
    """
    if "lattice_meta" not in params:
        return None, None
    from repro.core.ann import lattice_meta_tuple
    lattice = lattice_meta_tuple(np.asarray(params["lattice_meta"]))
    cpl = None
    if compute_unit == "mxu":
        cpl = jnp.asarray(params["coupling"])
    return lattice, cpl


def chaotic_trajectory(params: Dict[str, jax.Array], x0: jax.Array, n_steps: int,
                       *, activation: str = "relu", backend: str = "auto",
                       s_block: int = 256, t_block: int = 128, unroll: int = 1,
                       compute_unit: str = "vpu", config=None) -> jax.Array:
    """Generate (n_steps, S, I) oscillator trajectories.

    backend: 'auto' | 'pallas' | 'pallas_interpret' | 'ref'; 'auto' is
    decided by ``resolve_backend``.
    config: optional ``repro.core.dse.Candidate`` — when given, overrides the
    explicit (s_block, t_block, unroll, compute_unit) arguments so the DSE
    output drives the kernel instantiation.
    """
    w1, b1, w2, b2 = params["w1"], params["b1"], params["w2"], params["b2"]
    kw = dict(s_block=s_block, t_block=t_block, unroll=unroll,
              compute_unit=compute_unit)
    if config is not None:
        kw = _kernel_kwargs(config)
    lattice, cpl = _lattice_args(params, kw["compute_unit"])
    if backend == "ref":
        if lattice is not None:
            return ref.chaotic_ann_lattice_ref(
                w1, b1, w2, b2, x0, n_steps, activation, lattice=lattice,
                coupling=cpl, compute_unit=kw["compute_unit"])
        return ref.chaotic_ann_ref(w1, b1, w2, b2, x0, n_steps, activation)
    interpret = resolve_backend(backend) == "pallas_interpret"
    return chaotic_ann_pallas(
        w1, b1, w2, b2, x0, cpl, n_steps=n_steps, activation=activation,
        lattice=lattice, interpret=interpret, **kw)


def chaotic_bits(params: Dict[str, jax.Array], x0: jax.Array, n_steps: int,
                 word_offset=0, *, activation: str = "relu",
                 backend: str = "auto", s_block: int = 256,
                 t_block: int = 128, unroll: int = 1,
                 compute_unit: str = "vpu",
                 config=None) -> Tuple[jax.Array, jax.Array]:
    """Fused PRNG draw: (n_steps // 2, S) uint32 words + (S, I) final state.

    The pallas backends use the fused kernel (trajectory never reaches HBM
    as floats); the 'ref' backend materializes the reference trajectory and
    packs it with ``pack_words`` — both produce the same words for the same
    float trajectory, which is the co-simulation contract tested in
    tests/test_fused_bits.py.
    """
    w1, b1, w2, b2 = params["w1"], params["b1"], params["w2"], params["b2"]
    kw = dict(s_block=s_block, t_block=t_block, unroll=unroll,
              compute_unit=compute_unit)
    if config is not None:
        kw = _kernel_kwargs(config)
    lattice, cpl = _lattice_args(params, kw["compute_unit"])
    if backend == "ref":
        if lattice is not None:
            traj = ref.chaotic_ann_lattice_ref(
                w1, b1, w2, b2, x0, n_steps, activation, lattice=lattice,
                coupling=cpl, compute_unit=kw["compute_unit"])
        else:
            traj = ref.chaotic_ann_ref(w1, b1, w2, b2, x0, n_steps,
                                       activation)
        return pack_words(traj, word_offset), traj[-1]
    interpret = resolve_backend(backend) == "pallas_interpret"
    return chaotic_ann_bits_pallas(
        w1, b1, w2, b2, x0, word_offset, cpl, n_steps=n_steps,
        activation=activation, lattice=lattice, interpret=interpret, **kw)


def chaotic_bits_gang(params: Dict[str, jax.Array], x0: jax.Array,
                      n_steps: int, word_offset=0, *, core_map,
                      row_map=None,
                      activation: str = "relu", backend: str = "auto",
                      s_block: int = 256, t_block: int = 128,
                      unroll: int = 1, compute_unit: str = "vpu",
                      mesh=None, mesh_axis: str = "data",
                      partitioner=None,
                      config=None) -> Tuple[jax.Array, jax.Array]:
    """Gang-scheduled fused PRNG draw: C stacked networks, ONE launch.

    ``params`` carries a leading core axis (w1 (C, I, H), b1 (C, H),
    w2 (C, H, I), b2 (C, I)); ``x0`` is the concatenated (S, I) stream pool
    with each ``s_block``-lane block homogeneous in core, and
    ``core_map[g]`` names the weight slab of block ``g``.  Lanes evolve
    independently, so per lane the result is bit-identical to a per-core
    ``chaotic_bits`` launch with that lane's network — the property the
    farm's gang scheduler relies on (tests/test_gang.py).

    ``row_map`` (optional, same shape as ``core_map``) makes the launch
    demand-shaped: block ``g`` computes only
    ``gang_effective_rows(row_map, n_steps, t_block, unroll)[g]`` word
    rows (its demand rounded up to the kernel's unroll-chunk granularity)
    and its state advances by exactly that many; word rows past a block's
    effective demand are unwritten garbage that callers must slice away.

    The 'ref' backend replays each lane block through the reference
    trajectory + ``pack_words`` with its own weights (C tiny launches),
    keeping the usual co-simulation contract — including the effective-row
    rounding of a ragged launch (garbage rows are zero-filled there).

    ``mesh``/``mesh_axis`` (pallas backends only) shard the launch across
    the named device axis: the pool and both scalar-prefetch maps
    partition on the lane/block axis while the weight slabs replicate, so
    one *logical* gang launch spans every device bit-identically.
    ``partitioner`` overrides the per-device map partitioner (default
    ``gang_partition_maps``, which pads the block axis with dead zero-row
    blocks until it divides the device count).  The 'ref' oracle ignores
    the mesh — sharding must never change the words.
    """
    kw = dict(s_block=s_block, t_block=t_block, unroll=unroll,
              compute_unit=compute_unit)
    if config is not None:
        kw = _kernel_kwargs(config)
    lattice, cpl = _lattice_args(params, kw["compute_unit"])
    if backend == "ref":
        s_blk = kw["s_block"]
        cmap = [int(c) for c in jnp.asarray(core_map)]
        eff = (gang_effective_rows(row_map, n_steps, kw["t_block"],
                                   kw["unroll"])
               if row_map is not None else
               np.full(len(cmap), n_steps // 2, np.int32))
        off = jnp.broadcast_to(jnp.asarray(word_offset, jnp.uint32),
                               (x0.shape[0],))
        n_rows = n_steps // 2
        words_parts, state_parts = [], []
        for g, c in enumerate(cmap):
            xg = x0[g * s_blk:(g + 1) * s_blk]
            r_g = int(eff[g])
            if r_g == 0:
                words_parts.append(jnp.zeros((n_rows, s_blk), jnp.uint32))
                state_parts.append(xg)
                continue
            if lattice is not None:
                traj = ref.chaotic_ann_lattice_ref(
                    params["w1"][c], params["b1"][c], params["w2"][c],
                    params["b2"][c], xg, 2 * r_g, activation,
                    lattice=lattice, coupling=cpl,
                    compute_unit=kw["compute_unit"])
            else:
                traj = ref.chaotic_ann_ref(
                    params["w1"][c], params["b1"][c], params["w2"][c],
                    params["b2"][c], xg, 2 * r_g, activation)
            w = pack_words(traj, off[g * s_blk:(g + 1) * s_blk])
            if r_g < n_rows:
                w = jnp.concatenate(
                    [w, jnp.zeros((n_rows - r_g, s_blk), jnp.uint32)])
            words_parts.append(w)
            state_parts.append(traj[-1])
        return (jnp.concatenate(words_parts, axis=1),
                jnp.concatenate(state_parts, axis=0))
    interpret = resolve_backend(backend) == "pallas_interpret"
    rmap = None if row_map is None else jnp.asarray(row_map, jnp.int32)
    if mesh is not None and int(mesh.shape[mesh_axis]) > 1:
        n_dev = int(mesh.shape[mesh_axis])
        part = partitioner if partitioner is not None else gang_partition_maps
        cmap_p, rmap_p, pad = part(core_map, rmap, n_dev=n_dev,
                                   n_rows=n_steps // 2)
        s_total = x0.shape[0]
        xp, offp = x0, jnp.broadcast_to(
            jnp.asarray(word_offset, jnp.uint32), (s_total,))
        if pad:
            s_blk = kw["s_block"]
            xp = jnp.concatenate(
                [x0, jnp.zeros((pad * s_blk, x0.shape[1]), x0.dtype)])
            offp = jnp.concatenate(
                [offp, jnp.zeros(pad * s_blk, jnp.uint32)])
        words, state = chaotic_ann_gang_bits_sharded(
            params["w1"], params["b1"], params["w2"], params["b2"], xp,
            cmap_p, offp, rmap_p, cpl, mesh=mesh, mesh_axis=mesh_axis,
            n_steps=n_steps, activation=activation, lattice=lattice,
            interpret=interpret, **kw)
        if pad:
            words, state = words[:, :s_total], state[:s_total]
        return words, state
    return chaotic_ann_gang_bits_pallas(
        params["w1"], params["b1"], params["w2"], params["b2"], x0,
        core_map, word_offset, rmap, cpl, n_steps=n_steps,
        activation=activation, lattice=lattice, interpret=interpret, **kw)


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
    advances the whole group.

    ``params`` carries a leading core axis; ``x0`` is (C, S, I) — one pool
    per core, all the same shape.  The fast path for homogeneous farm
    groups (see ``chaotic_ann_gang_stacked_pallas``); ragged groups go
    through ``chaotic_bits_gang``.  vpu groups only — the stacked update
    is the broadcast-FMA order itself.

    ``row_map`` (optional, (C,)) freezes core ``c``'s state after exactly
    ``row_map[c]`` word rows (no FMA saved — the sublane stack is one
    fused sweep — but the core's final state and word prefix match a
    per-core launch of that many rows, so a demand-shaped absorb never
    buffers overdraw).  Word rows past a core's demand are garbage.
    Returns words (n_steps // 2, C, S) and final state (C, S, I).

    ``mesh``/``mesh_axis`` (pallas backends only) shard the equal-size
    pools on the STREAM axis across the named device axis — every device
    keeps the full sublane stack with 1/n_dev of each pool's lanes; the
    pool size must divide the device count (the gang scheduler checks
    this before choosing the stacked layout on a mesh).  The 'ref' oracle
    ignores the mesh.
    """
    kw = dict(s_block=s_block, t_block=t_block, unroll=unroll,
              compute_unit=compute_unit)
    if config is not None:
        kw = _kernel_kwargs(config)
    lattice, cpl = _lattice_args(params, kw["compute_unit"])
    if backend == "ref":
        n_cores = x0.shape[0]
        n_rows = n_steps // 2
        rows = (np.minimum(np.asarray(row_map, np.int64), n_rows)
                if row_map is not None else
                np.full(n_cores, n_rows, np.int64))
        off = jnp.broadcast_to(jnp.asarray(word_offset, jnp.uint32),
                               x0.shape[:2])
        words_parts, state_parts = [], []
        for c in range(n_cores):
            r_c = int(rows[c])
            if r_c == 0:
                words_parts.append(
                    jnp.zeros((n_rows, x0.shape[1]), jnp.uint32))
                state_parts.append(x0[c])
                continue
            if lattice is not None:
                traj = ref.chaotic_ann_lattice_ref(
                    params["w1"][c], params["b1"][c], params["w2"][c],
                    params["b2"][c], x0[c], 2 * r_c, activation,
                    lattice=lattice, coupling=cpl,
                    compute_unit=kw["compute_unit"])
            else:
                traj = ref.chaotic_ann_ref(
                    params["w1"][c], params["b1"][c], params["w2"][c],
                    params["b2"][c], x0[c], 2 * r_c, activation)
            w = pack_words(traj, off[c])
            if r_c < n_rows:
                w = jnp.concatenate(
                    [w, jnp.zeros((n_rows - r_c, x0.shape[1]), jnp.uint32)])
            words_parts.append(w)
            state_parts.append(traj[-1])
        return (jnp.stack(words_parts, axis=1),
                jnp.stack(state_parts, axis=0))
    interpret = resolve_backend(backend) == "pallas_interpret"
    rmap = None if row_map is None else jnp.asarray(row_map, jnp.int32)
    if mesh is not None and int(mesh.shape[mesh_axis]) > 1:
        return chaotic_ann_gang_stacked_sharded(
            params["w1"], params["b1"], params["w2"], params["b2"], x0,
            word_offset, rmap, mesh=mesh, mesh_axis=mesh_axis,
            n_steps=n_steps, activation=activation, lattice=lattice,
            interpret=interpret, **kw)
    return chaotic_ann_gang_stacked_pallas(
        params["w1"], params["b1"], params["w2"], params["b2"], x0,
        word_offset, rmap, n_steps=n_steps, activation=activation,
        lattice=lattice, interpret=interpret, **kw)


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
    Input (..., I) floats; output (...,) uint32 (I folded in).
    """
    folded = _fold_low16(traj)
    # Pack pairs along the leading (time) axis into 32-bit words.
    t = folded.shape[0] // 2
    words = (folded[0:2 * t:2] << jnp.uint32(16)) | folded[1:2 * t:2]
    # Weyl whitening.
    idx = jnp.arange(t, dtype=jnp.uint32)
    weyl = idx * jnp.uint32(0x9E3779B9)
    words = words ^ weyl.reshape((t,) + (1,) * (words.ndim - 1))
    return _finalize_words(words)


def pack_words(traj: jax.Array, word_offset=0) -> jax.Array:
    """Offset-aware reference of the fused kernel's packing stage.

    traj: (T, S, I) floats with T even.  word_offset: scalar or (S,) uint32,
    the global word-row index of the first packed row (per stream).  Equal to
    ``bits_from_trajectory(traj)`` when word_offset == 0; the offset is what
    lets a chunked, resumable stream reproduce one long draw bit-exactly.
    Returns (T // 2, S) uint32.
    """
    folded = _fold_low16(traj)
    t = folded.shape[0] // 2
    words = (folded[0:2 * t:2] << jnp.uint32(16)) | folded[1:2 * t:2]
    off = jnp.asarray(word_offset, jnp.uint32)
    idx = jnp.arange(t, dtype=jnp.uint32)[:, None] + off[None, ...]
    words = words ^ (idx * jnp.uint32(0x9E3779B9))
    return _finalize_words(words)

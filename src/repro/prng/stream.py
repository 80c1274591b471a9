"""Chaotic-oscillator PRNG streams (the paper's end application).

The trained ANN oscillator (paper Fig. 1: MUX selecting seed vs feedback)
becomes a batched, jit-able random-bit source.  It is plugged into the LM
training stack as a first-class substrate: data-pipeline shuffling, dropout
masks, and stochastic rounding for gradient compression all draw from it.

Two layers:

  * ``ChaoticPRNG`` + ``StreamState`` — the chunked, resumable engine.  The
    oscillator state lives on device and is threaded explicitly; every draw
    is one fused-kernel launch (``ops.chaotic_bits``) that emits packed
    uint32 words and the next state, so the float trajectory never makes an
    HBM round-trip.  Word emission is defined in absolute word-row space
    (the Weyl counter travels with the state), which makes the emitted
    sequence invariant to how draws are chunked, and makes ``fork()``
    counter-based: children are derived from the (seed, lineage-path) hash,
    never from consumed parent entropy.
  * ``ChaoticStream`` — the original stateful convenience wrapper, now a
    thin shim over the engine (API-compatible with the seed version).

Seeding: stream seeds are derived from a counter via a splitmix64-style hash
and placed in the normalized attractor box; sensitivity to initial conditions
gives stream independence after a short burn-in (Lyapunov decorrelation),
which the NIST subset in tests verifies empirically.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import pathlib
import warnings
import zipfile
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

_GOLDEN = 0x9E3779B9


def _splitmix_seeds(counter: jax.Array, n_streams: int, dim: int) -> jax.Array:
    """Derive (S, I) normalized seeds in [-0.9, 0.9] from an integer counter."""
    idx = counter.astype(jnp.uint32) * jnp.uint32(_GOLDEN) + jnp.arange(
        n_streams * dim, dtype=jnp.uint32).reshape(n_streams, dim) * jnp.uint32(0x85EBCA77)
    z = idx
    z = (z ^ (z >> jnp.uint32(16))) * jnp.uint32(0x7FEB352D)
    z = (z ^ (z >> jnp.uint32(15))) * jnp.uint32(0x846CA68B)
    z = z ^ (z >> jnp.uint32(16))
    return (z.astype(jnp.float32) / jnp.float32(2 ** 32) - 0.5) * 1.8


def _lineage_counter(seed: int, path: Tuple[int, ...]) -> int:
    """Fold a fork path into a 32-bit seed counter (splitmix-style chain)."""
    c = seed & 0xFFFFFFFF
    for p in path:
        c = (c ^ ((p + 1) * 0x85EBCA77)) & 0xFFFFFFFF
        c = (c ^ (c >> 16)) * 0x7FEB352D & 0xFFFFFFFF
        c = (c ^ (c >> 15)) * 0x846CA68B & 0xFFFFFFFF
        c = c ^ (c >> 16)
    return c


def effective_burn_in(burn_in: int) -> int:
    """The burn-in the engine actually runs, validated.

    The fused kernel packs 2 samples per word row, so burn-in advances in
    pairs of steps; an odd request is rounded UP to the next even count.
    This used to happen silently — two configurations differing only in
    burn-in parity produced identical streams while *recording* different
    burn-ins, so a registry/snapshot keyed on the requested value could
    disagree with the stream it described.  Now the rounding warns, and
    every persisted record (``StreamState.burn_in``, service snapshots, the
    registry fingerprint) stores this effective value.
    """
    b = int(burn_in)
    if b < 0:
        raise ValueError(f"burn_in must be >= 0, got {burn_in}")
    if b % 2:
        warnings.warn(
            f"odd burn_in {b} rounded up to {b + 1}: the fused kernel "
            f"advances two oscillator steps per word row",
            UserWarning, stacklevel=2)
        b += 1
    return b


def _round_rows(n_rows: int, t_block: int) -> int:
    """Word rows to actually launch for a draw needing ``n_rows``.

    Large draws round up to whole time-blocks: an odd/prime row count would
    gcd-collapse the autotuned t_block inside the kernel, paying per-grid-
    cell overhead across a big launch.  Small draws (under one time-block)
    round up only to the next power of two — the kernel's gcd fallback
    shrinks t_block for them, which costs far less than computing (and
    buffering) a full block for a 10-word request.  Either way the overdraw
    is buffered and delivered first on the next draw, so the emitted
    sequence is unchanged (absolute-row Weyl indexing).
    """
    q = max(1, t_block // 2)
    if n_rows >= q:
        return -(-n_rows // q) * q
    r = 4
    while r < n_rows:
        r *= 2
    return r


@dataclasses.dataclass
class StreamState:
    """Resumable stream cursor: everything needed to continue a stream.

    ``x`` is the on-device oscillator state; ``row`` is the absolute word-row
    counter (the Weyl offset of the next emitted row); ``buf`` holds words
    already generated by the kernel but not yet handed to the caller (a draw
    always completes whole S-wide rows), so arbitrary chunk sizes stay
    bit-identical to one long draw.
    """

    x: jax.Array
    row: int = 0
    buf: np.ndarray = dataclasses.field(
        default_factory=lambda: np.empty(0, np.uint32))
    seed: int = 0
    path: Tuple[int, ...] = ()
    # Effective (post-rounding) burn-in the stream was seeded with — part
    # of the stream's identity: replaying a stream from (seed, path)
    # reproduces it only under the same effective burn-in.
    burn_in: int = 0

    @property
    def n_streams(self) -> int:
        return self.x.shape[0]


class ChaoticPRNG:
    """Chunked, resumable chaotic PRNG over the fused bits kernel.

    Holds only static configuration (weights + kernel microarchitecture);
    stream state is explicit, so one engine can serve many independent
    streams and states can be snapshotted/restored freely.
    """

    def __init__(self, params: Dict[str, jax.Array], *, n_streams: int = 256,
                 burn_in: int = 16, activation: str = "relu",
                 backend: str = "auto", config=None, dtype=None):
        self.params = {k: jnp.asarray(v) for k, v in params.items()}
        self.n_streams = int(n_streams)
        self.burn_in = effective_burn_in(burn_in)
        self.activation = activation
        self.backend = backend
        self.dim = self.params["w1"].shape[0]
        # The kernel computes in the dtype of the state it is handed; f32
        # unless the caller pins a half-width core (e.g. a bf16 DSE solution).
        self.dtype = jnp.dtype(dtype) if dtype is not None else jnp.float32
        if config is None:
            from repro.core.dse import select_config
            n_nodes = 1
            if "lattice_meta" in self.params:
                from repro.core.ann import lattice_meta_tuple
                n_nodes = lattice_meta_tuple(self.params["lattice_meta"])[0]
            config = select_config(self.dim, self.params["w1"].shape[1],
                                   s_total=self.n_streams, dtype=self.dtype,
                                   n_nodes=n_nodes)
        self.config = config

    # -- state management ---------------------------------------------------

    def init(self, seed: int = 0, path: Tuple[int, ...] = ()) -> StreamState:
        """Seed + burn in a fresh stream (rows start counting at 0 after)."""
        counter = _lineage_counter(seed, path)
        x = _splitmix_seeds(jnp.asarray(counter, jnp.uint32),
                            self.n_streams, self.dim).astype(self.dtype)
        if self.burn_in:
            _, x = self._draw(x, self.burn_in // 2, 0)
        return StreamState(x=x, row=0, seed=seed, path=path,
                           burn_in=self.burn_in)

    def fork(self, state: StreamState, n_children: int) -> List[StreamState]:
        """Counter-based fork: children derive from (seed, path + (i,)).

        The parent is untouched — forking never consumes parent entropy, so
        fork trees are reproducible regardless of draw interleaving.
        """
        return [self.init(state.seed, state.path + (i,))
                for i in range(n_children)]

    # -- drawing ------------------------------------------------------------

    def _draw(self, x: jax.Array, n_rows: int, row: int):
        """One fused launch: n_rows word rows + the advanced state."""
        return ops.chaotic_bits(
            self.params, x, 2 * n_rows, jnp.uint32(row),
            activation=self.activation, backend=self.backend,
            config=self.config)

    def next_words(self, state: StreamState, n_words: int
                   ) -> Tuple[np.ndarray, StreamState]:
        """Draw ``n_words`` uint32 words; returns (words, advanced state).

        The emitted sequence is the row-major flattening of word rows, so
        any chunking of draws yields the same sequence bit-for-bit.
        """
        take = min(len(state.buf), n_words)
        parts = [state.buf[:take]]
        buf = state.buf[take:]
        x, row = state.x, state.row
        need = n_words - take
        if need > 0:
            n_rows = _round_rows(-(-need // self.n_streams),
                                 self.config.t_block)
            words2d, x = self._draw(x, n_rows, row)
            flat = np.asarray(words2d).reshape(-1)
            parts.append(flat[:need])
            buf = flat[need:]
            row += n_rows
        return (np.concatenate(parts),
                StreamState(x=x, row=row, buf=buf, seed=state.seed,
                            path=state.path, burn_in=state.burn_in))


@dataclasses.dataclass
class ChaoticStream:
    """Stateful convenience wrapper over the resumable engine (seed API)."""

    params: Dict[str, jax.Array]
    activation: str = "relu"
    n_streams: int = 256
    burn_in: int = 16
    backend: str = "auto"
    counter: int = 0

    @classmethod
    def from_trained(cls, params, **kw) -> "ChaoticStream":
        return cls(params={k: jnp.asarray(v) for k, v in params.items()}, **kw)

    @functools.cached_property
    def _engine(self) -> ChaoticPRNG:
        return ChaoticPRNG(self.params, n_streams=self.n_streams,
                           burn_in=self.burn_in, activation=self.activation,
                           backend=self.backend)

    @functools.cached_property
    def _state_box(self) -> List[StreamState]:
        return [self._engine.init(self.counter)]

    def _draw_words(self, n_words: int) -> jax.Array:
        words, self._state_box[0] = self._engine.next_words(
            self._state_box[0], n_words)
        return jnp.asarray(words)

    def uniform(self, shape: Tuple[int, ...], dtype=jnp.float32) -> jax.Array:
        n = int(np.prod(shape)) if shape else 1
        words = self._draw_words(n)
        # Keep one bit more than the TARGET dtype's mantissa: every output
        # k * 2^-m is then exactly representable after the final cast, so
        # nothing rounds up to 1.0 (a full u32 / 2^32 does, in any float).
        m = min(24, jnp.finfo(jnp.dtype(dtype)).nmant + 1)
        u = (words[:n] >> jnp.uint32(32 - m)).astype(jnp.float32) \
            * jnp.float32(2.0 ** -m)
        return u.reshape(shape).astype(dtype)

    def bits(self, n_words: int) -> jax.Array:
        return self._draw_words(n_words)[:n_words]

    def bernoulli(self, p: float, shape: Tuple[int, ...]) -> jax.Array:
        return self.uniform(shape) < p

    def permutation(self, n: int) -> jax.Array:
        """Random permutation via argsort of chaotic keys (shuffling)."""
        return jnp.argsort(self.bits(n))

    def fork(self, n_children: int) -> List["ChaoticStream"]:
        """Counter-based fork at the wrapper level (fresh child streams)."""
        return [dataclasses.replace(
            self, counter=_lineage_counter(self.counter, (i,)))
            for i in range(n_children)]


@functools.partial(jax.jit, static_argnames=("n_words", "n_streams", "burn_in",
                                             "activation", "backend"))
def draw_words(w1, b1, w2, b2, counter: int, n_words: int, n_streams: int,
               burn_in: int, activation: str, backend: str) -> jax.Array:
    """Legacy stateless one-shot draw (kept for API compatibility).

    New code should use ``ChaoticPRNG`` — it draws through the fused kernel
    and resumes instead of re-burning in on every call.
    """
    params = {"w1": w1, "b1": b1, "w2": w2, "b2": b2}
    dim = params["w1"].shape[0]
    x0 = _splitmix_seeds(jnp.asarray(counter, jnp.uint32), n_streams, dim)
    # 2 samples -> 1 word; streams interleave in the flattened output.
    steps_needed = 2 * ((n_words + n_streams - 1) // n_streams) + 2 * burn_in
    steps_needed = max(steps_needed, 4)
    traj = ops.chaotic_trajectory(params, x0, steps_needed,
                                  activation=activation, backend=backend)
    # Drop ALL 2*burn_in burn-in samples generated above: dropping fewer
    # would emit early words from a half-burned-in, seed-correlated prefix.
    words = ops.bits_from_trajectory(traj[2 * burn_in:])
    return words.reshape(-1)[:n_words]


# ---------------------------------------------------------------------------
# Per-system trained-weight registry (disk-cached; covers all of SYSTEMS)
# ---------------------------------------------------------------------------

# Per-system training recipe: the paper's Table-II hyperparameters, with a
# wider hidden layer for the 4-D hyperchaotic system (I*H head-room).
_TRAIN_HIDDEN = {"hyperlorenz": 16}
_TRAIN_EPOCHS = 120
_TRAIN_LR = 3e-3
_TRAIN_SAMPLES = 20_000

_BUNDLE_KEYS = ("w1", "b1", "w2", "b2", "scale", "offset")
_WEIGHTS_CACHE: Dict[Tuple[str, int], Dict[str, np.ndarray]] = {}
_FINGERPRINT_KEY = "recipe_fingerprint"


def registry_fingerprint(system: str, burn_in: Optional[int] = None) -> str:
    """Version stamp of one registry entry: hash of (training recipe, jax).

    Registry npz files carry this stamp; a loaded entry whose stamp
    disagrees with the *current* recipe constants or jax version is stale
    (a recipe change or a numerics-affecting jax upgrade happened since it
    was trained) and is retrained instead of being served.

    ``burn_in``, when given, folds the EFFECTIVE (even-rounded) burn-in
    into the stamp — consumers that persist a stream identity alongside
    weights (the serving registry) key on the burn-in the engine actually
    ran, so two configs differing only in odd/even spelling stamp
    identically and a genuinely different burn-in stamps differently.
    ``None`` (the default) keeps pure weight-registry stamps — and every
    npz already on disk — unchanged.
    """
    import hashlib

    import jax as _jax
    recipe = (system, _TRAIN_HIDDEN.get(system, 8), _TRAIN_EPOCHS,
              _TRAIN_LR, _TRAIN_SAMPLES, _jax.__version__)
    if burn_in is not None:
        recipe = recipe + (effective_burn_in(burn_in),)
    return hashlib.sha256(repr(recipe).encode()).hexdigest()[:16]


def weights_dir() -> pathlib.Path:
    """Disk location of the weight registry (REPRO_WEIGHTS_DIR overrides)."""
    env = os.environ.get("REPRO_WEIGHTS_DIR")
    if env:
        return pathlib.Path(env)
    return pathlib.Path(__file__).resolve().parents[3] / "results" / "weights"


def registry_weights(system: str) -> Optional[Dict[str, np.ndarray]]:
    """The committed registry bundle of a (scalar) system, or ``None``
    when its file is missing, torn, or stamped by another training recipe
    or jax version — the entries ``trained_oscillator`` would retrain."""
    path = weights_dir() / f"{system}.npz"
    try:
        bundle = {k: np.asarray(v) for k, v in np.load(path).items()}
    except (OSError, ValueError, EOFError, zipfile.BadZipFile):
        return None
    stamp = bundle.pop(_FINGERPRINT_KEY, None)
    if (set(_BUNDLE_KEYS) <= set(bundle) and stamp is not None
            and str(stamp) == registry_fingerprint(system)):
        return bundle
    return None


def trained_oscillator(system: str = "chen", seed: int = 0
                       ) -> Dict[str, np.ndarray]:
    """Trained weights + normalizer for any registered chaotic system.

    The full bundle (w1, b1, w2, b2, scale, offset) is cached per process
    and, for the default seed, on disk at ``results/weights/<system>.npz``,
    so every consumer (PRNG engines, the serving farm, codegen) shares one
    set of weights per system instead of retraining the Chen network only.
    """
    key = (system, seed)
    if key in _WEIGHTS_CACHE:
        return _WEIGHTS_CACHE[key]
    if "@" in system:
        # Lattice bundle ("chen@ring8"): DERIVED from the trained base
        # oscillator (block-diagonal expansion + coupling operator), never
        # trained at lattice size and never persisted — it is a pure
        # function of the base registry entry.
        from repro.core.ann import expand_lattice_params
        from repro.core.chaotic import (DEFAULT_LATTICE_COUPLING,
                                        parse_lattice_name)
        base_name, topo, n_nodes = parse_lattice_name(system)
        base = trained_oscillator(base_name, seed)
        bundle = dict(
            expand_lattice_params(base, n_nodes=n_nodes,
                                  coupling=DEFAULT_LATTICE_COUPLING,
                                  topology=topo),
            scale=np.tile(base["scale"], n_nodes),
            offset=np.tile(base["offset"], n_nodes))
        _WEIGHTS_CACHE[key] = bundle
        return bundle
    if seed == 0:
        bundle = registry_weights(system)
        if bundle is not None:
            _WEIGHTS_CACHE[key] = bundle
            return bundle
        # Missing, torn or stale entry (recipe/jax changed since
        # training): retrain rather than serve stale weights.
    fingerprint = registry_fingerprint(system)
    path = weights_dir() / f"{system}.npz"
    from repro.core.ann import AnnConfig, extract_parameters, train
    from repro.core.chaotic import get_system, make_dataset
    sys_ = get_system(system)           # fail fast on unknown system names
    ds = make_dataset(system, n_samples=_TRAIN_SAMPLES, seed=seed)
    cfg = AnnConfig(dim=sys_.dim, hidden=_TRAIN_HIDDEN.get(system, 8))
    params, _ = train(cfg, ds, epochs=_TRAIN_EPOCHS, lr=_TRAIN_LR, seed=seed)
    bundle = dict(extract_parameters(params),
                  scale=np.asarray(ds.scale, np.float32),
                  offset=np.asarray(ds.offset, np.float32))
    if seed == 0:
        try:
            path.parent.mkdir(parents=True, exist_ok=True)
            # Atomic publish: concurrent readers must never see a torn zip.
            # The recipe fingerprint rides in the npz so a later recipe or
            # jax change invalidates the entry instead of serving it.
            tmp = path.with_suffix(f".tmp{os.getpid()}.npz")
            np.savez(tmp, **bundle, **{_FINGERPRINT_KEY: fingerprint})
            os.replace(tmp, path)
        except OSError:
            pass                         # read-only checkout: RAM cache only
    _WEIGHTS_CACHE[key] = bundle
    return bundle


def default_params(seed: int = 0, system: str = "chen") -> Dict[str, np.ndarray]:
    """Trained oscillator weights for ``system`` (registry-cached).

    ``seed`` stays the first parameter — the pre-registry signature was
    ``default_params(seed)`` and positional callers must keep working.
    """
    bundle = trained_oscillator(system, seed)
    keys = ("w1", "b1", "w2", "b2", "coupling", "lattice_meta")
    return {k: bundle[k] for k in keys if k in bundle}


def default_stream(n_streams: int = 256, seed: int = 0,
                   system: str = "chen") -> ChaoticStream:
    """A ready-to-use stream over a registry oscillator (Chen by default)."""
    return ChaoticStream.from_trained(default_params(seed, system),
                                      n_streams=n_streams)

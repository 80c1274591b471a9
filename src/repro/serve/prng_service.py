"""Streaming chaotic-PRNG serving engine (the HENNC end product at scale).

The paper's hardware engine serves one random stream from one synthesized
core; here the TPU analogue serves *many named client streams from one
kernel launch*: each client owns a contiguous block of lanes on the stream
axis of the fused bits kernel, so a single ``ops.chaotic_bits`` launch
advances every client at once (the batched-MAC-array idea, lifted to the
serving layer).  Multi-device scale-out shards the stream pool across
devices with ``distributed.sharding.shard_stream_pool`` — lanes are
embarrassingly parallel, so the partition is exact.

Determinism contract: a client's word stream depends only on (weights,
seed, lanes_per_client, kernel config) — never on which other clients are
registered, how requests interleave, or how the pool is sharded.  That
holds because (a) every lane evolves independently in the kernel, (b) each
client carries its own word-row (Weyl) counter, passed to the kernel as a
per-lane offset vector, and (c) overdraw from batched launches is buffered
per client, not dropped.  The same property makes the service resumable:
``snapshot()`` captures pool state + counters + buffers.

The kernel microarchitecture is not hand-picked: ``core.dse.select_config``
(the paper's DSE, Eqs. 8-9) chooses (s_block, t_block, unroll,
compute_unit) — the first place the explorer's output drives the hot path
end to end.  It is tuned for one client's lane block and pinned at
construction (not re-tuned as the pool grows), so a client's words never
depend on when it joined; pass ``config=`` to override.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.prng.stream import (_lineage_counter, _round_rows,
                               _splitmix_seeds, effective_burn_in)


@dataclasses.dataclass(eq=False)
class _Client:
    name: str
    slot: int                 # lane block index into the pool
    seed: int
    row: int = 0              # word rows emitted (per-lane Weyl counter)
    buf: np.ndarray = dataclasses.field(
        default_factory=lambda: np.empty(0, np.uint32))
    pending: int = 0          # words requested but not yet delivered


class PRNGService:
    """Batches many named client streams onto one fused-kernel launch."""

    def __init__(self, params: Dict[str, jax.Array], *,
                 lanes_per_client: int = 128, burn_in: int = 16,
                 activation: str = "relu", backend: str = "auto",
                 config=None, mesh=None, mesh_axis: str = "data",
                 dtype=None):
        self.params = {k: jnp.asarray(v) for k, v in params.items()}
        self.dim = self.params["w1"].shape[0]
        self.lanes_per_client = int(lanes_per_client)
        self.burn_in = effective_burn_in(burn_in)
        self.activation = activation
        self.backend = ops.resolve_backend(backend)   # never 'auto'
        # Kernel compute dtype: f32 unless serving a half-width (bf16) core.
        self.dtype = jnp.dtype(dtype) if dtype is not None else jnp.float32
        if config is None:
            from repro.core.dse import select_config
            n_nodes = 1
            if "lattice_meta" in self.params:
                from repro.core.ann import lattice_meta_tuple
                n_nodes = lattice_meta_tuple(self.params["lattice_meta"])[0]
            config = select_config(self.dim, self.params["w1"].shape[1],
                                   s_total=self.lanes_per_client,
                                   dtype=self.dtype, n_nodes=n_nodes)
        self.config = config
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.clients: Dict[str, _Client] = {}
        self.pool_x: Optional[jax.Array] = None       # (n_clients * L, I)
        self.launches = 0                             # batched pool launches
        # Optional observation hook: called with each launch's raw word
        # slab inside absorb(), off the delivery path (the farm's
        # health-monitoring seam, ``OscillatorFarm.attach_monitor``).
        # The hook must be cheap and thread-safe — under an offloaded
        # front-end, absorb() runs on the launch executor thread.
        self.sample_hook = None
        # Words already served by a flush but not yet returned to their
        # requester (a draw() for one client must not drop co-tenants'
        # flushed requests).
        self._outbox: Dict[str, np.ndarray] = {}

    # -- registration -------------------------------------------------------

    def register(self, name: str, seed: Optional[int] = None) -> None:
        """Add a named stream: seed its lane block, burn it in, join pool.

        With no explicit seed, one is derived from the client name so that
        distinct clients never silently share a stream; pass the same
        explicit seed to two clients only if identical streams are wanted.
        """
        if name in self.clients:
            raise ValueError(f"client {name!r} already registered")
        if seed is None:
            seed = zlib.crc32(name.encode())
        L = self.lanes_per_client
        counter = _lineage_counter(seed, ())
        x = _splitmix_seeds(jnp.asarray(counter, jnp.uint32), L,
                            self.dim).astype(self.dtype)
        if self.burn_in:
            # Dedicated small launch so a client's stream is independent of
            # when it registered (burn-in never advances other clients).
            _, x = ops.chaotic_bits(
                self.params, x, self.burn_in, jnp.uint32(0),
                activation=self.activation, backend=self.backend,
                config=self.config)
        slot = len(self.clients)
        self.clients[name] = _Client(name=name, slot=slot, seed=seed)
        self.pool_x = x if self.pool_x is None else jnp.concatenate(
            [self.pool_x, x], axis=0)

    # -- request/flush ------------------------------------------------------

    def request(self, name: str, n_words: int) -> None:
        """Queue a draw; all queued draws are served by one flush() launch."""
        if n_words < 0:
            raise ValueError(f"n_words must be >= 0, got {n_words}")
        self.clients[name].pending += int(n_words)

    def rows_needed(self) -> int:
        """Unrounded max word rows any pending request still needs (0 when
        no launch is required).  Cheap — safe to poll per request()."""
        return self.rows_needed_with(None)

    def rows_needed_with(self, extra: Optional[Dict[str, int]] = None) -> int:
        """``rows_needed()`` if ``extra`` words per client were also pending.

        Demand introspection for front-ends that hold requests of their own
        (the async flusher): a request coverable from a client's buffer
        contributes zero rows, so coalescing thresholds count launch work,
        not raw words.  No state changes.
        """
        L = self.lanes_per_client
        extra = extra or {}
        n_rows = 0
        for c in self.clients.values():
            need = c.pending + extra.get(c.name, 0) - len(c.buf)
            if need > 0:
                n_rows = max(n_rows, -(-need // L))
        return n_rows

    def pending_words(self, name: str) -> int:
        """Words this client has requested but not yet been served."""
        return self.clients[name].pending

    def outbox_words(self, name: str) -> int:
        """Words already served for this client but parked undelivered."""
        parked = self._outbox.get(name)
        return 0 if parked is None else int(parked.size)

    def prepare_rows(self) -> Tuple[int, Optional[np.ndarray]]:
        """Plan a pool launch without performing it: (rows needed, offsets).

        Rows needed is ``rows_needed()``; offsets is the (S_pool,) per-lane
        uint32 Weyl-counter vector a launch issued now must use (None when
        no launch is required).  This is the farm-facing half of
        ``flush()``: a gang scheduler calls ``prepare_rows()`` on every
        group member, launches once for the group (possibly with MORE rows
        than this service asked for — overdraw is buffered, so delivered
        words are chunk-invariant), and hands the result back through
        ``absorb()``.  No state changes.
        """
        n_rows = self.rows_needed()
        if n_rows == 0:
            return 0, None
        offsets = np.repeat(
            np.asarray([c.row for c in self._by_slot()], np.uint32),
            self.lanes_per_client)
        return n_rows, offsets

    def absorb(self, words: Optional[np.ndarray], new_pool_x,
               n_rows: int, *, deliver: bool = True) -> Dict[str, np.ndarray]:
        """Bookkeeping half of ``flush()``: fold one launch's output back in.

        ``words`` is the (n_rows, S_pool) uint32 slab of this service's
        lanes and ``new_pool_x`` the advanced (S_pool, I) state (both may be
        None with n_rows == 0 for a launch-free delivery pass).  Clients
        that needed words get them buffered and their Weyl counters
        advanced; idle clients are *frozen* — their lanes rode the launch
        but their state is rolled back to the current pool, so a client's
        stream never depends on co-tenant traffic.  Then every pending
        request that the buffers now cover is delivered (outbox first).
        With ``deliver=False`` served words are parked in the outbox
        instead (auto-flush path): nothing is lost, the next
        flush()/draw() returns them.
        """
        L = self.lanes_per_client
        if n_rows > 0:
            words = np.asarray(words)
            if self.sample_hook is not None:
                self.sample_hook(words)
            active = [c for c in self._by_slot() if c.pending - len(c.buf) > 0]
            for c in active:
                mine = words[:, c.slot * L:(c.slot + 1) * L].reshape(-1)
                c.buf = np.concatenate([c.buf, mine])
                c.row += n_rows
            active_slots = {c.slot for c in active}
            idle_lanes = np.concatenate(
                [np.arange(c.slot * L, (c.slot + 1) * L)
                 for c in self._by_slot() if c.slot not in active_slots]
            ) if len(active_slots) < len(self.clients) else None
            if idle_lanes is not None:
                new_pool_x = new_pool_x.at[idle_lanes].set(
                    self.pool_x[idle_lanes])
            self.pool_x = new_pool_x
        out: Dict[str, np.ndarray] = {}
        for name, parked in self._outbox.items():
            out[name] = parked
        self._outbox = {}
        for c in self.clients.values():
            if c.pending:
                served = c.buf[:c.pending]
                out[c.name] = (np.concatenate([out[c.name], served])
                               if c.name in out else served)
                c.buf = c.buf[c.pending:]
                c.pending = 0
        if deliver:
            return out
        for name, served in out.items():
            self._park(name, served)
        return {}

    def flush(self) -> Dict[str, np.ndarray]:
        """One batched kernel launch serving every pending request.

        Every client that needs words advances by the same number of word
        rows (the max any pending request needs) with overdraw buffered, so
        per-client sequences stay independent of batching.  Clients that
        need nothing are *frozen* — their lanes are computed (they ride the
        launch) but their state/counters are rolled back — so idle clients
        neither advance nor accumulate buffer memory.  Implemented as
        ``prepare_rows()`` -> launch -> ``absorb()``; the farm's gang
        scheduler drives the same two halves around a shared launch.
        """
        n_need, offsets = self.prepare_rows()
        # Whole time-blocks for big launches, next-pow2 for small ones
        # (overdraw is buffered anyway; see stream._round_rows).
        n_rows = _round_rows(n_need, self.config.t_block) if n_need else 0
        if n_rows > 0:
            words, new_x = self._launch(n_rows, jnp.asarray(offsets))
            return self.absorb(words, new_x, n_rows)
        return self.absorb(None, None, 0)

    def draw(self, name: str, n_words: int) -> np.ndarray:
        """Convenience: request + flush for one client.

        The flush may also serve other clients' queued requests (and any
        earlier request for this client); those words are parked in the
        outbox and delivered by the next flush() — never dropped.
        """
        self.request(name, n_words)  # validates the client name
        if n_words == 0:
            return np.empty(0, np.uint32)
        prior = self.clients[name].pending - n_words
        out = self.flush()
        mine = out.pop(name)
        if prior > 0:                      # earlier request for this client
            self._park(name, mine[:prior])
            mine = mine[prior:]
        for other, words in out.items():
            self._park(other, words)
        return mine

    def park(self, name: str, words: np.ndarray) -> None:
        """Append already-served words to this client's outbox (delivered,
        outbox-first, by the next flush()/draw()).  Public for front-ends
        that receive a flush()'s words on behalf of other callers: words a
        front-end cannot route to one of its own requests are parked back
        here — never dropped — and surface on the sync path."""
        if words.size == 0:
            return
        self._outbox[name] = (np.concatenate([self._outbox[name], words])
                              if name in self._outbox else words)

    _park = park

    def _by_slot(self) -> List[_Client]:
        return sorted(self.clients.values(), key=lambda c: c.slot)

    def _launch(self, n_rows: int, offsets: jax.Array):
        """The one batched pool launch: ((n_rows, S_pool) words, new state).

        Does NOT assign ``pool_x`` — ``absorb()`` owns that, because idle
        lanes must be rolled back against the pre-launch pool.
        """
        n_steps = 2 * n_rows

        def run(x, off):
            return ops.chaotic_bits(
                self.params, x, n_steps, off, activation=self.activation,
                backend=self.backend, config=self.config)

        s_pool = self.pool_x.shape[0]
        if self.mesh is not None and s_pool % self.mesh.shape[self.mesh_axis] == 0:
            from repro.distributed.sharding import shard_stream_pool
            run = shard_stream_pool(run, self.mesh, self.mesh_axis)
        words, new_x = run(self.pool_x, offsets)
        self.launches += 1
        return np.asarray(words), new_x

    # -- resumability -------------------------------------------------------

    def replay_client(self, name: str, *, row: int, pending: int = 0,
                      buf_words: int = 0, outbox_words: int = 0,
                      chunk_rows: int = 4096) -> None:
        """Advance a client to an absolute stream position (crash
        recovery, ``repro.serve.journal``).

        Recomputes the client's lanes forward from their *current* row —
        0 for a freshly-registered client (full replay), or a
        checkpoint-restored position (delta replay bounded by the journal
        rotation window) — with the same fused kernel the crashed process
        used.  Chunk-invariant absolute-row indexing makes the replay
        bit-identical to however many launches originally produced the
        stream, so the final ``buf_words + outbox_words`` regenerated
        words rebuild the undelivered tail exactly: the stream order is
        always [delivered][outbox][buffer] (outbox words were served from
        the buffer head before the buffer's current contents
        accumulated), and a tail that reaches back before the checkpoint
        row is covered by the checkpoint's own undelivered words.
        ``chunk_rows`` bounds replay memory — only the owed tail is kept.
        """
        c = self.clients[name]
        row, buf_words, outbox_words = int(row), int(buf_words), int(outbox_words)
        if row < c.row:
            raise ValueError(
                f"replay_client({name!r}) cannot rewind: client is at row "
                f"{c.row}, journal says {row}")
        L = self.lanes_per_client
        if row * L < buf_words + outbox_words:
            raise ValueError(
                f"inconsistent position for {name!r}: {row} rows emit "
                f"{row * L} words < buf {buf_words} + outbox {outbox_words}")
        tail_need = buf_words + outbox_words
        # undelivered words at the starting position seed the tail: a
        # final tail reaching behind the start row must come from them
        held = np.concatenate([self._outbox.pop(name, np.empty(0, np.uint32)),
                               c.buf])
        if tail_need > held.size + (row - c.row) * L:
            raise ValueError(
                f"inconsistent position for {name!r}: owed tail "
                f"{tail_need} exceeds held {held.size} + "
                f"{(row - c.row) * L} replayable words")
        tail = held[-tail_need:] if tail_need else np.empty(0, np.uint32)
        if row > c.row:
            lanes = slice(c.slot * L, (c.slot + 1) * L)
            x = self.pool_x[lanes]
            done = c.row
            while done < row:
                n = min(int(chunk_rows), row - done)
                words, x = ops.chaotic_bits(
                    self.params, x, 2 * n, jnp.uint32(done),
                    activation=self.activation, backend=self.backend,
                    config=self.config)
                if tail_need:
                    tail = np.concatenate(
                        [tail, np.asarray(words).reshape(-1)])[-tail_need:]
                done += n
            self.pool_x = self.pool_x.at[lanes].set(x)
            c.row = row
        if outbox_words:
            self._park(name, tail[:outbox_words])
        c.buf = tail[outbox_words:]
        c.pending = int(pending)

    def snapshot(self) -> Dict[str, object]:
        """Serializable state: restore() continues every stream bit-exactly.

        ``pending`` (words requested but not yet flushed) is part of the
        in-flight contract: a snapshot taken between request() and flush()
        must not silently lose the queued draws on restore.
        """
        return {
            "pool_x": np.asarray(self.pool_x) if self.pool_x is not None else None,
            "clients": {
                c.name: {"slot": c.slot, "seed": c.seed, "row": c.row,
                         "buf": c.buf.copy(), "pending": c.pending}
                for c in self.clients.values()
            },
            "launches": self.launches,
            "outbox": {k: v.copy() for k, v in self._outbox.items()},
            # Effective burn-in is part of every stream's identity: a
            # restore under a different burn-in would silently continue
            # from stream positions the new engine can never reproduce.
            "burn_in": self.burn_in,
        }

    def restore(self, snap: Dict[str, object]) -> None:
        snap_burn = snap.get("burn_in")
        if snap_burn is not None and int(snap_burn) != self.burn_in:
            raise ValueError(
                f"snapshot was taken with effective burn_in {snap_burn}, "
                f"this service runs {self.burn_in}; streams would resume "
                f"at positions the engine cannot reproduce")
        self.pool_x = (jnp.asarray(snap["pool_x"], self.dtype)
                       if snap["pool_x"] is not None else None)
        self.clients = {
            name: _Client(name=name, slot=st["slot"], seed=st["seed"],
                          row=st["row"], buf=np.asarray(st["buf"], np.uint32),
                          pending=int(st.get("pending", 0)))
            for name, st in snap["clients"].items()
        }
        self.launches = int(snap["launches"])
        self._outbox = {k: np.asarray(v, np.uint32)
                        for k, v in snap.get("outbox", {}).items()}

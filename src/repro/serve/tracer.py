"""The serving stack's one tracer: stage timers, counters, profiler spans.

``OscillatorFarm(profile=True)`` owns one ``Tracer`` and hands it to its
services; ``AsyncOscillatorFarm`` reaches it through its farm.  A *span*
adds the seconds it was open to a totals key, read through the injected
``Clock``, and opens a ``jax.profiler.TraceAnnotation`` of its name, so a
profiler trace (``jax.profiler.start_trace``) shows it beside the device
ops on one clock.  Spans nest: an inner span's seconds count in its own
key and inside every enclosing one (``launch_wait`` and ``launch_copy``
are parts of ``launch``).  A span with no key only annotates.  A
*counter* adds to a totals key.  ``stats()`` is the one read-out: a flat
``{key: total}`` dict.

A tracer built with no clock is off: every span is one shared null
context, counters do nothing, no clock is read, no annotation is created,
and ``stats()`` is None.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Iterator, Optional

import jax
import numpy as np

from repro.clock import Clock
from repro.kernels.chaotic_ann import sharded_launch_builds

#: Seconds a span was open, by totals key.
TIMERS = ("plan", "stack", "launch", "launch_wait", "launch_copy", "absorb",
          "commit", "resolve")
#: Sums that are not span times: flushes, the committed draws' summed
#: queue wait (seconds) and count, the lane-rows the kernels computed
#: and the lane-rows whose words a tenant buffered, the words absorb
#: wrote on the host (tenant buffers and the health monitor's sample),
#: the launches of pools on a mesh of more than one device and those of
#: them that ran split over every device of the mesh, the sharded launch
#: callables built (``Tracer.launched``), the fetches whose words the
#: host assembled from more than one device buffer, every launch fetched,
#: and the fetches that began while a later launch of the same flush was
#: already dispatched (``Tracer.fetch``).
COUNTERS = ("flushes", "queue_wait_s", "draws_committed", "lanes_computed",
            "lanes_used", "absorb_words_copied", "mesh_launches",
            "mesh_launches_split", "launch_builds", "fetch_assembled",
            "fetches", "fetches_overlapped")

_OFF = contextlib.nullcontext()


class Tracer:
    """Totals of spans and counters; off when built with no clock."""

    def __init__(self, clock: Optional[Clock] = None):
        self.clock = clock
        self._totals: Optional[Dict[str, float]] = (
            None if clock is None else dict.fromkeys(TIMERS + COUNTERS, 0.0))
        # the launch phase runs on the front-end's worker thread
        self._lock = threading.Lock()
        self._builds = sharded_launch_builds() if clock is not None else 0

    @property
    def on(self) -> bool:
        return self._totals is not None

    def span(self, name: str, key: Optional[str] = None):
        """Context manager: a profiler annotation ``name``; with ``key``,
        its open seconds are added to that total."""
        if self._totals is None:
            return _OFF
        return self._span(name, key)

    @contextlib.contextmanager
    def _span(self, name: str, key: Optional[str]) -> Iterator[None]:
        with jax.profiler.TraceAnnotation(name):
            if key is None:
                yield
                return
            t0 = self.clock.now()
            try:
                yield
            finally:
                self.count(**{key: self.clock.now() - t0})

    def count(self, **sums: float) -> None:
        """Add to counter totals, all at once: a read-out never sees one
        of the sums without the others (a mean or a share of two counters
        is taken from whole events)."""
        if self._totals is not None:
            with self._lock:
                for key, v in sums.items():
                    self._totals[key] += float(v)

    def stats(self) -> Optional[Dict[str, float]]:
        if self._totals is None:
            return None
        with self._lock:
            return dict(self._totals)

    def launched(self, mesh, mesh_axis: str, state: jax.Array) -> None:
        """Count one launch, before its words are fetched: the sharded
        launch callables built since the tracer's previous launch (the
        misses of the builders' caches, ``sharded_launch_builds``), and a
        pool on a mesh of more than one device in ``mesh_launches``, and
        in ``mesh_launches_split`` when the launch's final state lies on
        every device of the mesh (the launch ran split over all of them).
        The state decides, not the words: a sharded launch gathers its
        words onto every device, so where they lie proves nothing."""
        if self._totals is None:
            return
        builds = sharded_launch_builds()
        with self._lock:
            built, self._builds = builds - self._builds, builds
        sums = {"launch_builds": built}
        if mesh is not None and int(mesh.shape[mesh_axis]) > 1:
            split = set(state.sharding.device_set) >= set(mesh.devices.flat)
            sums.update(mesh_launches=1, mesh_launches_split=int(split))
        self.count(**sums)

    def fetch(self, words: jax.Array, *,
              overlapped: bool = False) -> np.ndarray:
        """A launch's words on the host.  Traced, as two spans: the wait
        for the device to finish the launch, then what remains of the
        device-to-host copy (started early by ``copy_to_host_async``);
        counted in ``fetches``, in ``fetches_overlapped`` when a later
        launch of the same flush was already dispatched, and in
        ``fetch_assembled`` when the words lie in more than one device
        buffer, which the copy assembles on the host."""
        if self._totals is None:
            return np.asarray(words)
        self.count(fetches=1, fetches_overlapped=int(overlapped),
                   fetch_assembled=int(
                       not words.sharding.is_fully_replicated
                       and len(words.addressable_shards) > 1))
        with self.span("farm.launch.wait", "launch_wait"):
            jax.block_until_ready(words)
        with self.span("farm.launch.copy", "launch_copy"):
            return np.asarray(words)

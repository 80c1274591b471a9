"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``load`` reads an ``.xplane.pb`` with nothing but JAX: the ``XLA Ops``
line of every ``/device:TPU:<n>`` plane (one event per executed op, named
by its HLO text) and the harness's own host spans (names with a ``.``
prefix the harness chooses, such as ``bench.submit``).  Host and device
events share one clock.  The reductions then clip everything to the
measured window, which the harness marks with two host spans.
"""
from __future__ import annotations

import dataclasses
import gzip
import re
from typing import Dict, List, Sequence, Tuple

from bench import work

WINDOW_OPEN = "bench.window_open"
WINDOW_CLOSE = "bench.window_close"
HOST_PREFIXES = ("bench.", "frontend.", "farm.", "service.")

Interval = Tuple[str, float, float]          # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Interval]]       # plane -> XLA ops
    host: List[Interval]                     # harness spans

    def window(self) -> Tuple[float, float]:
        """(start_ns, end_ns) of the measured window, from its markers."""
        opens = [s for n, s, _ in self.host if n == WINDOW_OPEN]
        closes = [s for n, s, _ in self.host if n == WINDOW_CLOSE]
        if not opens or not closes:
            raise ValueError("trace holds no window markers")
        return min(opens), max(closes)


def load(path) -> Trace:
    """Read an ``.xplane.pb`` (or a gzipped one, ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData
    path = str(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
            devices[plane.name] = sorted(ops, key=lambda o: o[1])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIXES))
    return Trace(devices=devices, host=sorted(host, key=lambda h: h[1]))


def short_name(text: str) -> str:
    """``%chaotic_ann_bits_pallas.1 = (...) custom-call(...)`` ->
    ``chaotic_ann_bits_pallas``."""
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def clip(ops: Sequence[Interval], t0: float, t1: float) -> List[Interval]:
    return [(n, max(s, t0), min(e, t1)) for n, s, e in ops
            if e > t0 and s < t1]


def merged(ops: Sequence[Interval]) -> List[Tuple[float, float]]:
    """Union of the ops' intervals as disjoint (start, end) pairs."""
    out: List[List[float]] = []
    for _, s, e in sorted(ops, key=lambda o: o[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops: Sequence[Interval], t0: float, t1: float) -> float:
    return sum(e - s for s, e in merged(clip(ops, t0, t1)))


def busy_s(tr: Trace) -> float:
    """Seconds in the window in which an op ran, averaged over devices."""
    t0, t1 = tr.window()
    if not tr.devices:
        return 0.0
    return sum(busy_ns(ops, t0, t1) for ops in tr.devices.values()) / (
        1e9 * len(tr.devices))


def idle_share(tr: Trace) -> float:
    t0, t1 = tr.window()
    return 1.0 - busy_s(tr) * 1e9 / (t1 - t0)


def op_time(tr: Trace, k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` device ops (by short name) that took most time in the
    window, in seconds summed over devices."""
    t0, t1 = tr.window()
    tot: Dict[str, float] = {}
    for ops in tr.devices.values():
        for n, s, e in clip(ops, t0, t1):
            key = short_name(n)
            tot[key] = tot.get(key, 0.0) + (e - s) / 1e9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:k]


def kernel_calls(tr: Trace) -> List[Tuple[Dict, float]]:
    """Every Pallas kernel call that started in the window, parsed, with
    its device seconds."""
    t0, t1 = tr.window()
    out = []
    for ops in tr.devices.values():
        for n, s, e in ops:
            if t0 <= s < t1:
                call = work.parse_call(n)
                if call is not None:
                    out.append((call, (e - s) / 1e9))
    return out


def kernel_time(tr: Trace) -> Dict[str, float]:
    """Device seconds of each Pallas kernel, by name."""
    tot: Dict[str, float] = {}
    for call, sec in kernel_calls(tr):
        tot[call["name"]] = tot.get(call["name"], 0.0) + sec
    return tot


def idle_gaps(tr: Trace, k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` longest device-idle gaps in the window (first device),
    each named by the innermost harness span that covers its middle."""
    t0, t1 = tr.window()
    if not tr.devices:
        return []
    busy = merged(clip(next(iter(tr.devices.values())), t0, t1))
    edges = [t0] + [x for s, e in busy for x in (s, e)] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = [h for h in tr.host
             if h[0] not in (WINDOW_OPEN, WINDOW_CLOSE)]
    out = []
    for s, e in gaps[:k]:
        mid = (s + e) / 2
        cover = [h for h in spans if h[1] <= mid <= h[2]]
        name = min(cover, key=lambda h: h[2] - h[1])[0] if cover else "host idle"
        out.append((name, (e - s) / 1e9))
    return out

"""One benchmark run of a cell: build the farm, warm it up, serve one
measured window of traffic through ``AsyncOscillatorFarm.submit``, audit
what the window delivered against the plain reference, and reduce.

The program under test is imported from ``src`` and used only through its
public constructors and entry points; the benchmark wraps a few of its
calls to record spans (traced runs) and to sample launches for the audit.
"""
from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import functools
import itertools
import math
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from bench import spec
from bench.trace import WINDOW_CLOSE, WINDOW_OPEN

LOG = "bench:"


def say(*parts) -> None:
    print(LOG, *parts, file=sys.stderr, flush=True)


class CompileWatch:
    """Compiles and persistent-cache loads, from JAX's own monitoring
    events, in all and while ``on``; a compile inside the window means
    warm-up missed a shape.  JAX times every request for an executable
    (``backend_compile_duration``), a cache hit included, so a compile is
    a request that no cache load answered."""

    REQUEST = "/jax/core/compile/backend_compile_duration"
    LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax
        self.on = False
        self.total = {"requests": 0, "cache_loads": 0}
        self.window = dict(self.total)
        self.window_programs: Dict[str, int] = {}   # name -> requests
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, fun_name=None, **_):
        kind = {self.REQUEST: "requests", self.LOAD: "cache_loads"}.get(event)
        if kind is None:
            return
        self.total[kind] += 1
        if kind == "requests":
            self.seconds += duration
        if self.on:
            self.window[kind] += 1
            if kind == "requests":
                name = str(fun_name)
                self.window_programs[name] = (
                    self.window_programs.get(name, 0) + 1)

    @staticmethod
    def compiles(counts) -> int:
        return counts["requests"] - counts["cache_loads"]


def build_farm(cell: spec.Cell, profile: bool, devs):
    """The cell's farm through the public constructors: ``OscillatorFarm``
    + ``add_core``, replaying each committed ``solution.json`` with the
    stream-block clamp ``OscillatorFarm.from_generated`` applies.  Each
    core's parameters come from its kind (``bench/cores/<kind>.py``).  A
    configuration with a ``mesh`` puts every pool on one ``Mesh`` over the
    cell's devices, along the named axis; one without runs on the cell's
    one device."""
    import jax
    import jax.numpy as jnp
    from repro.core.dse import Candidate
    from repro.serve.farm import OscillatorFarm
    config = cell.config
    if config.get("mesh"):
        from jax.sharding import Mesh
        axis = config["mesh"]["axis"]
        place = dict(mesh=Mesh(np.asarray(devs), (axis,)), mesh_axis=axis)
        device = contextlib.nullcontext()
    elif len(devs) == 1:
        place, device = {}, jax.default_device(devs[0])
    else:
        raise spec.Refused(f"{len(devs)} devices and no mesh")
    farm = OscillatorFarm(profile=profile)
    lanes = int(config["lanes_per_client"])
    p_cap = max(0, (-(-lanes // 128)).bit_length() - 1)
    with device:
        for core in config["cores"]:
            sol = spec.read_json(spec.ROOT / core["solution"])
            cand = Candidate(**sol["candidate"])
            cand = dataclasses.replace(cand, p=min(cand.p, p_cap))
            params = cell.kinds[core["name"]].program_params(spec.ROOT, core)
            svc = farm.add_core(core["name"], params, config=cand,
                                dtype=jnp.dtype(cand.dtype_name),
                                activation=sol.get("activation", "relu"),
                                lanes_per_client=lanes,
                                burn_in=int(config["burn_in"]), **place)
            stated = (core["i_dim"], core["h_dim"], core["dtype"],
                      core["compute_unit"])
            runs = (svc.dim, int(svc.params["w1"].shape[1]),
                    jnp.dtype(svc.dtype).name, svc.config.compute_unit)
            if runs != stated:
                raise RuntimeError(f"{core['name']} runs as {runs}, the "
                                   f"configuration states {stated}")
    return farm


def supervision_only():
    """Launch supervision (retries, circuit breaker) with the online
    quality gate off: the committed bf16 cores fail the gate on the chip
    (their lanes merge onto common orbits; PERF.md), so it is held
    silent, and its samples are dropped rather than kept (a window that
    never fills, as ``chip_smoke.py`` sets it, keeps a copy of every
    served word)."""
    from repro.serve.health import HealthMonitor

    class Supervision(HealthMonitor):
        def ingest(self, core, words):
            return None

    return Supervision()


@dataclasses.dataclass
class Record:
    core: str
    client: str
    slot: int
    row0: int
    n_rows: int
    words: np.ndarray       # (n_rows, lanes) the launch's words, host copy
    pre: object             # the pool before the launch (device array)
    post: object            # the launch's advanced pool (device array)
    # (stream position, words) the tenant's futures received in the
    # launch's range of positions
    delivered: List[tuple] = dataclasses.field(default_factory=list)


class Audit:
    """Samples launches that advanced audited tenants while ``open``: a
    reservoir of ``k`` (launch, tenant) events drawn from the seed, plus
    the launch of the most rows.  It wraps each service's ``absorb``, the
    call that folds a launch into the pool, and records what the launch
    was handed and produced; device arrays are held, not copied.  Of the
    words the audited tenants' futures receive, it keeps only those at
    the positions of a record it holds, and they go with the record."""

    def __init__(self, farm, watch: Dict[str, set], k: int, seed: int,
                 session: "Session"):
        self.watch, self.k = watch, int(k)
        session.audit = self
        self.rng = np.random.default_rng([seed, 2])
        self.open = False
        self.seen = 0
        self.kept: List[Record] = []
        self.longest: Optional[Record] = None
        for core, svc in farm.services.items():
            svc.absorb = functools.partial(self._absorb, core, svc,
                                           svc.absorb, session)

    def _absorb(self, core, svc, absorb, session, words, new_x, n_rows, *,
                deliver=True):
        with session.span("service.absorb"):
            if not (self.open and n_rows > 0 and core in self.watch):
                return absorb(words, new_x, n_rows, deliver=deliver)
            active = [(c.name, c.slot, c.row) for c in
                      (svc.clients[n] for n in self.watch[core])
                      if c.pending - len(c.buf) > 0]
            pre = svc.pool_x
            out = absorb(words, new_x, n_rows, deliver=deliver)
            lanes = svc.lanes_per_client
            for name, slot, row in active:
                self.seen += 1
                j = (len(self.kept) if self.seen <= self.k
                     else int(self.rng.integers(self.seen)))
                longest = (self.longest is None
                           or n_rows > self.longest.n_rows)
                if j >= self.k and not longest:
                    continue
                rec = Record(core, name, slot, row, int(n_rows),
                             np.array(np.asarray(words)[
                                 :, slot * lanes:(slot + 1) * lanes]),
                             pre, new_x)
                if longest:
                    self.longest = rec
                if j < len(self.kept):
                    self.kept[j] = rec
                elif j < self.k:
                    self.kept.append(rec)
            return out

    def deliver(self, core: str, client: str, pos: int,
                words: np.ndarray) -> None:
        """Words a future of ``client`` received, from stream position
        ``pos``: the part inside a held record's launch is kept.  A launch
        is absorbed before any of its words is delivered, so every record
        its words fall in exists already."""
        n = len(words)
        for r in self.records():
            if r.core != core or r.client != client:
                continue
            a = r.row0 * len(r.words[0])
            lo, hi = max(a, pos), min(a + r.words.size, pos + n)
            if lo < hi:
                r.delivered.append((lo, np.array(words[lo - pos:hi - pos])))

    def records(self) -> List[Record]:
        recs = list(self.kept)
        if self.longest is not None and all(r is not self.longest
                                            for r in recs):
            recs.append(self.longest)
        return recs


def compare(records: List[Record], kinds: Dict, refcores: Dict,
            lanes: int, precision: Dict[str, str],
            control: Optional[str] = None) -> Dict[str, float]:
    """The audit's numbers.  For every sampled launch the reference (the
    core's kind, ``launch``) runs from the program's pre-launch state of
    the tenant's lanes, at the launch's rows and word offsets, in the
    configuration's precision.
    Compared: the launch's words and advanced state, and the words the
    tenant's futures received at those stream positions.  With
    ``control`` the reference in that lower precision takes the
    program's place: its words and states are what is compared."""
    groups: Dict[tuple, List[Record]] = {}
    for r in records:
        groups.setdefault((r.core, r.n_rows), []).append(r)
    out = {"kernel_word_mismatch": 0, "state_mismatch_lanes": 0,
           "delivered_word_mismatch": 0, "delivered_words_compared": 0,
           "launches_audited": len(records), "state_max_abs_diff": 0.0}
    for (core, n_rows), recs in groups.items():
        sl = [slice(r.slot * lanes, (r.slot + 1) * lanes) for r in recs]
        x0 = np.concatenate([np.asarray(r.pre[s], np.float32)
                             for r, s in zip(recs, sl)])
        row0 = np.repeat(np.asarray([r.row0 for r in recs], np.uint32), lanes)
        want_w, want_x = kinds[core].launch(
            refcores[core], x0, row0, n_rows, precision[core])
        if control is None:
            got_w = np.concatenate([r.words for r in recs], axis=1)
            got_x = np.concatenate([np.asarray(r.post[s], np.float32)
                                    for r, s in zip(recs, sl)])
        else:
            got_w, got_x = kinds[core].launch(
                refcores[core], x0, row0, n_rows, control)
        out["kernel_word_mismatch"] += int((got_w != want_w).sum())
        diff = np.abs(got_x - want_x)
        diff[np.isnan(diff)] = np.inf
        out["state_mismatch_lanes"] += int((diff > 0).any(axis=1).sum())
        out["state_max_abs_diff"] = max(out["state_max_abs_diff"],
                                        float(diff.max()))
        for j, r in enumerate(recs):
            blk = slice(j * lanes, (j + 1) * lanes)
            ref_stream = want_w[:, blk].reshape(-1)
            got_stream = got_w[:, blk].reshape(-1)
            a = r.row0 * lanes
            for lo, words in r.delivered:
                hi = lo + len(words)
                seen = (words if control is None
                        else got_stream[lo - a:hi - a])
                out["delivered_word_mismatch"] += int(
                    (seen != ref_stream[lo - a:hi - a]).sum())
                out["delivered_words_compared"] += hi - lo
    return out


CHECK_LIMITS = {"unresolved_draws": 0, "kernel_word_mismatch": 0,
                "state_mismatch_lanes": 0, "delivered_word_mismatch": 0}


def verdict(numbers: Dict[str, float]) -> Dict[str, Dict]:
    """Each compared number beside its limit; ``correct`` is all of them
    within it, and at least one delivered word compared."""
    checks = {k: {"value": numbers[k], "limit": lim}
              for k, lim in CHECK_LIMITS.items()}
    for k in ("window_draws", "delivered_words_compared"):
        checks[k] = {"value": numbers[k], "limit": 1, "at_least": True}
    return checks


def passed(checks: Dict[str, Dict]) -> bool:
    return all((c["value"] >= c["limit"]) if c.get("at_least")
               else (c["value"] <= c["limit"]) for c in checks.values())


def percentile(x: np.ndarray, q: float) -> float:
    return float(np.percentile(x, q)) if len(x) else math.nan


class Session:
    """The state of one run: the farm, the tenants, every draw submitted,
    and what the audit needs to see of what was delivered."""

    def __init__(self, cell: spec.Cell, seed: int, seconds: float,
                 trace_dir=None):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.trace_dir = trace_dir
        self.tracing = False
        cfg, mix = cell.config, cell.mix
        self.cores = [c["name"] for c in cfg["cores"]]
        per = int(mix["tenants_per_core"])
        self.tenants = [(core, f"t{t:04d}") for core in self.cores
                        for t in range(per)]
        seeds = np.random.default_rng([self.seed, 1]).choice(
            2 ** 32, size=len(self.tenants), replace=False)
        self.tenant_seed = [int(s) for s in seeds]
        pick = np.random.default_rng([self.seed, 3])
        k = int(mix["audit_tenants_per_core"])
        self.audited = {(core, f"t{t:04d}") for core in self.cores
                        for t in pick.choice(per, size=k, replace=False)}
        self.pos = [0] * len(self.tenants)        # stream words requested
        self.due: List[float] = []
        self.sent: List[float] = []
        self.done: List[float] = []
        self.words: List[int] = []
        self.tid: List[int] = []
        self.audit: Optional[Audit] = None
        self.t_base = time.perf_counter()
        self.t0 = self.t1 = math.nan               # window markers
        self.af = None

    # -- the generator's surface ---------------------------------------------

    def now(self) -> float:
        return time.perf_counter() - self.t_base

    def span(self, name: str):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def submit(self, tenant: int, n_words: int, due: float,
               deadline_ms: float, slo: Optional[str]):
        """Submit one draw; returns its future, or None when refused."""
        from repro.serve.admission import Overloaded
        core, client = self.tenants[tenant]
        idx = len(self.due)
        self.due.append(due)
        self.sent.append(self.now())
        self.done.append(math.nan)
        self.words.append(n_words)
        self.tid.append(tenant)
        try:
            fut = self.af.submit(core, client, n_words,
                                 deadline_ms=deadline_ms, slo=slo)
        except Overloaded:
            return None
        pos = self.pos[tenant]
        self.pos[tenant] += n_words
        fut.add_done_callback(functools.partial(self._done, idx, pos))
        return fut

    def _done(self, idx: int, pos: int, fut) -> None:
        t = self.now()
        if fut.cancelled() or fut.exception() is not None:
            return
        words = fut.result()
        if len(words) != self.words[idx]:
            return
        self.done[idx] = t
        key = self.tenants[self.tid[idx]]
        if key in self.audited and self.audit is not None:
            self.audit.deliver(*key, pos, np.asarray(words))

    # -- set-up ---------------------------------------------------------------

    def sync_draw(self, farm, demand: Dict[int, int], slo) -> None:
        """One flush of the synchronous farm serving ``demand``
        (tenant -> words); the words advance the tenants' streams."""
        for t, n in demand.items():
            core, client = self.tenants[t]
            farm.request(core, client, n)
            self.pos[t] += n
        farm.flush(slo_by_core={c: slo for c in self.cores} if slo else None)

    def warm_shapes(self, farm) -> None:
        """Every launch shape the traffic can ask for, before the window.

        The idle-lane rollback of ``absorb`` is shaped by how many tenants
        of a pool a flush leaves idle: every count of active tenants in
        the mix's ``warm_active`` range is served once.  The planner
        shapes a launch by the per-core row buckets up to the mix's
        ``warm_max_rows``: a ``bulk`` mix pins the padded launch, whose
        shape is the largest bucket, so each bucket is served once; any
        other mix lets the planner go ragged or split, so every multiset
        of per-core buckets (a core with no demand included) is served."""
        from repro.prng.stream import _round_rows
        mix = self.cell.mix
        per = int(mix["tenants_per_core"])
        lanes = int(self.cell.config["lanes_per_client"])
        n_cores, slo = len(self.cores), mix["slo"]
        lo, hi = mix["warm_active"]
        for k in range(max(1, lo), min(per, hi) + 1):
            self.sync_draw(farm, {c * per + t: 4 * lanes
                                  for c in range(n_cores)
                                  for t in range(k)}, slo)
        t_block = max(farm.services[c].config.t_block for c in self.cores)
        buckets = sorted({_round_rows(r, t_block)
                          for r in range(1, int(mix["warm_max_rows"]) + 1)})
        if slo == "bulk":
            demands = [(b,) * n_cores for b in buckets]
        else:
            demands = [d for d in itertools.combinations_with_replacement(
                [0] + buckets, n_cores) if any(d)]
        for d in demands:
            self.sync_draw(farm, {c * per: b * lanes
                                  for c, b in enumerate(d) if b}, slo)

    # -- the run --------------------------------------------------------------

    async def serve(self, farm, plan, watch: CompileWatch, audit: Audit):
        from repro.serve.admission import AdmissionController
        from repro.serve.async_frontend import AsyncOscillatorFarm
        import jax
        loop = asyncio.get_running_loop()
        health = supervision_only()
        admission = AdmissionController(max_queued_rows=1 << 20)
        t_open, t_close = plan.window
        marks: Dict[str, object] = {}

        def mark(name: str) -> None:
            with self.span(name):
                t = self.now()
                marks[name] = (t, dict(farm.profile_stats or {}),
                               self.af.flushes)
            if name == WINDOW_OPEN:
                self.t0, watch.on, audit.open = t, True, True
            else:
                self.t1, watch.on = t, False

        def start_trace() -> None:
            # device ops and the harness's spans; no Python call tracing
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
            self.tracing = True

        async with AsyncOscillatorFarm(farm, offload=True,
                                       admission=admission,
                                       health=health) as af:
            self.af = af
            if self.trace_dir is not None:
                self._instrument(af, farm)
                loop.call_later(max(0.0, t_open - 1.0), start_trace)
            self.t_base = time.perf_counter()
            loop.call_later(t_open, mark, WINDOW_OPEN)
            loop.call_later(t_close, mark, WINDOW_CLOSE)
            await self.cell.generator.drive(plan, self)
            while WINDOW_CLOSE not in marks:
                await asyncio.sleep(t_close - self.now() + 1e-3)
            await self._collect(plan)
            await af.drain()
            audit.open = False
            errors = list(af.flush_errors)
        self.af = None              # the program's state goes with the farm
        if self.tracing:
            jax.profiler.stop_trace()
            self.tracing = False
        (ta, pa, fa), (tb, pb, fb) = (marks[WINDOW_OPEN],
                                      marks[WINDOW_CLOSE])
        stages = {k: pb.get(k, 0.0) - pa.get(k, 0.0) for k in pb}
        return {"flushes": fb - fa, "stages": stages, "errors": errors,
                "health": dict(health.stats)}

    async def _collect(self, plan) -> None:
        """Wait for every draw due in the window, a minute past the close
        at most; one that never comes counts as failed."""
        deadline = self.now() + 60.0
        while self.now() < deadline:
            idx = self.window_draws(plan)
            done = np.asarray(self.done)[idx]
            if not np.isnan(done).any():
                break
            await asyncio.sleep(0.01)
        self.t_collected = self.now()

    def window_draws(self, plan) -> np.ndarray:
        """Indices of the draws the window measures: those due in it (open
        loop: scheduled; closed loop: submitted)."""
        due = np.asarray(self.due)
        lo, hi = plan.window
        return np.nonzero((due >= lo) & (due < hi))[0]

    def _instrument(self, af, farm) -> None:
        """Host spans around the calls into each layer (traced runs)."""
        def wrap(obj, attr, name):
            fn = getattr(obj, attr)

            @functools.wraps(fn)
            def inner(*a, **kw):
                with self.span(name):
                    return fn(*a, **kw)
            setattr(obj, attr, inner)
        wrap(af, "_commit", "frontend.commit")
        wrap(af, "_resolve", "frontend.resolve")
        wrap(farm, "flush", "farm.flush")

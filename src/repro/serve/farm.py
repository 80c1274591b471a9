"""Heterogeneous oscillator farm: many generated cores, one serving API.

The paper emits ONE hardware core per run; the serving-scale analogue is a
*farm* of generated cores — different chaotic systems, system dimensions,
dtypes, and DSE-autotuned kernel configs — multiplexed behind a single
register/request/flush/snapshot surface.  Each core is backed by its own
``PRNGService`` pool (its clients share one fused-kernel launch per flush),
and every determinism/resumability guarantee of ``PRNGService`` carries
over unchanged: a client's words are identical whether served standalone
or through the farm.

**Gang scheduling** (the launch-overhead killer): compatible cores — same
(i_dim, h_dim, dtype, activation, kernel config) — do not each pay their
own kernel launch per flush.  ``GangScheduler`` stacks their weights along
a leading core axis, concatenates their lane pools, and issues ONE
``ops.chaotic_bits_gang`` launch for the whole group, then scatters words
and final states back to each ``PRNGService`` via its
``prepare_rows()/absorb()`` halves.  Lanes evolve independently and word
emission is defined in absolute word-row space, so per-client words are
bit-identical to the per-core path (gang overdraw is buffered exactly like
batching overdraw).  Incompatible cores fall back to their own per-core
launch.  Mesh-sharded pools gang too: cores on the SAME mesh share one
shard_map'd gang launch whose stream axis (and scalar-prefetch maps) are
partitioned across the named device axis — see
``kernels.chaotic_ann.chaotic_ann_gang_bits_sharded``.

Cores come from two places:

  * ``add_core(name, params, ...)`` — weights in hand (e.g. straight from
    the registry ``repro.prng.stream.trained_oscillator``);
  * ``from_generated(farm_dir)`` — a directory of ``generate_farm`` output:
    each package's weights.npz + solution.json are loaded and the frozen
    DSE solution (block shapes, compute unit, dtype) drives that core's
    service config, closing the train -> DSE -> codegen -> serve loop.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import pathlib
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dse import VMEM_USABLE, GangCostModel, stacked_gang_vmem_bytes
from repro.prng.stream import _round_rows
from repro.serve.clock import Clock, SystemClock
from repro.serve.health import CoreQuarantined
from repro.serve.prng_service import PRNGService
from repro.serve.tracer import Tracer


def _topology(svc: PRNGService) -> Optional[Tuple]:
    """Hashable device-axis signature of a service's mesh.

    ``None`` for an unsharded (single-device) pool; otherwise the named
    axis, its device count, and the flat device ids — the full identity a
    sharded launch depends on.  Part of every gang compat key, plan /
    decision / dispatch cache key, and farm snapshot, so nothing planned
    on one device count can silently serve another.
    """
    if svc.mesh is None:
        return None
    n_dev = int(svc.mesh.shape[svc.mesh_axis])
    devs = tuple(int(d.id) for d in np.asarray(svc.mesh.devices).reshape(-1))
    return (svc.mesh_axis, n_dev, devs)


def _as_topo(t) -> Optional[Tuple]:
    """Canonicalize a topology signature (JSON round-trips turn the tuples
    into lists; journal checkpoints compare through this)."""
    if t is None:
        return None
    return (str(t[0]), int(t[1]), tuple(int(x) for x in t[2]))


def _lattice_sig(svc: PRNGService) -> Optional[Tuple]:
    """Hashable lattice identity of one core's service, or ``None`` for a
    scalar (uncoupled) core.  The coupling operator is a pure function of
    this tuple (``lattice_coupling_matrix``), so equal signatures imply a
    shared coupling operand is exact for every member of a gang."""
    meta = svc.params.get("lattice_meta")
    if meta is None:
        return None
    from repro.core.ann import lattice_meta_tuple
    return lattice_meta_tuple(np.asarray(meta))


def _compat_key(svc: PRNGService) -> Optional[Tuple]:
    """Gang-compatibility signature of one core's service.

    Two cores may share a stacked-weight launch iff every static property
    of the kernel instantiation matches: network shape (i_dim, h_dim),
    compute dtype, activation, backend, the full DSE kernel config
    (s_block, t_block, unroll, compute_unit), the lattice signature
    (scalar cores never gang with lattice cores, and lattice cores gang
    only on identical (n_nodes, base_dim, topology, strength) — the
    launch carries ONE shared coupling operand), and the device topology.
    Mesh-sharded pools gang with pools on the SAME mesh (axis name, device
    count, device ids): the group launches as one shard_map'd gang across
    that mesh — the single-device-only limit recorded by PR 4 is gone.
    """
    c = svc.config
    return (svc.dim, int(svc.params["w1"].shape[1]), str(svc.dtype),
            svc.activation, svc.backend,
            c.s_block, c.t_block, c.unroll, c.compute_unit,
            _lattice_sig(svc), _topology(svc))


@dataclasses.dataclass(eq=False)
class _Dispatched:
    """One launch of a flush, dispatched and not yet fetched: what its
    finish half needs to hand each member its words and final state."""
    words: jax.Array          # on the device until fetched
    members: List[Tuple]      # (core, service, rows needed, offsets)
    rows: List[int]           # word rows each member takes
    cuts: List[Tuple]         # each member's (index into words, state)
    computed: int             # lane-rows the kernel computed
    plan: Optional[Dict] = None       # a gang's plan: its x0 cache
    state: Optional[jax.Array] = None  # a gang's whole final state


class GangScheduler:
    """Launches a group of compatible cores as stacked-weight kernels,
    choosing HOW per flush with a launch-cost model (the gang *planner*).

    Three caches keep steady-state traffic replay-only:

    * plan cache — per (group, membership, layout): stacked weight arrays,
      pool layout (lane spans + per-block core-id map), reusable offset /
      dead-lane padding buffers, and the last launch's device-resident
      stacked state (reused as the next x0 when no absorb rewrote any
      member pool — the common all-tenants-active case skips the
      per-flush ``jnp.stack``/``jnp.concatenate`` entirely);
    * decision cache — per (membership, ``_round_rows``-bucketed per-core
      demand vector): the cost-minimizing choice among ONE padded
      group-max launch (PR 3's policy), ONE ragged launch (each lane
      block computes only its own demand), or a SPLIT into
      demand-homogeneous sub-launches.  Steady traffic never replans;
    * dispatch keys — distinct (plan, bucketed rows) shapes ever launched;
      each is one XLA compile, and steady state stops growing it.

    ``planner=False`` pins every decision to the padded group-max launch,
    reproducing the PR 3 scheduler exactly.
    """

    def __init__(self, cost_model: Optional[GangCostModel] = None,
                 planner: bool = True, faults=None):
        self.faults = faults          # FaultPlan (chaos harness) or None
        self._plans: Dict[Tuple, Dict] = {}
        self._decisions: Dict[Tuple, Dict] = {}
        self._dispatch_keys = set()   # (plan key, n_rows) ever launched
        self.launches = 0
        self.planner = bool(planner)
        self.cost_model = cost_model or GangCostModel()
        self.decisions = {"padded": 0, "ragged": 0, "split": 0}
        self.layouts = {"stacked": 0, "concat": 0}   # gang launches by layout
        # flushes where an SLO class actually constrained the choice set
        self.slo_forced = {"latency": 0, "bulk": 0}
        self.tracer = Tracer()        # the farm's, when it profiles

    @property
    def dispatch_misses(self) -> int:
        """Distinct (group, bucketed rows) keys launched so far — each one
        is a fresh XLA compile; steady state stops growing this."""
        return len(self._dispatch_keys)

    def _plan(self, key: Tuple, members: List[Tuple[str, PRNGService]],
              mode: str) -> Dict:
        """Stacked weights + pool layout for one (membership, layout).

        Two launch layouts: equal-size vpu pools may take the
        *sublane-stacked* kernel (one grid cell per lane block advances the
        whole group — cheapest for the small coalesced flushes gangs exist
        for); ragged-pool or mxu groups — and ragged-DEMAND launches, where
        the early-out needs one grid cell per (block, core) — take the
        lane-concat kernel with a per-block core-id map.
        """
        sig = (key, tuple((name, int(svc.pool_x.shape[0]))
                          for name, svc in members), mode)
        plan = self._plans.get(sig)
        if plan is not None:
            return plan
        svc0 = members[0][1]
        s_block = svc0.config.s_block
        params = {k: jnp.stack([svc.params[k] for _, svc in members])
                  for k in ("w1", "b1", "w2", "b2")}
        # Lattice cores carry the coupling keys UN-stacked: the compat key
        # pins an identical lattice signature across the group, so one
        # shared (I, I) operand serves every member (ops._lattice_args).
        for k in ("coupling", "lattice_meta"):
            if k in svc0.params:
                params[k] = jnp.asarray(svc0.params[k])
        sizes = [int(svc.pool_x.shape[0]) for _, svc in members]
        plan = {"sig": sig, "params": params, "s_block": s_block,
                "mode": mode, "last_x": None, "handed": None}
        if mode == "stacked":
            plan["s_each"] = sizes[0]
            plan["offs_buf"] = np.zeros((len(members), sizes[0]), np.uint32)
        else:
            spans, core_map, pads, start = [], [], [], 0
            for ci, live in enumerate(sizes):
                padded = -(-live // s_block) * s_block
                spans.append((start, live, padded))
                core_map.extend([ci] * (padded // s_block))
                if padded > live:  # dead-lane padding, built once
                    pads.append(jnp.zeros((padded - live, svc0.dim),
                                          svc0.dtype))
                else:
                    pads.append(None)
                start += padded
            plan.update(spans=spans, pads=pads,
                        core_map=np.asarray(core_map, np.int32),
                        s_total=start,
                        offs_buf=np.zeros(start, np.uint32))
        self._plans[sig] = plan
        return plan

    # -- planning ------------------------------------------------------------

    def _decide(self, key: Tuple, members: Sequence[Tuple],
                demands: Tuple[int, ...],
                slo: Optional[str] = None) -> Dict:
        """Pick the cost-minimizing launch shape for one flush.

        ``demands`` are the ``_round_rows``-bucketed per-member word rows;
        the decision is cached on (membership, demands, slo) so
        steady-state traffic replans exactly never.  Candidate plans:

        * ``padded``  — one launch, every member at the group max
          (sublane-stacked when pools are equal + vpu, else lane-concat);
          this is the only option with ``planner=False`` (PR 3);
        * ``ragged``  — one demand-shaped launch (stacked-with-freeze or
          lane-concat-with-early-out, whichever models cheaper);
        * ``split``   — demand-homogeneous subgroups, each padded (solo
          per-core launches for singletons), paying one launch overhead
          per subgroup.

        ``slo`` constrains the choice set (the deadline-tier contract of
        the async front-end): ``"latency"`` forbids the padded group-max
        launch whenever demand is actually skewed — a latency-class
        tenant must not wait for co-tenants' overdraw rows, so the
        planner must pick a demand-shaped ragged or split plan even when
        the cost model scores padded cheaper; ``"bulk"`` pins the padded
        launch — bulk tenants always ride the maximally-amortized shape.
        ``None`` leaves the planner free (cost-minimizing).
        """
        from repro.kernels.chaotic_ann import gang_effective_rows
        if not self.planner:
            slo = None          # policy pinned: PR 3 padded group-max
        mem_sig = (key, tuple((name, int(svc.pool_x.shape[0]))
                              for name, svc, _, _ in members))
        dsig = (mem_sig, demands, slo)
        dec = self._decisions.get(dsig)
        if dec is not None:
            return dec
        svc0 = members[0][1]
        c = svc0.config
        sizes = [int(svc.pool_x.shape[0]) for _, svc, _, _ in members]
        blocks = [-(-s // c.s_block) for s in sizes]
        topo = _topology(svc0)
        n_dev = 1 if topo is None else topo[1]
        # the stacked kernel shards its LANE axis: each device needs an
        # equal lane slice, so stacked is only eligible when the (equal)
        # pool size divides the device count — and the whole stack must
        # fit VMEM (every core's carry/hidden/x0 is resident at once);
        # past that cliff the planner falls back to the lane-concat layout
        stacked_ok = (len(set(sizes)) == 1 and c.compute_unit == "vpu"
                      and sizes[0] % n_dev == 0
                      and stacked_gang_vmem_bytes(c, len(members))
                      <= VMEM_USABLE)
        model = self.cost_model
        all_idx = tuple(range(len(members)))
        dmax = max(demands)
        base_layout = "stacked" if stacked_ok else "concat"
        options = [("padded",
                    model.gang_cost(c, demands, blocks, sizes,
                                    layout=base_layout, n_dev=n_dev),
                    [{"members": all_idx, "kind": "gang",
                      "layout": base_layout, "ragged": False}])]
        if self.planner and len(set(demands)) > 1:
            # one ragged launch: early-out concat vs freeze-stacked
            eff = gang_effective_rows(
                np.repeat(np.asarray(demands), blocks), 2 * dmax,
                c.t_block, c.unroll)
            r_cost = model.gang_cost(c, demands, blocks, sizes,
                                     layout="concat",
                                     rows_by_block=[int(r) for r in eff],
                                     n_dev=n_dev)
            r_layout = "concat"
            if stacked_ok:
                s_cost = model.gang_cost(c, demands, blocks, sizes,
                                         layout="stacked",
                                         rows_by_block=list(demands),
                                         n_dev=n_dev)
                # the freeze layout saves buffering only (no FMA skipped);
                # require a clear modeled margin over the purpose-built
                # early-out concat path before trusting a noisy fit
                if s_cost < 0.9 * r_cost:
                    r_cost, r_layout = s_cost, "stacked"
            options.append(("ragged", r_cost,
                            [{"members": all_idx, "kind": "gang",
                              "layout": r_layout, "ragged": True}]))
            # split into demand-homogeneous subgroups
            by_demand: Dict[int, List[int]] = {}
            for i, d in enumerate(demands):
                by_demand.setdefault(d, []).append(i)
            cost, parts = 0.0, []
            for d in sorted(by_demand, reverse=True):
                idxs = by_demand[d]
                if len(idxs) == 1:
                    i = idxs[0]
                    cost += model.solo_cost(c, d, blocks[i], n_dev=n_dev)
                    parts.append({"members": (i,), "kind": "solo"})
                else:
                    sub_sizes = [sizes[i] for i in idxs]
                    sub_stacked = (len(set(sub_sizes)) == 1
                                   and c.compute_unit == "vpu"
                                   and sub_sizes[0] % n_dev == 0
                                   and stacked_gang_vmem_bytes(c, len(idxs))
                                   <= VMEM_USABLE)
                    lay = "stacked" if sub_stacked else "concat"
                    cost += model.gang_cost(
                        c, [d] * len(idxs), [blocks[i] for i in idxs],
                        sub_sizes, layout=lay, n_dev=n_dev)
                    parts.append({"members": tuple(idxs), "kind": "gang",
                                  "layout": lay, "ragged": False})
            options.append(("split", cost, parts))
        free_kind = min(options, key=lambda o: o[1])[0]
        eligible = options
        if slo == "bulk":
            eligible = [o for o in options if o[0] == "padded"]
        elif slo == "latency" and len(options) > 1:
            # skewed demand + a latency-class tenant: the padded group-max
            # launch would make that tenant wait for co-tenants' overdraw
            eligible = [o for o in options if o[0] != "padded"]
        kind, cost, parts = min(eligible, key=lambda o: o[1])
        if slo is not None and kind != free_kind:
            self.slo_forced[slo] += 1
        dec = {"kind": kind, "parts": parts, "slo": slo,
               "modeled_cycles": {k: v for k, v, _ in options}}
        self._decisions[dsig] = dec
        return dec

    # -- execution -----------------------------------------------------------

    def _gather_x0(self, plan: Dict, members: Sequence[Tuple]):
        """The launch's pooled x0; reuses the last launch's device-resident
        stacked state when every member pool is still the exact array this
        scheduler handed to its ``absorb`` (identity check — any rollback,
        restore, or registration rebuilds)."""
        handed = plan["handed"]
        if (handed is not None and len(handed) == len(members)
                and all(svc.pool_x is h
                        for (_, svc, _, _), h in zip(members, handed))):
            return plan["last_x"]
        if plan["mode"] == "stacked":
            return jnp.stack([svc.pool_x for _, svc, _, _ in members])
        parts = []
        for (start, live, padded), pad, (_, svc, _, _) in zip(
                plan["spans"], plan["pads"], members):
            parts.append(svc.pool_x)
            if pad is not None:
                parts.append(pad)
        return jnp.concatenate(parts, axis=0)

    def _dispatch_group(self, key: Tuple, members: Sequence[Tuple],
                        demands: Sequence[int], *, layout: str,
                        ragged: bool) -> _Dispatched:
        """Dispatch one gang launch (padded or ragged) for ``members``."""
        from repro.kernels import ops
        from repro.kernels.chaotic_ann import gang_effective_rows
        if self.faults is not None:
            # the injection seam sits BEFORE any kernel work or absorb
            # bookkeeping: a failed launch leaves every member's demand
            # parked at the same absolute rows, so a retry is bit-exact
            self.faults.on_launch([name for name, _, _, _ in members])
        tr = self.tracer
        svc0 = members[0][1]
        cfg = svc0.config
        n_rows = max(demands)
        n_steps = 2 * n_rows
        with tr.span("farm.plan", "plan"):
            plan = self._plan(key, [(name, svc)
                                    for name, svc, _, _ in members], layout)
        with tr.span("farm.stack", "stack"):
            x0 = self._gather_x0(plan, members)
            offs = plan["offs_buf"]
            if layout == "stacked":
                for ci, (_, _, _, offsets) in enumerate(members):
                    offs[ci, :] = offsets
                row_map = np.asarray(demands, np.int32) if ragged else None
                member_rows = (list(demands) if ragged
                               else [n_rows] * len(members))
                # the sublane stack sweeps every row of every pool
                s_blk = plan["s_block"]
                computed = (n_rows * len(members)
                            * (-(-plan["s_each"] // s_blk) * s_blk))
                kernel = ops.chaotic_bits_gang_stacked
            else:
                for (start, live, _), (_, _, _, offsets) in zip(
                        plan["spans"], members):
                    offs[start:start + live] = offsets
                if ragged:
                    block_demand = np.repeat(
                        np.asarray(demands, np.int64),
                        [padded // plan["s_block"]
                         for _, _, padded in plan["spans"]])
                    eff = gang_effective_rows(block_demand, n_steps,
                                              cfg.t_block, cfg.unroll)
                    row_map = eff
                    # every block of a member shares its demand -> same
                    # eff rows
                    member_rows, b0 = [], 0
                    for _, _, padded in plan["spans"]:
                        member_rows.append(int(eff[b0]))
                        b0 += padded // plan["s_block"]
                else:
                    row_map = None
                    member_rows = [n_rows] * len(members)
                computed = sum(r * padded for r, (_, _, padded)
                               in zip(member_rows, plan["spans"]))
                kernel = functools.partial(ops.chaotic_bits_gang,
                                           core_map=plan["core_map"])
        with tr.span("farm.launch", "launch"):
            words, state = kernel(
                plan["params"], x0, n_steps, jnp.asarray(offs),
                row_map=row_map, activation=svc0.activation,
                backend=svc0.backend, mesh=svc0.mesh,
                mesh_axis=svc0.mesh_axis, config=cfg)
            tr.launched(svc0.mesh, svc0.mesh_axis, state)
            if layout == "stacked":
                cuts = [(np.s_[:member_rows[ci], ci, :], state[ci])
                        for ci in range(len(members))]
            else:
                cuts = [(np.s_[:member_rows[ci], start:start + live],
                         state[start:start + live])
                        for ci, (start, live, _) in enumerate(plan["spans"])]
            self.launches += 1
            self.layouts[layout] += 1
            # ragged and padded launches of the same shape are distinct
            # jit traces (row_map None vs array), hence distinct dispatch
            # keys
            self._dispatch_keys.add((plan["sig"], n_rows, bool(ragged)))
        return _Dispatched(words, list(members), member_rows, cuts, computed,
                           plan, state)

    def _dispatch_solo(self, member: Tuple, n_rows: int) -> _Dispatched:
        """Dispatch one core's own launch: a planner-split singleton, a
        core that gangs with no other, or every core of a ``gang=False``
        farm."""
        name, svc, _, offsets = member
        if self.faults is not None:
            self.faults.on_launch([name])
        with self.tracer.span("farm.launch", "launch"):
            words, new_x = svc._dispatch(n_rows, jnp.asarray(offsets))
        s_blk = svc.config.s_block
        computed = n_rows * (-(-svc.pool_x.shape[0] // s_blk) * s_blk)
        return _Dispatched(words, [member], [n_rows], [(np.s_[:], new_x)],
                           computed)

    def _finish(self, d: _Dispatched, *, deliver: bool,
                then: Optional[_Dispatched] = None
                ) -> Dict[str, Dict[str, np.ndarray]]:
        """Fetch a dispatched launch's words, start the copy of ``then``
        (the flush's next launch) as soon as they have landed, and absorb
        each member's words and final state."""
        tr = self.tracer
        with tr.span("farm.launch", "launch"):
            words = tr.fetch(d.words, overlapped=then is not None)
            if then is not None:
                then.words.copy_to_host_async()
        if d.plan is not None:
            d.plan["last_x"] = d.state
            d.plan["handed"] = [state for _, state in d.cuts]
        used = self._used_lanes(d.rows, [svc for _, svc, _, _ in d.members])
        out: Dict[str, Dict[str, np.ndarray]] = {}
        with tr.span("farm.absorb", "absorb"):
            for (index, state), rows, (name, svc, _, _) in zip(
                    d.cuts, d.rows, d.members):
                served = svc.absorb(words[index], state, rows,
                                    deliver=deliver)
                if served:
                    out[name] = served
        tr.count(lanes_computed=d.computed, lanes_used=used)
        return out

    def _finish_all(self, launched: List[_Dispatched], *, deliver: bool
                    ) -> Dict[str, Dict[str, np.ndarray]]:
        out: Dict[str, Dict[str, np.ndarray]] = {}
        for d, then in zip(launched, launched[1:] + [None]):
            out.update(self._finish(d, deliver=deliver, then=then))
        return out

    def run(self, dispatches: Iterable[_Dispatched], *, deliver: bool
            ) -> Dict[str, Dict[str, np.ndarray]]:
        """Serve one flush's launches: dispatch every one, in order, then
        finish each in the same order.  The first launch's copy to the
        host starts at its dispatch, and each next one's when the copy
        before it has landed, so the copies cross the host link one after
        another while the host absorbs the launch before, and the device
        runs the later kernels under the earlier copies.

        A dispatch that raises (the fault seam, tracing, the kernel call)
        propagates only after every launch dispatched before it is
        finished, its words parked in the outboxes (``deliver=False``):
        those members are served and a retry launches only the rest.  A
        fetch that raises drops the launches after it unabsorbed, their
        demand parked at the same absolute rows for a bit-exact retry."""
        launched: List[_Dispatched] = []
        dispatched = False
        try:
            for d in dispatches:
                if not launched:
                    d.words.copy_to_host_async()
                launched.append(d)
            dispatched = True
        finally:
            if not dispatched:        # a dispatch raised: it propagates
                self._finish_all(launched, deliver=False)
        return self._finish_all(launched, deliver=deliver)

    def _used_lanes(self, rows: Sequence[int],
                    svcs: Sequence[PRNGService]) -> int:
        """Lane-rows of a launch whose words a tenant takes: each member's
        rows times the lanes of its active tenants, read before its
        ``absorb`` (which rolls the others back).  0 when not tracing."""
        if not self.tracer.on:
            return 0
        return sum(r * svc.lanes_per_client * len(svc._active())
                   for r, svc in zip(rows, svcs))

    def dispatch(self, key: Tuple,
                 members: List[Tuple[str, PRNGService, int, np.ndarray]],
                 *, slo: Optional[str] = None) -> Iterator[_Dispatched]:
        """Dispatch one flush of ``members`` (each with its prepare_rows
        plan) with the planner-chosen launch shape (``slo`` constrains
        the choice set — see ``_decide``), one launch at a time: each part
        is dispatched when ``run`` asks for it.

        However the plan shapes launches, every member advances by a row
        count >= its own demand with overdraw buffered, so delivered words
        are bit-identical to the per-core path (chunk-invariance of the
        absolute-row Weyl indexing).
        """
        svc0 = members[0][1]
        with self.tracer.span("farm.plan", "plan"):
            demands = tuple(_round_rows(n, svc0.config.t_block)
                            for _, _, n, _ in members)
            dec = self._decide(key, members, demands, slo)
            self.decisions[dec["kind"]] += 1
        for part in dec["parts"]:
            sub = [members[i] for i in part["members"]]
            if part["kind"] == "solo":
                yield self._dispatch_solo(sub[0],
                                          demands[part["members"][0]])
            else:
                yield self._dispatch_group(
                    key, sub, [demands[i] for i in part["members"]],
                    layout=part["layout"], ragged=part["ragged"])


class OscillatorFarm:
    """Routes named clients to per-core ``PRNGService`` pools.

    ``gang=True`` (default) enables gang-scheduled flushes: compatible
    cores share one stacked-weight launch per flush.  ``gang=False``
    reproduces the legacy one-launch-per-core behavior — delivered words
    are bit-identical either way (tests/test_gang.py).
    ``planner=True`` (default) lets the gang scheduler shape each group's
    launch to per-core demand with the ``GangCostModel`` (padded / ragged /
    split, see ``GangScheduler``); ``planner=False`` pins the PR 3 padded
    group-max policy.  Pass ``gang_cost_model`` (e.g. a measured
    ``GangCostModel.fit``) to plan against this machine's real launch
    overhead.  ``auto_flush_rows`` is the coalescing threshold for
    ``request(..., auto_flush=True)``: the farm auto-flushes once total
    pending work reaches that many word rows (None = flush on every
    auto-flush request).  ``profile=True`` switches on the farm's
    ``Tracer`` (``repro.serve.tracer``), shared with its services and its
    ``AsyncOscillatorFarm``: stage timers and counters summed in
    ``profile_stats``, and named spans in a ``jax.profiler`` trace.
    Every time read (the profile timers are the only ones) goes through
    the injectable ``clock`` (``repro.serve.clock``): the sync farm's own
    deferral/coalescing logic is flush-cycle- and row-counted, never
    wall-clock-dependent, and a frozen ``FakeClock`` proves it
    (tests/test_async_frontend.py).
    """

    def __init__(self, *, gang: bool = True, planner: bool = True,
                 gang_cost_model: Optional[GangCostModel] = None,
                 auto_flush_rows: Optional[int] = None,
                 profile: bool = False, clock: Optional[Clock] = None,
                 faults=None):
        self.services: Dict[str, PRNGService] = {}
        self.gang = bool(gang)
        self.auto_flush_rows = auto_flush_rows
        self.clock: Clock = clock or SystemClock()
        self.faults = faults          # FaultPlan (chaos harness) or None
        self._sched = GangScheduler(cost_model=gang_cost_model,
                                    planner=planner, faults=faults)
        self.tracer = Tracer(self.clock if profile else None)
        self._sched.tracer = self.tracer
        self._deferred: set = set()   # cores deferred by the last flush
        # Self-healing state (see quarantine()/rotate()): quarantined
        # cores are skipped by every flush; standbys are cold spare
        # services rotated into a quarantined core's routing slot.
        self._quarantined: set = set()
        self._standbys: Dict[str, PRNGService] = {}
        self._rotations: Dict[str, int] = {}
        self.monitor = None           # HealthMonitor via attach_monitor()

    # -- core management ----------------------------------------------------

    def add_core(self, core: str, params, *, config=None, dtype=None,
                 activation: str = "relu", lanes_per_client: int = 128,
                 burn_in: int = 16, backend: str = "auto",
                 mesh=None, mesh_axis: str = "data") -> PRNGService:
        """Attach a core (one oscillator network) as a serving pool."""
        if core in self.services:
            raise ValueError(f"core {core!r} already attached")
        svc = PRNGService(params, lanes_per_client=lanes_per_client,
                          burn_in=burn_in, activation=activation,
                          backend=backend, config=config, dtype=dtype,
                          mesh=mesh, mesh_axis=mesh_axis)
        svc.tracer = self.tracer
        self.services[core] = svc
        if self.monitor is not None:
            self._install_hook(core)
        return svc

    @classmethod
    def from_generated(cls, farm_dir: str | pathlib.Path,
                       cores: Optional[Iterable[str]] = None,
                       gang: bool = True, planner: bool = True,
                       gang_cost_model: Optional[GangCostModel] = None,
                       auto_flush_rows: Optional[int] = None,
                       profile: bool = False,
                       **service_kw) -> "OscillatorFarm":
        """Build a farm from a ``generate_farm`` output directory.

        Every subdirectory with weights.npz + solution.json becomes a core;
        its frozen DSE solution is replayed as the service kernel config
        (including the solution's dtype), so serving uses exactly the
        microarchitecture the explorer picked for that system.  One
        adjustment: the solution's stream block is clamped to one client's
        lane block (the same sizing ``PRNGService`` autotunes for) — a
        wider s_block would only compute padding lanes, and since lanes
        evolve independently the clamp is bit-exact.
        """
        import dataclasses
        from repro.core.dse import LANES, Candidate, _pad
        reserved = {"config", "dtype", "activation"} & set(service_kw)
        if reserved:
            raise ValueError(
                f"{sorted(reserved)} are replayed from each core's "
                f"solution.json and cannot be overridden here; use "
                f"add_core() to attach a core with custom values")
        farm_dir = pathlib.Path(farm_dir)
        farm = cls(gang=gang, planner=planner,
                   gang_cost_model=gang_cost_model,
                   auto_flush_rows=auto_flush_rows, profile=profile)
        names = sorted(cores) if cores is not None else sorted(
            p.name for p in farm_dir.iterdir()
            if (p / "solution.json").exists() and (p / "weights.npz").exists())
        if not names:
            raise ValueError(f"no generated cores under {farm_dir}")
        lanes = service_kw.get("lanes_per_client", 128)
        p_cap = max(0, (_pad(lanes, LANES) // LANES).bit_length() - 1)
        for name in names:
            sol = json.loads((farm_dir / name / "solution.json").read_text())
            cand = Candidate(**sol["candidate"])
            cand = dataclasses.replace(cand, p=min(cand.p, p_cap))
            params = dict(np.load(farm_dir / name / "weights.npz"))
            farm.add_core(name, params, config=cand,
                          dtype=jnp.dtype(cand.dtype_name),
                          activation=sol.get("activation", "relu"),
                          **service_kw)
        return farm

    @property
    def cores(self) -> Tuple[str, ...]:
        return tuple(self.services)

    def _svc(self, core: str) -> PRNGService:
        try:
            return self.services[core]
        except KeyError:
            raise KeyError(f"unknown core {core!r}; have {sorted(self.services)}")

    # -- self-healing: quarantine, standbys, rotation ------------------------

    @property
    def quarantined(self) -> frozenset:
        """Cores currently quarantined (skipped by every flush)."""
        return frozenset(self._quarantined)

    @property
    def rotations(self) -> Dict[str, int]:
        """Standby rotations performed so far, per logical core."""
        return dict(self._rotations)

    def add_standby(self, core: str, params, *, config=None, dtype=None,
                    activation: str = "relu", lanes_per_client: int = 128,
                    burn_in: int = 16, backend: str = "auto",
                    mesh=None, mesh_axis: str = "data") -> PRNGService:
        """Attach a cold standby service for logical core ``core``.

        The standby (typically a retrained sibling from the weight
        registry) serves no traffic until :meth:`rotate` installs it in
        the core's routing slot.  Its streams are its own: a client
        re-registered on the standby restarts at row 0 of the standby's
        deterministic stream (same seed => same burn-in => bit-identical
        to serving that client on the standby solo from the start).
        """
        if core not in self.services:
            raise KeyError(f"unknown core {core!r}; attach it before a "
                           f"standby")
        if core in self._standbys:
            raise ValueError(f"core {core!r} already has a standby")
        svc = PRNGService(params, lanes_per_client=lanes_per_client,
                          burn_in=burn_in, activation=activation,
                          backend=backend, config=config, dtype=dtype,
                          mesh=mesh, mesh_axis=mesh_axis)
        svc.tracer = self.tracer
        self._standbys[core] = svc
        return svc

    def has_standby(self, core: str) -> bool:
        return core in self._standbys

    def quarantine(self, core: str, reason: str = "") -> bool:
        """Take ``core`` out of service: every flush skips it, cached
        gang plans and planner decisions drop (its groups re-plan
        without it), and its undeliverable pending demand is cleared
        (the caller already failed the owning futures with
        ``CoreQuarantined``).  Idempotent: returns False when the core
        was already quarantined.  Already-served words parked in its
        outbox stay (they are valid) — they surface if the core is ever
        un-quarantined by a rotation.
        """
        svc = self._svc(core)
        if core in self._quarantined:
            return False
        self._quarantined.add(core)
        for c in svc.clients.values():
            c.pending = 0
        self._deferred.discard(core)
        self._sched._plans.clear()
        self._sched._decisions.clear()
        if self.monitor is not None:
            self.monitor.reset(core)
        return True

    def rotate(self, core: str) -> PRNGService:
        """Install ``core``'s standby in its routing slot and lift the
        quarantine.  Every client of the old service is re-registered on
        the standby with its original seed — their streams restart at
        row 0 of the standby's own deterministic stream (bit-identical
        to a solo farm that served them on the standby all along).
        Returns the replaced (bad) service for post-mortem.
        """
        standby = self._standbys.pop(core, None)
        if standby is None:
            raise ValueError(
                f"core {core!r} has no standby attached; add_standby() "
                f"a registry sibling before rotating")
        old = self._svc(core)
        for c in sorted(old.clients.values(), key=lambda c: c.slot):
            standby.register(c.name, seed=c.seed)
        self.services[core] = standby
        self._quarantined.discard(core)
        self._rotations[core] = self._rotations.get(core, 0) + 1
        self._sched._plans.clear()
        self._sched._decisions.clear()
        if self.monitor is not None:
            self.monitor.reset(core)
            self._install_hook(core)
        return old

    def attach_monitor(self, monitor) -> None:
        """Wire a ``HealthMonitor``: every core's service gets a
        sampling hook that feeds each launch's word slab (bounded, and
        run through the fault plan's sample corruption when a chaos
        harness is attached) into ``monitor.ingest`` — off the delivery
        path.  Under an offloaded front-end the hook runs on the launch
        executor thread; ``ingest`` is thread-safe by contract."""
        self.monitor = monitor
        for core in self.services:
            self._install_hook(core)

    def _install_hook(self, core: str) -> None:
        svc = self.services[core]
        monitor, faults = self.monitor, self.faults
        cap = int(monitor.window_words)
        if faults is not None:
            faults.bind(core, svc)

        def hook(slab, _core=core, _svc=svc):
            # only the rows that hold the first ``cap`` words: a gang
            # member's strided slab is not copied whole for the sample
            w = slab[:-(-cap // slab.shape[1])].reshape(-1)[:cap]
            _svc.tracer.count(absorb_words_copied=w.size)
            if faults is not None:
                w = faults.corrupt_sample(_core, _svc, w)
            monitor.ingest(_core, w)

        svc.sample_hook = hook

    def _check_serving(self, core: str) -> None:
        if core in self._quarantined:
            raise CoreQuarantined(
                f"core {core!r} is quarantined (no standby rotated in); "
                f"resubmit on another core or after rotation",
                core=core, reason="quarantined")

    # -- client API (per-core routing) --------------------------------------

    def register(self, core: str, client: str,
                 seed: Optional[int] = None) -> None:
        """Register a named client stream on one core's pool."""
        self._check_serving(core)
        self._svc(core).register(client, seed=seed)

    def request(self, core: str, client: str, n_words: int,
                auto_flush: bool = False) -> None:
        """Queue a draw; served by the next farm-wide flush().

        ``auto_flush=True`` lets small tenants coalesce instead of each
        calling flush(): after queueing, the farm flushes itself once total
        pending work across all cores reaches ``auto_flush_rows`` word rows
        (immediately when that threshold is None).  Words served by an
        auto-flush are parked in the per-service outboxes and returned by
        the tenant's next flush()/draw() — never dropped.
        """
        self._check_serving(core)
        self._svc(core).request(client, n_words)
        if auto_flush:
            if (self.auto_flush_rows is None
                    or self.pending_rows >= self.auto_flush_rows):
                self.flush(deliver=False)

    @property
    def pending_rows(self) -> int:
        """Unserved demand across all cores, in launch rows (words already
        coverable from client buffers contribute nothing).  This is the
        quantity the ``auto_flush_rows`` threshold compares against — the
        same accounting the async front-end uses for its coalescing
        trigger (``repro.serve.async_frontend``)."""
        return sum(svc.rows_needed() for svc in self.services.values())

    def flush(self, max_wait_rows: Optional[int] = None,
              deliver: bool = True,
              slo_by_core: Optional[Dict[str, str]] = None,
              ) -> Dict[str, Dict[str, np.ndarray]]:
        """Serve every pending request: one batched launch per core GROUP.

        Cores are grouped by gang-compatibility signature (``_compat_key``);
        each group with pending work costs one stacked-weight launch
        (``gang=False``: one launch per core, the legacy path).  Delivered
        words are bit-identical either way.  Every launch of the flush is
        dispatched before any is fetched, and each is then fetched and
        absorbed in launch order (``GangScheduler.run``).

        ``max_wait_rows`` is the deadline knob: a group whose total needed
        rows is below it is *deferred* — no launch, its tenants keep
        waiting so the next flush sees a fuller gang — but a group is never
        deferred twice in a row (the deadline: at most one flush cycle).
        Deferred cores deliver nothing this flush.

        ``deliver=False`` parks all served words in the per-service
        outboxes instead of returning them (the auto-flush path).

        ``slo_by_core`` maps a core name to the SLO class of this flush's
        demand on it (``"latency"`` / ``"bulk"``, the async front-end's
        per-request tiers aggregated per core).  A group launches as
        ``"latency"`` if ANY member core carries latency-class demand
        (forbids the padded group-max shape on skewed demand), as
        ``"bulk"`` only if EVERY member is bulk (pins the padded shape);
        mixed/absent leaves the planner free.  SLO classes never change
        delivered words — only which launch shape serves them.

        Returns {core: {client: words}} for every client that received
        words (pending requests and previously parked outbox words alike).
        """
        if self.faults is not None:
            self.faults.on_flush()
        plans = {core: svc.prepare_rows()
                 for core, svc in self.services.items()
                 if core not in self._quarantined}
        # Group cores that need a launch by compatibility signature.
        groups: Dict[object, List[str]] = {}
        for core, (n_need, _) in plans.items():
            if n_need > 0:
                key = _compat_key(self.services[core]) if self.gang else None
                groups.setdefault(key if key is not None else ("solo", core),
                                  []).append(core)
        launching: List[Tuple[object, List[str]]] = []
        deferred_now: set = set()
        for key, cores in groups.items():
            total = sum(plans[c][0] for c in cores)
            overdue = any(c in self._deferred for c in cores)
            if max_wait_rows is None or total >= max_wait_rows or overdue:
                launching.append((key, cores))
            else:
                deferred_now.update(cores)
        launching_cores = {c for _, cores in launching for c in cores}
        slo_by_core = slo_by_core or {}

        def dispatches() -> Iterator[_Dispatched]:
            for key, cores in launching:
                classes = {slo_by_core.get(c) for c in cores}
                group_slo = ("latency" if "latency" in classes
                             else "bulk" if classes == {"bulk"} else None)
                if self.gang and len(cores) > 1:
                    yield from self._sched.dispatch(
                        key, [(c, self.services[c], plans[c][0], plans[c][1])
                              for c in cores], slo=group_slo)
                else:
                    for c in cores:
                        svc = self.services[c]
                        yield self._sched._dispatch_solo(
                            (c, svc, plans[c][0], plans[c][1]),
                            _round_rows(plans[c][0], svc.config.t_block))

        out = self._sched.run(dispatches(), deliver=deliver)
        # Launch-free delivery pass for cores with nothing to launch (their
        # buffers/outboxes may still owe words).  Deferred cores are fully
        # skipped: their buffers do not cover their pending requests yet.
        for core, (n_need, _) in plans.items():
            if core in launching_cores or core in deferred_now:
                continue
            if n_need == 0:
                served = self.services[core].absorb(None, None, 0,
                                                    deliver=deliver)
                if served:
                    out[core] = served
        self._deferred = deferred_now
        self.tracer.count(flushes=1)
        return out

    def draw(self, core: str, client: str, n_words: int) -> np.ndarray:
        """Convenience: request + flush one client on one core.

        Only that core's pool launches; other cores are untouched (their
        pending requests keep waiting for the next farm-wide flush()).
        """
        self._check_serving(core)
        return self._svc(core).draw(client, n_words)

    @property
    def launches(self) -> int:
        """Actual kernel launches issued: per-core launches + gang launches
        (a gang launch advances a whole group but costs ONE launch)."""
        return (sum(svc.launches for svc in self.services.values())
                + self._sched.launches)

    @property
    def gang_launches(self) -> int:
        return self._sched.launches

    @property
    def dispatch_misses(self) -> int:
        """Distinct (group, bucketed rows) gang keys compiled so far."""
        return self._sched.dispatch_misses

    @property
    def plan_decisions(self) -> Dict[str, int]:
        """Executed planner decisions so far, by kind
        (padded / ragged / split)."""
        return dict(self._sched.decisions)

    @property
    def layout_launches(self) -> Dict[str, int]:
        """Gang launches so far, by layout (stacked / concat)."""
        return dict(self._sched.layouts)

    @property
    def slo_forced(self) -> Dict[str, int]:
        """Planner decisions where an SLO class overrode the free
        cost-minimizing choice (by class)."""
        return dict(self._sched.slo_forced)

    @property
    def profile_stats(self) -> Optional[Dict[str, float]]:
        """The tracer's totals (``profile=True`` farms, else None): stage
        seconds by ``repro.serve.tracer.TIMERS`` key, and the sums of
        ``COUNTERS``, the flush count among them."""
        return self.tracer.stats()

    # -- resumability -------------------------------------------------------

    def snapshot(self) -> Dict[str, object]:
        """Farm-wide snapshot: every core pool, every client, in flight.

        Includes the deadline-deferral set, so a snapshot taken mid-gang
        (between request() and flush(), possibly after a deferring flush)
        replays identically — and each core's device topology, so a
        restore onto a different device count is caught (see restore()).
        """
        return {"cores": {core: svc.snapshot()
                          for core, svc in self.services.items()},
                "gang_launches": self._sched.launches,
                "deferred": sorted(self._deferred),
                "quarantined": sorted(self._quarantined),
                "rotations": dict(self._rotations),
                "topology": {core: _topology(svc)
                             for core, svc in self.services.items()}}

    def restore(self, snap: Dict[str, object], *,
                on_topology_mismatch: str = "refuse") -> None:
        """Restore a snapshot() onto a farm with the SAME cores attached.

        The core sets must match exactly: restoring onto a farm with extra
        cores would leave those pools in their post-snapshot state (clients,
        pending, outbox) — a silently mixed restore point.

        If the snapshot was taken on a different device topology (mesh
        axis / device count / device ids differ for any core), the restore
        must not silently proceed over plans shaped for the old topology:
        ``on_topology_mismatch="refuse"`` (default) raises;
        ``"replan"`` drops every cached gang plan and planner decision and
        restores anyway — stream words are device-count-invariant (lanes
        evolve independently, word rows are absolute), so a sharded
        snapshot restores bit-exactly onto an unsharded farm and vice
        versa once the planner re-plans on the new topology.
        """
        if on_topology_mismatch not in ("refuse", "replan"):
            raise ValueError(
                f"on_topology_mismatch must be 'refuse' or 'replan', "
                f"got {on_topology_mismatch!r}")
        cores = snap["cores"]
        missing = set(cores) - set(self.services)
        extra = set(self.services) - set(cores)
        if missing or extra:
            raise ValueError(
                f"snapshot/farm core mismatch: snapshot-only {sorted(missing)}, "
                f"farm-only {sorted(extra)}")
        snap_topo = snap.get("topology")
        if snap_topo is not None:
            changed = sorted(
                core for core, svc in self.services.items()
                if core in snap_topo
                and _as_topo(snap_topo[core]) != _topology(svc))
            if changed:
                if on_topology_mismatch == "refuse":
                    raise ValueError(
                        f"snapshot device topology differs from this farm's "
                        f"on cores {changed}; restore(snap, "
                        f"on_topology_mismatch='replan') to drop cached "
                        f"plans and re-plan on the current topology")
                self._sched._plans.clear()
                self._sched._decisions.clear()
        # Degraded-topology state replays BEFORE the per-core restores:
        # rotations re-point routing slots at standbys (the snapshot's
        # pool states belong to the post-rotation services), and the
        # per-core restore then overwrites the rotation's re-registered
        # clients wholesale with the snapshot's exact pool state.
        want = {c: int(n) for c, n in dict(snap.get("rotations", {})).items()}
        for core in sorted(set(want) | set(self._rotations)):
            n, have = want.get(core, 0), self._rotations.get(core, 0)
            if have > n:
                raise ValueError(
                    f"farm already rotated core {core!r} {have}x but the "
                    f"snapshot recorded {n}; cannot un-rotate")
            while self._rotations.get(core, 0) < n:
                self.rotate(core)
        self._quarantined = set(snap.get("quarantined", ()))
        for core, sub in cores.items():
            self.services[core].restore(sub)
        self._sched.launches = int(snap.get("gang_launches", 0))
        self._deferred = set(snap.get("deferred", ()))

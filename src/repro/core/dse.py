"""Design-space exploration with analytical cost/latency estimation
(paper §III-B.1/2, Eqs. 8-9, Figs. 3-5) — adapted from FPGA to TPU v5e.

The paper's flow: (1) parameterize the microarchitecture by a parallelism
level P; (2) *measure* post-synthesis latency/cost for a sample of design
points; (3) fit cheap closed-form estimators — latency = (I·H)·poly3(P),
cost = c1·I·H + c2·I + c3·H + β — with per-mode coefficient tables (DSP vs
LUT); (4) use the estimators to sweep the space in seconds and hand the user
min-latency / lowest-cost / Pareto candidates.

TPU mapping (see DESIGN.md §2):
  P              -> log2(stream-block width / 128 lanes)
  DSP vs LUT     -> MXU vs VPU compute path (+ bf16 vs f32 dtype)
  #LUT cost      -> VMEM working-set bytes of the kernel instance
  post-synthesis latency -> cycle count from the microarchitectural model
                    below, cross-validated against compiled-HLO FLOP/byte
                    counts (`validate_cycle_model_vs_hlo` in tests)

The same estimate-then-validate structure is preserved: `measure_candidate`
is the ground-truth oracle (the paper's Vivado report), `LatencyModel` /
`CostModel` are the fitted estimators (the paper's Eqs. 8-9), and
`benchmarks/table3_dse.py` reports estimate-vs-actual exactly like Table III.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# ---------------------------------------------------------------------------
# TPU v5e hardware model (single core).  Documented model constants; the
# roofline numerators elsewhere use the same peak numbers.
# ---------------------------------------------------------------------------
CLOCK_HZ = 940e6
PEAK_BF16_FLOPS = 197e12                    # per chip
MXU_MACS_PER_CYCLE_BF16 = PEAK_BF16_FLOPS / 2 / CLOCK_HZ   # ~104.8k
MXU_MACS_PER_CYCLE_F32 = MXU_MACS_PER_CYCLE_BF16 / 4        # f32 via passes
VPU_FMA_VREGS_PER_CYCLE = 4                 # (8,128) vreg FMAs issued/cycle
HBM_BYTES_PER_CYCLE = 819e9 / CLOCK_HZ      # ~871 B
VMEM_BYTES = 128 * 2 ** 20                  # v5e VMEM
VMEM_USABLE = int(VMEM_BYTES * 0.75)        # compiler headroom
GRID_STEP_OVERHEAD_CYCLES = 500.0           # per pallas grid cell (control)
LOOP_ITER_OVERHEAD_CYCLES = 8.0             # fori_loop bookkeeping per chunk

LANES = 128
SUBLANES = 8


def _pad(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


@dataclasses.dataclass(frozen=True, order=True)
class Candidate:
    """One point in the kernel design space (paper: one HLS solution).

    ``n_nodes > 1`` marks a block-coupled lattice core: ``i_dim``/``h_dim``
    are the full lattice dims (n_nodes x base dims), the weights are
    block-diagonal by construction, and the step carries a diffusive
    coupling term (an extra MXU contraction on the mxu path, roll/select
    passes on the vpu path).  The field is last so older serialized
    candidates (``Candidate(**solution["candidate"])``) keep loading.
    """

    i_dim: int = 3
    h_dim: int = 8
    p: int = 1                  # parallelism level; s_block = 128 * 2**p
    compute_unit: str = "vpu"   # 'vpu' | 'mxu'  (paper: LUT | DSP)
    dtype_bytes: int = 4        # 4 = f32, 2 = bf16
    unroll: int = 4
    t_block: int = 128
    n_nodes: int = 1            # lattice nodes (1 = scalar system)

    @property
    def s_block(self) -> int:
        return LANES * (2 ** self.p)

    @property
    def i_pad(self) -> int:
        return _pad(self.i_dim, SUBLANES)

    @property
    def h_pad(self) -> int:
        return _pad(self.h_dim, SUBLANES)

    @property
    def dtype_name(self) -> str:
        return {2: "bfloat16", 4: "float32"}[self.dtype_bytes]


# ---------------------------------------------------------------------------
# Ground-truth oracle ("post-synthesis measurement" analogue)
# ---------------------------------------------------------------------------

def _overhead_share(c: Candidate) -> float:
    """Per-step control-overhead share of a candidate's (t_block, unroll).

    Used both inside the cycle oracle below and as the tie-break of every
    selection path — ``select`` modes, Pareto-front tie order, and the
    ``select_config`` autotuner.  The Eq. 8/9 estimators are blind to these
    two knobs (they normalize per P / per size), so one scoring rule here
    keeps the DSE output consistent across the flow (a ``select``-emitted
    core and a ``select_config``-tuned service agree on the solution).
    """
    return (GRID_STEP_OVERHEAD_CYCLES / c.t_block
            + LOOP_ITER_OVERHEAD_CYCLES / c.unroll)


def measure_candidate(c: Candidate) -> Dict[str, float]:
    """Microarchitectural cycle/byte accounting for one oscillator step of a
    full stream block, plus the VMEM working set.  Deterministic; this plays
    the role of the paper's post-synthesis Vivado report."""
    vregs = lambda rows, cols: (_pad(rows, SUBLANES) // SUBLANES) * (_pad(cols, LANES) // LANES)

    if c.compute_unit == "vpu":
        # h accumulate: i_dim FMAs over (h_pad, s_block); activation: 1 pass;
        # y accumulate: h_dim FMAs over (i_pad, s_block); bias adds: 2 passes.
        fma_vregs = (
            c.i_dim * vregs(c.h_pad, c.s_block)
            + vregs(c.h_pad, c.s_block)
            + c.h_dim * vregs(c.i_pad, c.s_block)
            + vregs(c.h_pad, c.s_block) + vregs(c.i_pad, c.s_block)
        )
        if c.n_nodes > 1:
            # Block-sparse diffusive coupling: the kernel applies it as
            # wrapped rolls + boundary selects + the scaled accumulate
            # over the (i_pad, s_block) state — ~10 elementwise passes
            # for a ring (grid pays ~2x; model the ring floor), NOT an
            # n_nodes^2 matmul.
            fma_vregs += 10 * vregs(c.i_pad, c.s_block)
        compute_cycles = fma_vregs / VPU_FMA_VREGS_PER_CYCLE
    else:
        macs_per_cycle = (MXU_MACS_PER_CYCLE_BF16 if c.dtype_bytes == 2
                          else MXU_MACS_PER_CYCLE_F32)
        # Both matmuls pad contraction + one free dim to 128 on the MXU.
        macs = (_pad(c.i_pad, 128) * _pad(c.h_pad, 128) * c.s_block
                + _pad(c.h_pad, 128) * _pad(c.i_pad, 128) * c.s_block)
        extra_vpu = 0.0
        if c.n_nodes > 1:
            # The coupling operator is one more genuinely MXU-shaped
            # contraction: (i_pad x i_pad) @ (i_pad x s_block).  The
            # operator is block-sparse (nearest-neighbour blocks only),
            # but the block-sparse route already did its work upstream —
            # the lattice state is n_nodes x base_dim, not n_nodes^2, so
            # a single 128-padded pass covers it.
            macs += _pad(c.i_pad, 128) * _pad(c.i_pad, 128) * c.s_block
            extra_vpu = vregs(c.i_pad, c.s_block)   # the += into y
        # activation + biases still run on the VPU
        vpu_cycles = (vregs(c.h_pad, c.s_block) * 2 + vregs(c.i_pad, c.s_block)
                      + extra_vpu) / VPU_FMA_VREGS_PER_CYCLE
        compute_cycles = macs / macs_per_cycle + vpu_cycles

    # HBM traffic per step: the trajectory write-out (state never leaves VMEM).
    hbm_bytes_per_step = c.i_pad * c.s_block * c.dtype_bytes
    memory_cycles = hbm_bytes_per_step / HBM_BYTES_PER_CYCLE

    # Per-step share of control overheads (shared with the DSE tie-break).
    overhead = _overhead_share(c)

    cycles_per_step = max(compute_cycles, memory_cycles) + overhead
    # Paper-comparable "iteration latency": cycles for one oscillator update
    # of ONE stream (the FPGA implements exactly one oscillator).
    per_stream_cycles = cycles_per_step / c.s_block

    vmem = vmem_bytes(c)
    return {
        "cycles_per_step": cycles_per_step,
        "per_stream_latency_cycles": per_stream_cycles,
        "compute_cycles": compute_cycles,
        "memory_cycles": memory_cycles,
        "overhead_cycles": overhead,
        "vmem_bytes": float(vmem),
        "samples_per_sec": c.s_block / cycles_per_step * CLOCK_HZ,
        "fits_vmem": float(vmem <= VMEM_USABLE),
    }


def vmem_bytes(c: Candidate) -> int:
    """Closed-form VMEM working set of the kernel instance (the cost)."""
    d = c.dtype_bytes
    weights = (c.i_pad * c.h_pad + c.h_pad + c.h_pad * c.i_pad + c.i_pad) * d
    if c.n_nodes > 1 and c.compute_unit == "mxu":
        weights += c.i_pad * c.i_pad * d     # resident coupling operator
    state = c.i_pad * c.s_block * d          # scratch carry
    hidden = c.h_pad * c.s_block * d * c.unroll   # live h per unrolled step
    x0_blk = c.i_pad * c.s_block * d
    out_blk = 2 * c.t_block * c.i_pad * c.s_block * d   # double-buffered
    return weights + state + hidden + x0_blk + out_blk


def stacked_gang_vmem_bytes(c: Candidate, n_cores: int) -> int:
    """VMEM working set of one ``chaotic_ann_gang_stacked_pallas`` launch
    stacking ``n_cores`` equal pools on the sublane axis.

    The kernel keeps the group dimension-major, the C cores on sublanes:
    state and x0 blocks are (I, C, s_block), the live hidden is
    (H, C, s_block), and every weight is a (C, 1) column, which Mosaic
    pads to a whole (sublane x 128-lane) tile.  Pallas double-buffers
    every block, the words block (t_block/2, C, s_block) included, and
    the VPU computes a half-width group at f32 width.  This is the
    planner's stacked-layout feasibility check: the pool size where this
    crosses ``VMEM_USABLE`` is the stacked-layout VMEM cliff, past which
    the planner must fall back to a lane-concat (ragged/padded) launch.
    It is checked against the TPU compiler's own figure
    (tests/test_tpu_compile.py): the compiler needs 97.66 MiB for 512
    stacked bf16 3-8-3 cores at s_block 128, t_block 256, unroll 8.
    """
    C = max(1, int(n_cores))
    d = c.dtype_bytes
    cp = _pad(C, SUBLANES * (4 // d))        # a sublane tile of this dtype
    c32 = _pad(C, SUBLANES)                  # ... and of f32 / uint32
    s = c.s_block
    tables = 2 * (2 * c.i_dim * c.h_dim + c.h_dim + c.i_dim) * cp * LANES * d
    state = 2 * 2 * c.i_dim * cp * s * d     # x0 in, final state out
    hidden = c.h_dim * c32 * s * 4 * c.unroll
    temps = 2 * (c.i_dim + c.h_dim) * c32 * s * 4   # one row's two steps
    out_blk = 2 * (c.t_block // 2) * c32 * s * 4    # uint32 words
    return tables + state + hidden + temps + out_blk


# ---------------------------------------------------------------------------
# Fitted estimators (paper Eqs. 8 & 9)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LatencyModel:
    """Latency = (I·H) · (b3·P³ + b2·P² + b1·P + b0)   (paper Eq. 8).

    Separate coefficient tables per (compute_unit, dtype) — the paper keeps
    separate tables for DSP vs no-DSP."""

    coeffs: Dict[Tuple[str, int], np.ndarray] = dataclasses.field(default_factory=dict)

    @staticmethod
    def fit(p_levels: Sequence[int] = range(0, 6),
            sizes: Sequence[Tuple[int, int]] = ((3, 4), (3, 8), (3, 16), (4, 8), (4, 16)),
            units: Sequence[str] = ("vpu", "mxu"),
            dtypes: Sequence[int] = (4, 2)) -> "LatencyModel":
        """Paper §III-B.2: measure a range of solutions, normalize latency by
        I·H, average per P, then fit a degree-3 polynomial in P."""
        model = LatencyModel()
        for unit, dt in itertools.product(units, dtypes):
            norm_by_p = []
            for p in p_levels:
                vals = []
                for (i, h) in sizes:
                    m = measure_candidate(Candidate(i_dim=i, h_dim=h, p=p,
                                                    compute_unit=unit, dtype_bytes=dt))
                    vals.append(m["per_stream_latency_cycles"] / (i * h))
                norm_by_p.append(np.mean(vals))
            model.coeffs[(unit, dt)] = np.polyfit(np.asarray(list(p_levels), dtype=np.float64),
                                                  np.asarray(norm_by_p), deg=3)
        return model

    def predict(self, i_dim: int, h_dim: int, p: int,
                compute_unit: str = "vpu", dtype_bytes: int = 4) -> float:
        b = self.coeffs[(compute_unit, dtype_bytes)]
        return float((i_dim * h_dim) * np.polyval(b, float(p)))


@dataclasses.dataclass
class CostModel:
    """#VMEM-bytes = c1·I·H + c2·I + c3·H + β, per parallelism level
    (paper Eq. 9, with a per-P constant table)."""

    coeffs: Dict[Tuple[int, str, int], np.ndarray] = dataclasses.field(default_factory=dict)

    @staticmethod
    def fit(p_levels: Sequence[int] = range(0, 6),
            i_range: Sequence[int] = (2, 3, 4, 6, 8),
            h_range: Sequence[int] = (4, 8, 12, 16, 24, 32),
            units: Sequence[str] = ("vpu", "mxu"),
            dtypes: Sequence[int] = (4, 2)) -> "CostModel":
        model = CostModel()
        for p, unit, dt in itertools.product(p_levels, units, dtypes):
            rows, ys = [], []
            for i, h in itertools.product(i_range, h_range):
                c = Candidate(i_dim=i, h_dim=h, p=p, compute_unit=unit, dtype_bytes=dt)
                rows.append([i * h, i, h, 1.0])
                ys.append(float(vmem_bytes(c)))
            sol, *_ = np.linalg.lstsq(np.asarray(rows), np.asarray(ys), rcond=None)
            model.coeffs[(p, unit, dt)] = sol
        return model

    def predict(self, i_dim: int, h_dim: int, p: int,
                compute_unit: str = "vpu", dtype_bytes: int = 4) -> float:
        c1, c2, c3, beta = self.coeffs[(p, compute_unit, dtype_bytes)]
        return float(c1 * i_dim * h_dim + c2 * i_dim + c3 * h_dim + beta)


# ---------------------------------------------------------------------------
# Gang launch-cost model: Eq. 8/9 lifted from kernel instances to LAUNCHES
# ---------------------------------------------------------------------------

# Fixed per-launch overhead (dispatch, host sync, argument marshalling) in
# model cycles.  The PR 3 gang scheduler implicitly set this to infinity
# ("one launch is always cheaper"); the planner needs a finite default, and
# ``GangCostModel.fit`` replaces it with a measured value.
GANG_LAUNCH_OVERHEAD_CYCLES = 30_000.0
# Host-side buffering rate for overdraw words (absorb copies them into
# per-client numpy buffers); modeled well below HBM speed.
HOST_BUFFER_BYTES_PER_CYCLE = HBM_BYTES_PER_CYCLE / 4.0


@dataclasses.dataclass
class GangCostModel:
    """Predicts the cost of ONE kernel launch for (membership, per-core
    rows, layout) — the estimator a gang *planner* minimizes over.

    ``LatencyModel``/``CostModel`` (paper Eqs. 8/9) estimate the per-stream
    step latency and VMEM cost of a kernel instance; they say nothing about
    what a whole launch costs, which is what decides whether a skewed-demand
    group should launch once at the group max (PR 3's policy), once ragged
    (each lane block computes only its own demand), or split into several
    launches.  The launch cost here is

        cycles = launch_overhead_cycles
               + sum_over_lane_blocks( 2 * rows_block ) * step_cycles
               + buffered_overdraw_words * 4 / HOST_BUFFER_BYTES_PER_CYCLE

    where ``step_cycles`` comes from the same microarchitectural accounting
    as ``measure_candidate`` — for a sublane-stacked gang sweep the
    compute/memory terms scale with the stack height C (one fused op
    advances all C cores), while the per-cell control overhead is paid once.

    ``fit`` calibrates the wall-clock-sensitive knobs against real
    launches on the serving machine: the fixed per-launch overhead, a
    per-grid-cell overhead (an analytic share is already inside
    ``step_cycles`` via ``_overhead_share``, but executed cells can carry
    a much larger fixed cost — e.g. Pallas interpret mode pays several ms
    per cell), and a stacked-sweep scale factor (XLA executes a C-tall
    sweep at other than exactly C times the single-core rate).
    ``sec_per_cycle`` is kept so fitted costs can be reported in seconds.
    """

    launch_overhead_cycles: float = GANG_LAUNCH_OVERHEAD_CYCLES
    cell_overhead_cycles: float = 0.0
    stacked_step_scale: float = 1.0
    # Per-row cost of the ragged-stacked freeze (one mask compare + select
    # over the stacked state per word row); analytic default ~2 vreg ops.
    freeze_row_cycles: float = 4.0
    # Extra cost per device beyond the first when a launch is shard_map'd
    # across a mesh (collective setup, per-device program dispatch, and
    # the all-gather that brings every device's words onto each device
    # inside the launch).  The compute/cell terms are counted on the
    # *busiest device's shard* (``n_dev`` in launch_cycles/gang_cost/
    # solo_cost), so this is the only term that grows with the mesh —
    # ``fit(mesh=...)`` measures it from a real sharded launch, gather
    # included.
    cross_dev_overhead_cycles: float = 10_000.0
    sec_per_cycle: Optional[float] = None

    def step_cycles(self, c: Candidate, stack: int = 1) -> float:
        """Cycles for one oscillator step of one s_block-wide lane block
        with ``stack`` cores stacked on the sublane axis."""
        m = measure_candidate(c)
        compute = m["compute_cycles"] * stack
        memory = m["memory_cycles"] * stack
        scale = self.stacked_step_scale if stack > 1 else 1.0
        return max(compute, memory) * scale + _overhead_share(c)

    def launch_cycles(self, c: Candidate, rows_by_block: Sequence[int],
                      *, stack: int = 1, n_dev: int = 1) -> float:
        """One launch computing ``rows_by_block[i]`` word rows in lane
        block ``i`` (2 oscillator steps per word row).

        Only the FMA steps shrink with a block's rows: the grid is static
        (every block iterates the launch's full time axis), so an
        early-out cell still pays its dispatch/DMA share — cell overhead
        counts the whole max(rows)-deep grid for every block.

        ``n_dev > 1`` models the shard_map'd launch: lane blocks split
        into contiguous runs of ``ceil(blocks/n_dev)`` per device, so the
        step and cell terms follow the *busiest device's shard* (SPMD
        wall time) and each extra device adds
        ``cross_dev_overhead_cycles`` of dispatch.
        """
        n_dev = max(1, int(n_dev))
        rows_per_cell = max(1, c.t_block // 2)
        t_cells = max(1, -(-int(max(rows_by_block)) // rows_per_cell))
        blocks_local = -(-len(rows_by_block) // n_dev)
        if n_dev > 1:
            rb = list(rows_by_block)
            steps = 2.0 * float(max(
                sum(rb[d * blocks_local:(d + 1) * blocks_local])
                for d in range(n_dev)))
        else:
            steps = 2.0 * float(sum(rows_by_block))
        cells = blocks_local * t_cells
        return (self.launch_overhead_cycles
                + self.cross_dev_overhead_cycles * (n_dev - 1)
                + self.cell_overhead_cycles * cells
                + steps * self.step_cycles(c, stack))

    def buffer_cycles(self, overdrawn_words: float) -> float:
        """Host cost of buffering overdraw words nobody asked for yet."""
        return 4.0 * float(overdrawn_words) / HOST_BUFFER_BYTES_PER_CYCLE

    def gang_cost(self, c: Candidate, demands: Sequence[int],
                  blocks: Sequence[int], lanes: Sequence[int], *,
                  layout: str, rows_by_block: Optional[Sequence[int]] = None,
                  n_dev: int = 1) -> float:
        """Cost of one gang launch serving members with ``demands`` word
        rows (``blocks``/``lanes`` = per-member lane-block and live-lane
        counts).

        layout 'stacked': the whole group advances max(demands) rows per
        lane block (ragged freeze changes buffering, not compute).
        layout 'concat': pass ``rows_by_block`` for a ragged launch — the
        per-BLOCK effective rows, ``sum(blocks)`` long, member ``i``
        occupying ``blocks[i]`` consecutive equal entries; None means the
        padded group-max launch.
        """
        dmax = max(demands)
        if layout == "stacked":
            cost = self.launch_cycles(c, [dmax] * blocks[0],
                                      stack=len(demands), n_dev=n_dev)
            # ragged freeze absorbs exactly the demand -> no overdraw, but
            # pays the per-row freeze mask over the whole launch (split
            # across devices along the lane axis)
            if rows_by_block is not None:
                cost += (self.freeze_row_cycles * dmax * blocks[0]
                         / max(1, n_dev))
                over = 0
            else:
                over = sum((dmax - d) * l for d, l in zip(demands, lanes))
        else:
            if rows_by_block is None:
                rows_by_block = [dmax] * sum(blocks)
                per_member = [dmax] * len(demands)
            else:
                # every block of a member computes its demand, so the
                # member's advanced rows are its first block's entry
                starts = np.cumsum([0] + list(blocks[:-1]))
                per_member = [rows_by_block[int(s)] for s in starts]
            over = sum((r - d) * l
                       for r, d, l in zip(per_member, demands, lanes))
            cost = self.launch_cycles(c, rows_by_block, n_dev=n_dev)
        return cost + self.buffer_cycles(max(0, over))

    def solo_cost(self, c: Candidate, rows: int, blocks: int, *,
                  n_dev: int = 1) -> float:
        """One per-core launch of ``rows`` word rows over ``blocks`` lane
        blocks (``n_dev``: the pool's own shard_map'd launch when its
        service sits on a mesh)."""
        return self.launch_cycles(c, [rows] * blocks, n_dev=n_dev)

    def seconds(self, cycles: float) -> Optional[float]:
        return None if self.sec_per_cycle is None else cycles * self.sec_per_cycle

    @classmethod
    def fit(cls, c: Candidate, *, backend: str = "auto", n_cores: int = 3,
            reps: int = 3, clock=None, mesh=None,
            mesh_axis: str = "data") -> "GangCostModel":
        """Calibrate (launch_overhead_cycles, cell_overhead_cycles,
        stacked_step_scale, sec_per_cycle) from real launches of
        candidate ``c`` — the paper's estimate-then-validate loop applied
        to the launch model.

        Five measurements separate the terms:
          t1  solo launch, 1 grid cell   (t_block//2 rows)
          t2  solo launch, 2 cells, 2x the steps
          t3  solo launch, 2 cells, SAME steps (t_block halved)
          t4  sublane-stacked gang launch of ``n_cores`` cores, 1 cell
          t5  the same stacked launch with a skewed row map (freeze)
        so  cell_sec = t3 - t1,  step_sec = (t2 - t3) / steps,
        launch_sec = t1 - cell_sec - steps * step_sec, t4 gives the
        stacked-sweep scale and t5 - t4 the per-row freeze cost.  Runs
        5 + 5*reps kernel launches.  ``clock`` injects the timer
        (``repro.clock.Clock``); the default ``SystemClock`` measures
        real wall time.

        With a ``mesh`` (>1 device on ``mesh_axis``), one extra
        measurement t6 — a lane-concat gang of one block per device,
        shard_map'd across the mesh — calibrates
        ``cross_dev_overhead_cycles``: each device does exactly t1's
        per-shard work, so the residual over t1 split across the extra
        devices is the per-device dispatch fee.  (On a host with fewer
        physical CPUs than forced devices this honestly measures the
        serialization penalty, steering the planner away from
        over-sharding.)
        """
        import dataclasses as _dc

        import jax
        import jax.numpy as jnp

        from repro.clock import SystemClock
        from repro.kernels import ops  # lazy: keep dse importable alone

        clock = clock or SystemClock()
        base = cls()
        rng = np.random.default_rng(0)
        dtype = jnp.dtype(c.dtype_name)

        def mk_params():
            return {"w1": jnp.asarray(rng.normal(0, .4, (c.i_dim, c.h_dim)),
                                      dtype),
                    "b1": jnp.asarray(rng.normal(0, .1, (c.h_dim,)), dtype),
                    "w2": jnp.asarray(rng.normal(0, .4, (c.h_dim, c.i_dim)),
                                      dtype),
                    "b2": jnp.asarray(rng.normal(0, .1, (c.i_dim,)), dtype)}

        def timed(fn):
            fn()                                   # compile
            ts = []
            for _ in range(reps):
                t0 = clock.now()
                out = fn()
                jax.tree_util.tree_map(
                    lambda a: a.block_until_ready()
                    if hasattr(a, "block_until_ready") else a, out)
                ts.append(clock.now() - t0)
            ts.sort()
            return ts[len(ts) // 2]

        params = mk_params()
        x0 = jnp.asarray(rng.normal(0, .3, (c.s_block, c.i_dim)), dtype)
        rows = max(4, c.t_block // 2)
        steps = 2 * rows
        c_half = _dc.replace(c, t_block=max(2, c.t_block // 2))
        t1 = timed(lambda: ops.chaotic_bits(
            params, x0, steps, config=c, backend=backend))
        t2 = timed(lambda: ops.chaotic_bits(
            params, x0, 2 * steps, config=c, backend=backend))
        t3 = timed(lambda: ops.chaotic_bits(
            params, x0, steps, config=c_half, backend=backend))
        if t2 <= t3:                              # timing noise: keep defaults
            return base
        cell_sec = max(0.0, t3 - t1)
        step_sec = (t2 - t3) / steps
        launch_sec = max(0.0, t1 - cell_sec - steps * step_sec)
        spc = step_sec / base.step_cycles(c)
        overhead = float(np.clip(launch_sec / spc, 500.0, 5e8))
        cell_overhead = float(np.clip(cell_sec / spc, 0.0, 5e8))
        scale, freeze = 1.0, cls.freeze_row_cycles
        if c.compute_unit == "vpu":
            plist = [mk_params() for _ in range(n_cores)]
            stacked = {k: jnp.stack([p[k] for p in plist])
                       for k in ("w1", "b1", "w2", "b2")}
            xs = jnp.asarray(rng.normal(0, .3, (n_cores, c.s_block, c.i_dim)),
                             dtype)
            t4 = timed(lambda: ops.chaotic_bits_gang_stacked(
                stacked, xs, steps, config=c, backend=backend))
            st_step_sec = max(1e-12, t4 - launch_sec - cell_sec) / steps
            m = measure_candidate(c)
            sweep = max(m["compute_cycles"], m["memory_cycles"]) * n_cores
            scale = float(np.clip(
                (st_step_sec / spc - _overhead_share(c)) / sweep, 0.1, 4.0))
            skew_map = np.asarray([rows] + [min(rows, 4)] * (n_cores - 1),
                                  np.int32)
            t5 = timed(lambda: ops.chaotic_bits_gang_stacked(
                stacked, xs, steps, row_map=skew_map, config=c,
                backend=backend))
            freeze = float(np.clip((t5 - t4) / rows / spc,
                                   cls.freeze_row_cycles, 5e7))
        cross = cls.cross_dev_overhead_cycles
        if mesh is not None and int(mesh.shape[mesh_axis]) > 1:
            n_dev = int(mesh.shape[mesh_axis])
            plist = [mk_params() for _ in range(n_dev)]
            gparams = {k: jnp.stack([p[k] for p in plist])
                       for k in ("w1", "b1", "w2", "b2")}
            xg = jnp.asarray(
                rng.normal(0, .3, (n_dev * c.s_block, c.i_dim)), dtype)
            cmap = np.arange(n_dev, dtype=np.int32)
            t6 = timed(lambda: ops.chaotic_bits_gang(
                gparams, xg, steps, core_map=cmap, config=c,
                backend=backend, mesh=mesh, mesh_axis=mesh_axis))
            cross = float(np.clip((t6 - t1) / (n_dev - 1) / spc, 0.0, 5e8))
        return cls(launch_overhead_cycles=overhead,
                   cell_overhead_cycles=cell_overhead,
                   stacked_step_scale=scale, freeze_row_cycles=freeze,
                   cross_dev_overhead_cycles=cross,
                   sec_per_cycle=spc)


# ---------------------------------------------------------------------------
# Exploration (paper §III-B.1, Figs. 3 & 5)
# ---------------------------------------------------------------------------

def enumerate_candidates(i_dim: int, h_dim: int,
                         p_levels: Sequence[int] = range(0, 6),
                         units: Sequence[str] = ("vpu", "mxu"),
                         dtypes: Sequence[int] = (4, 2),
                         unrolls: Sequence[int] = (1, 2, 4, 8),
                         t_blocks: Sequence[int] = (32, 64, 128, 256),
                         n_nodes: int = 1) -> List[Candidate]:
    out = []
    for p, u, d, un, tb in itertools.product(p_levels, units, dtypes, unrolls, t_blocks):
        c = Candidate(i_dim=i_dim, h_dim=h_dim, p=p, compute_unit=u,
                      dtype_bytes=d, unroll=un, t_block=tb, n_nodes=n_nodes)
        if vmem_bytes(c) <= VMEM_USABLE:
            out.append(c)
    return out


def _objective_score(c: Candidate, i_dim: int, h_dim: int,
                     lm: "LatencyModel", cm: "CostModel",
                     objective: str) -> Tuple[float, ...]:
    """The shared selection key: (primary estimate, objective-true ties).

    Ties are broken in the objective's own currency: min_latency prefers
    the lower analytic control-overhead share, lowest_cost prefers the
    smaller *measured* VMEM working set (the estimator is blind to
    (t_block, unroll) but the real footprint is not — out/hidden buffers
    scale with both), with overhead as the final tie-break.

    Lattice candidates (``n_nodes > 1``) score on the extended cycle
    model directly: the Eq. 8/9 estimators were fitted on scalar-core
    sizes (I<=8, H<=32) and normalize per I*H, so extrapolating them to
    lattice dims would erase exactly the block-sparse compute-unit
    tradeoff the lattice arms of ``measure_candidate`` encode.
    """
    if c.n_nodes > 1:
        m = measure_candidate(c)
        if objective == "min_latency":
            return (m["per_stream_latency_cycles"], _overhead_share(c))
        if objective == "lowest_cost":
            return (m["vmem_bytes"], _overhead_share(c))
        raise ValueError(f"unknown objective {objective!r}")
    if objective == "min_latency":
        primary = lm.predict(i_dim, h_dim, c.p, c.compute_unit, c.dtype_bytes)
        return (primary, _overhead_share(c))
    if objective == "lowest_cost":
        primary = cm.predict(i_dim, h_dim, c.p, c.compute_unit, c.dtype_bytes)
        return (primary, float(vmem_bytes(c)), _overhead_share(c))
    raise ValueError(f"unknown objective {objective!r}")


def pareto_front(cands: Sequence[Candidate],
                 latency_model: LatencyModel | None = None,
                 cost_model: CostModel | None = None) -> List[Tuple[Candidate, float, float]]:
    """Non-dominated (cost, latency) set, using the *estimators* (the paper's
    DSE runs entirely on Eq. 8/9 estimates; synthesis happens after).

    Candidates tied on (cost, latency) — the estimators ignore (t_block,
    unroll) — are represented by the lowest-overhead one (same tie-break as
    ``select``/``select_config``), not by enumeration order.
    """
    scored = []
    for c in cands:
        if latency_model is not None:
            lat = latency_model.predict(c.i_dim, c.h_dim, c.p, c.compute_unit, c.dtype_bytes)
            cost = cost_model.predict(c.i_dim, c.h_dim, c.p, c.compute_unit, c.dtype_bytes)
        else:
            m = measure_candidate(c)
            lat, cost = m["per_stream_latency_cycles"], m["vmem_bytes"]
        scored.append((c, cost, lat))
    front = []
    for c, cost, lat in sorted(scored,
                               key=lambda t: (t[1], t[2], _overhead_share(t[0]))):
        if all(not (fc <= cost and fl <= lat) for _, fc, fl in front):
            front.append((c, cost, lat))
    return front


def select(i_dim: int, h_dim: int, mode: str = "pareto", p: int | None = None,
            latency_model: LatencyModel | None = None,
            cost_model: CostModel | None = None,
            n_nodes: int = 1) -> Candidate:
    """Paper's three user options: 'min_latency', 'lowest_cost', or
    'pareto' with requested parallelism P."""
    lm = latency_model or LatencyModel.fit()
    cm = cost_model or CostModel.fit()
    cands = enumerate_candidates(i_dim, h_dim, n_nodes=n_nodes)
    if mode in ("min_latency", "lowest_cost"):
        return min(cands,
                   key=lambda c: _objective_score(c, i_dim, h_dim, lm, cm, mode))
    if mode == "pareto":
        front = pareto_front(cands, lm, cm)
        if p is not None:
            match = [c for c, _, _ in front if c.p == p]
            if match:
                return match[0]
            return min((c for c, _, _ in front), key=lambda c: abs(c.p - p))
        return front[len(front) // 2][0]
    raise ValueError(f"unknown mode {mode!r}")


# ---------------------------------------------------------------------------
# Autotuner: the DSE output driving the hot path (per-process cached)
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "f32": 4, "bf16": 2, 4: 4, 2: 2}


@functools.lru_cache(maxsize=None)
def _fitted_models() -> Tuple[LatencyModel, CostModel]:
    """Eq. 8/9 estimators, fitted once per process (~ms; pure numpy)."""
    return LatencyModel.fit(), CostModel.fit()


@functools.lru_cache(maxsize=None)
def select_config(i_dim: int, h_dim: int, s_total: Optional[int] = None,
                  dtype: object = "float32", unit: Optional[str] = None,
                  objective: str = "min_latency",
                  n_nodes: int = 1) -> Candidate:
    """Pick (s_block, t_block, unroll, compute_unit) for a kernel launch.

    The autotuned replacement for hand-picked per-call-site defaults: scores
    the enumerated design space with the *fitted* Eq. 8/9 estimators (the
    paper's DSE runs on estimates, not measurements), breaking ties between
    same-(P, unit) candidates with the analytic per-step overhead terms that
    the estimators normalize away.

    Args:
      s_total: number of streams the caller will actually launch; candidates
        whose stream block exceeds the padded stream count are dropped (they
        would only compute padding lanes).
      dtype: 'float32' | 'bfloat16' (or 4 | 2 byte widths, or a jnp dtype).
      unit: restrict to 'vpu' or 'mxu'; None searches both.
      objective: 'min_latency' | 'lowest_cost'.
    """
    key = dtype if isinstance(dtype, (str, int)) else np.dtype(dtype).name
    dt = _DTYPE_BYTES.get(key)
    if dt is None:
        raise ValueError(f"unknown dtype {dtype!r}")
    units = (unit,) if unit else ("vpu", "mxu")
    cands = enumerate_candidates(i_dim, h_dim, units=units, dtypes=(dt,),
                                 n_nodes=n_nodes)
    if s_total is not None:
        # p=0 (s_block=128) always fits the cap, so this never empties cands.
        s_cap = max(LANES, _pad(s_total, LANES))
        cands = [c for c in cands if c.s_block <= s_cap]
    if not cands:
        raise ValueError(f"no feasible candidate for I={i_dim} H={h_dim}")
    lm, cm = _fitted_models()
    return min(cands,
               key=lambda c: _objective_score(c, i_dim, h_dim, lm, cm, objective))

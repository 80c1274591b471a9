"""The four-chip cell ``farm5.bulk_mesh4`` at the CPU interpreter's size,
on four forced host devices, and the reader of ``device_busy_spread``.

Tier-1 runs on one CPU device, so the traced run is made in a subprocess
with ``--xla_force_host_platform_device_count=4``: the pools split over
the four devices, the audit holds at limit 0 against ``bench/cores/ann.py``,
the window compiles nothing, and the counters of mesh launches and launch
builds reach the per-layer readers through ``obs["stages"]``.  A chip that
computes its shard of the solo launch wrongly fails ``correct``, and the
cell's audit sample holds a record on every chip of every core.
"""
import json
import math
import os
import re
import subprocess
import sys

import pytest

from bench import serve, spec, trace

SCRIPT = """
import json
import pathlib
import sys
sys.path[:0] = [{root!r}, {root!r} + "/src", {root!r} + "/bench/tests"]
from tiny import tiny_cell
from bench import run as bench_run
bench_run.TRACE_DIR = pathlib.Path({tmp!r}) / "trace"
cell = tiny_cell("farm5.bulk_mesh4")
devs, _ = bench_run.device_info(cell.chips, require_tpu=False)
peaks = json.loads((bench_run.ROOT / "bench" / "peaks.json").read_text())
result, checks, _ = bench_run.run(cell, 2 ** 31 + 1013, 4.0, True, devs,
                                  peaks["TPU v5 lite"])
bench_run.emit(result, checks)
"""


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("mesh4")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(root=str(spec.ROOT),
                                             tmp=str(tmp))],
        env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def test_the_audit_holds_on_four_devices(tiny_run):
    result, _ = tiny_run
    assert result["correct"], result["checks"]
    for name in ("kernel_word_mismatch", "state_mismatch_lanes",
                 "delivered_word_mismatch", "unresolved_draws"):
        assert result["checks"][name]["value"] == 0, name
    assert result["device"]["count"] == 4


def test_the_window_compiles_nothing(tiny_run):
    _, err = tiny_run
    found = re.findall(r"inside the window: (\d+) compiles", err)
    assert found == ["0"], err[-3000:]


def test_the_mesh_counters_reach_the_readers(tiny_run):
    result, _ = tiny_run
    metrics = result["metrics"]
    assert metrics["launch_builds_per_flush.bulk_mesh4"]["value"] == 0
    assert metrics["mesh_split_share.bulk_mesh4"]["value"] == 100.0
    # no device planes in a CPU trace: the device readers report nothing
    assert "device_busy_spread.bulk_mesh4" not in metrics


def _two_devices():
    ops = {"/device:TPU:0": [("k", 100.0, 500.0), ("k", 600.0, 700.0)],
           "/device:TPU:1": [("k", 50.0, 450.0), ("k", 900.0, 1200.0)]}
    host = [(trace.WINDOW_OPEN, 0.0, 1.0), (trace.WINDOW_CLOSE, 1000.0,
                                            1001.0)]
    return trace.Trace(devices=ops, host=host)


def test_device_busy_spread_on_a_hand_built_trace():
    read = spec.reader("device_busy_spread.bulk_mesh4")
    # busy in [0, 1000]: device 0 500 ns, device 1 400 + 100 = 500 ns
    assert read({"trace": _two_devices()}) == pytest.approx(0.0)
    tr = _two_devices()
    tr.devices["/device:TPU:1"] = [("k", 50.0, 250.0)]      # 200 ns busy
    assert read({"trace": tr}) == pytest.approx(100.0 * 300 / 350)
    tr.devices.pop("/device:TPU:1")
    assert read({"trace": tr}) is None                  # one device
    assert read({"trace": None}) is None


FAULT = """
import json
import sys
sys.path[:0] = [{root!r}, {root!r} + "/src", {root!r} + "/bench/tests"]
from tiny import run_tiny, tiny_cell
from repro.kernels import ops

real = ops.chaotic_ann_bits_sharded
out = {{}}
for dev, seed in enumerate({seeds!r}):
    def broken(*a, mesh, mesh_axis, _dev=dev, **kw):
        # the words of one device's lanes altered, the other three intact
        words, state = real(*a, mesh=mesh, mesh_axis=mesh_axis, **kw)
        n = words.shape[1] // int(mesh.shape[mesh_axis])
        lanes = slice(_dev * n, (_dev + 1) * n)
        return words.at[:, lanes].set(words[:, lanes] ^ 1), state
    ops.chaotic_ann_bits_sharded = broken
    cell = tiny_cell("farm5.bulk_mesh4")
    # one tenant's 128 lanes on each device; every (launch, tenant) kept
    cell.mix.update(tenants_per_core=4, audit_tenants_per_core=4,
                    audit_records=512)
    result, checks, numbers = run_tiny(cell, seed)
    out[dev] = {{"correct": result["correct"],
                "kernel": numbers["kernel_word_mismatch"],
                "delivered": numbers["delivered_word_mismatch"]}}
print("RESULT " + json.dumps(out))
"""


def test_a_chip_that_computes_its_shard_wrongly_is_not_correct():
    """The words of one device's quarter of the hyperlorenz solo launch
    altered, for each of the four devices in turn (and a seed of its own):
    the audit's records of that device's tenant catch it."""
    seeds = [2 ** 31 + 1013, 2 ** 32 + 77, 2 ** 31 + 5003, 2 ** 32 + 901]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", FAULT.format(root=str(spec.ROOT),
                                            seeds=seeds)],
        env=env, capture_output=True, text=True, timeout=900)
    line = [x for x in p.stdout.splitlines() if x.startswith("RESULT ")]
    assert p.returncode == 0 and line, p.stdout[-2000:] + p.stderr[-4000:]
    found = json.loads(line[-1][len("RESULT "):])
    for dev in "0123":
        assert not found[dev]["correct"], (dev, found)
        assert found[dev]["kernel"] > 0, (dev, found)
        assert found[dev]["delivered"] > 0, (dev, found)


def test_the_cell_audits_every_chip_of_every_core():
    """At the cell's own mix (256 tenants per core, a quarter of each
    pool's lanes on each chip), on 400 large seeds: every chip's quarter
    of every core holds audited tenants, and the chance that the
    reservoir of ``audit_records`` (launch, tenant) events keeps none of
    some chip's and core's, each event drawn with the share of audited
    tenants there, is under 1e-5 on each seed (PERF.md section 2)."""
    cell = spec.cell("farm5.bulk_mesh4")
    per = int(cell.mix["tenants_per_core"])
    chips = int(cell.config["chips"])
    lanes = int(cell.config["lanes_per_client"])
    kept = int(cell.mix["audit_records"])
    for i in range(400):
        sess = serve.Session(cell, 2 ** 31 + 7919 * i, 51.0)
        held = {}
        for core, client in sess.audited:
            slot = int(client[1:])             # registered in name order
            chip = slot * lanes * chips // (per * lanes)
            held[core, chip] = held.get((core, chip), 0) + 1
        assert len(held) == len(sess.cores) * chips, (i, held)
        miss = sum(math.exp(kept * math.log1p(-m / len(sess.audited)))
                   for m in held.values())
        assert miss < 1e-5, (i, miss, held)

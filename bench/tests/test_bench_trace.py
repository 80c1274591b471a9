"""bench/trace.py: the busy union, the idle share and kernel time by name,
on hand-made intervals and on a small trace recorded on the chip."""
import pathlib

import numpy as np
import pytest

from bench import spec, trace

FIXTURE = pathlib.Path(__file__).resolve().parents[1] / "fixtures"
KERNEL = ('%chaotic_ann_bits_pallas.3 = (u32[4,256]{1,0}, bf16[8,256]{1,0}) '
          'custom-call(bf16[8,8,1]{2,1,0} %a, bf16[8,1]{1,0} %b), '
          'custom_call_target="tpu_custom_call"')


def _hand_made():
    ops = [("%copy.1 = f32[2] copy(f32[2] %x)", 100.0, 300.0),
           (KERNEL, 200.0, 500.0),              # overlaps the copy
           (KERNEL, 700.0, 800.0),
           ("%fusion.2 = f32[2] fusion(f32[2] %y)", 950.0, 1200.0)]
    host = [(trace.WINDOW_OPEN, 0.0, 1.0), ("bench.submit", 500.0, 700.0),
            (trace.WINDOW_CLOSE, 1000.0, 1001.0)]
    return trace.Trace(devices={"/device:TPU:0": ops}, host=host)


def test_busy_union_and_idle_share_by_hand():
    tr = _hand_made()
    assert tr.window() == (0.0, 1000.0)
    # union of [100, 500], [700, 800], [950, 1000] (clipped) = 550 ns
    assert trace.busy_s(tr) == pytest.approx(550e-9)
    assert trace.idle_share(tr) == pytest.approx(0.45)
    assert trace.kernel_time(tr) == {"chaotic_ann_bits_pallas":
                                     pytest.approx(400e-9)}
    gaps = trace.idle_gaps(tr)
    assert gaps[0] == ("bench.submit", pytest.approx(200e-9))
    assert ("host idle", pytest.approx(100e-9)) in gaps
    assert trace.op_time(tr)[0] == ("chaotic_ann_bits_pallas",
                                    pytest.approx(400e-9))


def _fixture():
    import gzip
    import json
    raw = json.loads(gzip.decompress(
        (FIXTURE / "bulk_window.json.gz").read_bytes()))
    devices = {p: [tuple(o) for o in ops] for p, ops in raw["devices"].items()}
    return trace.Trace(devices=devices, host=[tuple(h) for h in raw["host"]])


def _sweep_busy(ops, t0, t1):
    """Busy time by an endpoint sweep, independent of ``trace.merged``."""
    edges = sorted([(max(s, t0), 1) for _, s, e in ops if e > t0 and s < t1]
                   + [(min(e, t1), -1) for _, s, e in ops
                      if e > t0 and s < t1])
    busy, depth, last = 0.0, 0, None
    for t, d in edges:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_chip_trace_fixture():
    """A 1 s window of farm5.bulk recorded on one TPU v5 lite."""
    tr = _fixture()
    t0, t1 = tr.window()
    ops = tr.devices["/device:TPU:0"]
    assert trace.busy_s(tr) == pytest.approx(_sweep_busy(ops, t0, t1) / 1e9)
    assert trace.busy_s(tr) == pytest.approx(0.075499075)
    assert trace.idle_share(tr) == pytest.approx(
        1 - trace.busy_s(tr) * 1e9 / (t1 - t0))
    assert trace.idle_share(tr) == pytest.approx(0.9243061608915882)
    want = {}
    for n, s, e in ops:
        if t0 <= s < t1 and "tpu_custom_call" in n:
            name = n.split(" = ")[0].lstrip("%").rsplit(".", 1)[0]
            want[name] = want.get(name, 0.0) + (e - s) / 1e9
    got = trace.kernel_time(tr)
    assert set(got) == {"chaotic_ann_bits_pallas",
                        "chaotic_ann_gang_stacked_pallas"} == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k])
    assert got["chaotic_ann_gang_stacked_pallas"] == pytest.approx(0.031051382)


def test_the_latency_tail_reader():
    read = spec.reader("draw_p99_ms.bulk")
    lat = np.concatenate([np.full(980, 93.0), np.full(20, 210.0)])
    assert read({"latency_ms": lat}) == pytest.approx(
        float(np.percentile(lat, 99)))
    assert read({"latency_ms": np.array([])}) is None

"""bench/work.py against hand counts."""
import numpy as np
import pytest

from bench import spec, work
from bench.cores import ann

FARM = "results/generated_cores/farm"


def _cls(name, **kw):
    core = ann.load(spec.ROOT, {"weights": f"{FARM}/{name}/weights.npz"})
    return work.core_class(core["w1"], core["w2"], dtype="bfloat16", **kw)


def test_flops_per_word_by_hand():
    # 3-8-3: w1 and w2 have 24 nonzeros each; 2 FLOPs per nonzero per
    # step, 2 steps per word: 2 * 2 * (24 + 24) = 192
    assert _cls("chen")["flops_per_word"] == 192
    # 4-16-4: 2 * 2 * (64 + 64) = 512
    assert _cls("hyperlorenz")["flops_per_word"] == 512
    # chen@ring32: 32 nodes of 3-8-3 (2 * 48 * 32 = 3072 per step) plus a
    # ring operator of 3 nonzeros per row of 96 (2 * 288 = 576 per step):
    # 2 * (3072 + 576) = 7296, not the dense 2 * 2 * (2 * 96 * 256 + 96 * 96)
    ring = _cls("chen", n_nodes=32,
                coupling_nnz=work.ring_coupling_nnz(32, 3))
    assert ring["flops_per_word"] == 7296
    assert (ring["i_dim"], ring["h_dim"]) == (96, 256)


def test_launch_bytes_by_hand():
    cls = _cls("chen")
    # 128 lanes x 4 rows: state in and out 2 * 3 * 2 B, offset 4 B per
    # lane; 4 B per word; weights (3*8 + 8 + 8*3 + 3) * 2 B once
    assert work.launch_bytes(cls, 128, 4) == (
        128 * (12 + 4) + 4 * 128 * 4 + 59 * 2)


SOLO = ('%chaotic_ann_bits_pallas.1 = (u32[32,512]{1,0:T(8,128)}, '
        'bf16[8,512]{1,0:T(8,128)(2,1)S(1)}) custom-call(bf16[8,16,1]'
        '{2,1,0:T(8,128)(2,1)S(1)} %pad.13, bf16[16,1]{1,0} %copy.3, '
        'bf16[16,8,1]{2,1,0} %pad.14, bf16[8,1]{1,0} %pad.15, '
        'bf16[8,512]{1,0} %pad.16, u32[1,512]{1,0} %bitcast.13), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
STACKED = ('%chaotic_ann_gang_stacked_pallas.1 = (u32[32,4,512]{2,1,0}, '
           'bf16[3,4,512]{2,1,0}) custom-call(bf16[3,8,4,1]{3,2,1,0} %copy.1, '
           'bf16[8,4,1]{2,1,0} %copy.2, bf16[8,3,4,1]{3,2,1,0} %copy.3, '
           'bf16[3,4,1]{2,1,0} %copy.4, bf16[3,4,512]{2,1,0} %bitcast.19, '
           'u32[4,512]{1,0} %copy-done.4), custom_call_target="tpu_custom_call"')


def test_call_work_from_kernel_names():
    classes = [_cls("chen"), _cls("hyperlorenz")]
    solo = work.parse_call(SOLO)
    assert solo["name"] == "chaotic_ann_bits_pallas"
    w = work.call_work(solo, classes)                 # 4-16-4 by its widths
    assert w["flops"] == 32 * 512 * 512
    assert w["bytes"] == work.launch_bytes(classes[1], 512, 32)
    st = work.call_work(work.parse_call(STACKED), classes)
    assert st["flops"] == 32 * 4 * 512 * 192
    assert st["bytes"] == work.launch_bytes(classes[0], 4 * 512, 32, 4)
    assert work.parse_call("%copy.1 = bf16[512,3] copy(bf16[512,3] %x)") is None
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert work.lower_bound_s(st, peaks) == pytest.approx(
        max(st["flops"] / 197e12, st["bytes"] / 819e9))

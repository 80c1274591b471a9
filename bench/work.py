"""The work a served oscillator launch requires, whatever implements it.

FLOPs: 2 per nonzero of ``w1``, ``w2`` and the coupling operator, per
oscillator step, 2 steps per word, counted from the core's own committed
weights, so a block-diagonal lattice counts its nonzeros and not the
dense product an implementation may multiply.  Bytes: the least HBM
traffic of one launch: the state read and written once, the per-lane word
offsets read, the words written, and the weights read once.

A kernel call in a device trace is named by its HLO text, which carries
its shapes; ``call_work`` reads the launch's words, lanes and members from
them and prices the call with the core class whose padded widths match.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional

import numpy as np

SUBLANES = 8
_SHAPE = re.compile(r"\b(u32|s32|bf16|f32)\[([0-9,]*)\]")


def _pad(n: int, m: int = SUBLANES) -> int:
    return -(-int(n) // m) * m


def core_class(w1, w2, *, dtype: str, n_nodes: int = 1,
               coupling_nnz: int = 0) -> Dict:
    """Per-word FLOPs and widths of one core from ONE node's weights."""
    w1, w2 = np.asarray(w1), np.asarray(w2)
    i_dim, h_dim = w1.shape
    per_step = 2 * (np.count_nonzero(w1) + np.count_nonzero(w2)) * n_nodes
    per_step += 2 * int(coupling_nnz)
    return {"i_dim": i_dim * n_nodes, "h_dim": h_dim * n_nodes,
            "flops_per_word": 2 * int(per_step),
            "state_bytes": 2 if dtype == "bfloat16" else 4}


def ring_coupling_nnz(n_nodes: int, base_dim: int) -> int:
    """Nonzeros of a ring's diffusive operator: self and two neighbours."""
    return 3 * n_nodes * base_dim


def launch_bytes(cls: Dict, lanes: int, rows: int, members: int = 1) -> int:
    """Least HBM bytes of one launch of ``lanes`` lanes for ``rows`` rows."""
    i, h, b = cls["i_dim"], cls["h_dim"], cls["state_bytes"]
    weights = (2 * i * h + h + i) * b * members
    return lanes * (2 * i * b + 4) + rows * lanes * 4 + weights


def parse_call(text: str) -> Optional[Dict]:
    """Name and shapes of a Pallas kernel call from its HLO text, or None
    for any other op."""
    if 'custom_call_target="tpu_custom_call"' not in text or " = " not in text:
        return None
    name, rest = text.split(" = ", 1)
    outs, _, operands = rest.partition(" custom-call(")
    out = [(d, [int(x) for x in s.split(",") if x])
           for d, s in _SHAPE.findall(outs)]
    ins = [(d, [int(x) for x in s.split(",") if x])
           for d, s in _SHAPE.findall(operands.split("), ")[0])]
    words = next((s for d, s in out if d == "u32"), None)
    w1 = next((s for d, s in ins if d in ("bf16", "f32")), None)
    if words is None or w1 is None:
        return None
    base = re.sub(r"\.\d+$", "", name.strip().lstrip("%"))
    return {"name": base, "words": words, "w1": w1}


def call_work(call: Dict, classes: List[Dict]) -> Dict:
    """FLOPs and least bytes of one parsed kernel call."""
    words, w1 = call["words"], call["w1"]
    if len(words) == 3:                        # stacked: (R, C, S), w1 (I, H, C, 1)
        rows, members, lanes = words[0], words[1], words[1] * words[2]
        want = (w1[0], w1[1])
        match = [c for c in classes if (c["i_dim"], c["h_dim"]) == want]
    else:                                      # solo or lane-concat: (R, S)
        rows, lanes = words
        members = w1[0] if len(w1) == 4 else 1
        want = tuple(w1[-3:-1]) if w1[-1] == 1 else tuple(w1[-2:])
        match = [c for c in classes
                 if (_pad(c["i_dim"]), _pad(c["h_dim"])) == want]
    match = list({(c["i_dim"], c["h_dim"], c["flops_per_word"],
                    c["state_bytes"]): c for c in match}.values())
    if len(match) != 1:
        raise ValueError(f"kernel {call['name']} with w1 {w1} matches "
                         f"{len(match)} core classes")
    cls = match[0]
    return {"flops": rows * lanes * cls["flops_per_word"],
            "bytes": launch_bytes(cls, lanes, rows, members)}


def lower_bound_s(work: Dict, peaks: Dict) -> float:
    """Least time of a call on a chip: the larger of its FLOPs over the
    peak FLOP/s and its bytes over the peak HBM bandwidth."""
    return max(work["flops"] / peaks["flops_per_s"],
               work["bytes"] / peaks["hbm_bytes_per_s"])

"""The audit passes the program and fails the lower-precision control
(the reference in float8 put in the program's place), at a size the
CPU's Pallas interpreter runs."""
from tiny import run_tiny, tiny_cell

from bench import serve


def test_program_passes_and_control_fails():
    cell = tiny_cell("farm5.bulk")
    result, checks, numbers = run_tiny(cell, 2 ** 31 + 77, 6.0,
                                       control=cell.config["control_precision"])
    assert result["correct"], checks
    assert numbers["launches_audited"] > 0
    ctl = numbers["control_checks"]
    assert not serve.passed(ctl)
    assert ctl["kernel_word_mismatch"]["value"] > 0
    assert ctl["state_mismatch_lanes"]["value"] > 0

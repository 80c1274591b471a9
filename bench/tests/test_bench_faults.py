"""A run whose timed path is broken underneath comes out not correct."""
import numpy as np
import pytest
from tiny import run_tiny, tiny_cell

from repro.kernels import ops


def _state_unchanged(words, state, x0):
    return words, x0


def _word_altered(words, state, x0):
    mid = words.shape[0] // 2
    return words.at[mid].set(words[mid] ^ 1), state


def _half_the_lanes(words, state, x0):
    return words.at[..., ::2].set(0), state


@pytest.mark.parametrize("fault,caught", [
    (_state_unchanged, "state_mismatch_lanes"),
    (_word_altered, "kernel_word_mismatch"),
    (_half_the_lanes, "kernel_word_mismatch")])
def test_a_broken_launch_is_not_correct(monkeypatch, fault, caught):
    for name in ("chaotic_bits", "chaotic_bits_gang",
                 "chaotic_bits_gang_stacked"):
        kernel = getattr(ops, name)

        def broken(params, x0, n_steps, *a, _kernel=kernel, **kw):
            words, state = _kernel(params, x0, n_steps, *a, **kw)
            return fault(words, state, x0)
        monkeypatch.setattr(ops, name, broken)
    result, checks, _ = run_tiny(tiny_cell("farm5.bulk"), 2 ** 31 + 5, 6.0)
    assert not result["correct"], checks
    assert checks["window_draws"]["value"] > 0
    assert checks[caught]["value"] > checks[caught]["limit"], checks


def test_a_word_altered_on_its_way_to_the_tenant_is_not_correct(monkeypatch):
    """The launch's words are right and its tenants receive others: the
    delivered words the audit keeps for its sampled launches catch it."""
    from repro.serve.prng_service import PRNGService
    absorb = PRNGService.absorb

    def altered(self, words, new_x, n_rows, **kw):
        if n_rows > 0:
            words = np.asarray(words) ^ np.uint32(1)
        return absorb(self, words, new_x, n_rows, **kw)
    monkeypatch.setattr(PRNGService, "absorb", altered)
    result, checks, _ = run_tiny(tiny_cell("farm5.bulk"), 2 ** 31 + 11, 6.0)
    assert not result["correct"], checks
    assert checks["kernel_word_mismatch"]["value"] == 0, checks
    assert checks["delivered_word_mismatch"]["value"] > 0, checks

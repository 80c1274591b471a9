"""Registry-wide PRNG quality gate (NIST subset per system AND dtype).

The paper (§II, citing Yu et al.) claims ANN-based chaotic PRNGs pass the
NIST SP 800-22 suite; PR 1 verified that for the trained Chen f32 stream
only.  This module sweeps the gate across the whole weight registry and
both serving dtypes (f32 cores and half-width bf16 cores), so the farm can
*quarantine* a (system, dtype) whose bit quality regresses — a registry
entry may train fine yet emit biased words after the bf16 mantissa fold.

Used from tests (tier-1 gate: every f32 system must pass) and from
``benchmarks/farm.py`` (quarantined systems are marked in
BENCH_farm.json so a serving rollout can exclude them).

Two gates live here:

* :func:`nist_gate` / :func:`sweep_registry` — the OFFLINE sweep: the
  full 7-test subset over ``GATE_WORDS`` freshly generated words per
  (system, dtype), run from CI;
* :func:`online_gate` — the ONLINE monitor: a cheap 3-test subset
  (monobit, block frequency, runs) over a rolling window of words a
  farm core actually *served*, cheap enough to run per flush on the
  serving executor.  ``repro.serve.health.HealthMonitor`` feeds it and
  turns verdicts into quarantine + core rotation.
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional

import jax.numpy as jnp
import numpy as np

from repro.prng.nist import (_to_bits, block_frequency, monobit, runs,
                             run_nist_subset)
from repro.prng.stream import ChaoticPRNG, default_params

GATE_WORDS = 30_000          # ~0.96 Mbit per gated stream
GATE_ALPHA = 0.01

# Online monitoring: one rolling window of served words per core.  4096
# words = 128 Kbit — enough that a poisoned stream hard-fails monobit in
# ONE window while a healthy stream's per-window soft-failure odds stay
# at the single-test alpha.
ONLINE_WINDOW_WORDS = 4096

# A single NIST test at alpha=0.01 has a ~1% false-positive rate; gating a
# whole registry on "zero failures anywhere" would flake.  A (system,
# dtype) is quarantined only when MORE tests fail than chance plausibly
# explains: >= 2 failed tests out of the 7-test subset (P[>=2 | p=0.01]
# ~ 2e-3 per stream), or any single test failing catastrophically
# (p-value below ALPHA_HARD, far outside false-positive territory).
ALPHA_HARD = 1e-6
MAX_CHANCE_FAILS = 1


def nist_gate(system: str, dtype: str = "float32", *,
              n_words: int = GATE_WORDS, alpha: float = GATE_ALPHA,
              n_streams: int = 256, seed: int = 0,
              backend: str = "auto") -> Dict[str, object]:
    """Run the NIST subset on one registry (system, dtype) stream.

    Draws through the same fused path the serving stack uses (``ChaoticPRNG``
    with the registry weights and the requested compute dtype), so the gate
    measures exactly what a farm core would emit.
    """
    params = default_params(system=system)
    eng = ChaoticPRNG(params, n_streams=n_streams, backend=backend,
                      dtype=jnp.dtype(dtype))
    words, _ = eng.next_words(eng.init(seed=seed), n_words)
    return {"system": system, "dtype": str(jnp.dtype(dtype)),
            **nist_verdict(words, alpha=alpha)}


def nist_verdict(words: np.ndarray, *,
                 alpha: float = GATE_ALPHA) -> Dict[str, object]:
    """The offline gate's verdict on words already in hand (for example,
    words a farm core served): the full NIST subset, ``quarantined`` when
    more tests fail than chance explains or any fails hard."""
    words = np.asarray(words, np.uint32).reshape(-1)
    res = run_nist_subset(words, alpha=alpha)
    failed = sorted(k for k, v in res.items() if not v["passed"])
    hard_failed = sorted(k for k, v in res.items()
                         if v["p_value"] < ALPHA_HARD)
    return {
        "n_words": int(words.size),
        "failed_tests": failed, "hard_failed_tests": hard_failed,
        "p_values": {k: v["p_value"] for k, v in res.items()},
        "passed": not failed,
        "quarantined": len(failed) > MAX_CHANCE_FAILS or bool(hard_failed),
    }


def online_gate(words: np.ndarray, *,
                alpha: float = GATE_ALPHA) -> Dict[str, object]:
    """Gate ONE rolling window of served words (the online monitor).

    Runs the cheap third of the NIST subset — monobit, block frequency,
    runs — over exactly the words given (no generation; the caller
    sampled them off a live core).  Returns the same verdict shape as
    :func:`nist_gate`: ``failed_tests`` are tests under ``alpha``
    (chance-plausible for a single window — the caller should demand
    consecutive failing windows before acting), ``hard_failed_tests``
    are tests under ``ALPHA_HARD`` (far outside false-positive
    territory: act immediately).
    """
    words = np.asarray(words, np.uint32).reshape(-1)
    if words.size == 0:
        raise ValueError("online_gate needs a non-empty word window")
    bits = _to_bits(words)
    p_values = {"monobit": monobit(bits),
                "block_frequency": block_frequency(bits),
                "runs": runs(bits)}
    failed = sorted(k for k, v in p_values.items() if v < alpha)
    hard_failed = sorted(k for k, v in p_values.items()
                         if v < ALPHA_HARD)
    return {"n_words": int(words.size), "p_values": p_values,
            "failed_tests": failed, "hard_failed_tests": hard_failed,
            "passed": not failed}


def sweep_registry(systems: Optional[Iterable[str]] = None,
                   dtypes: Iterable[str] = ("float32", "bfloat16"),
                   **gate_kw) -> Dict[str, Dict[str, object]]:
    """Gate every (system, dtype) pair; keys are '<system>/<dtype>'."""
    if systems is None:
        from repro.core.chaotic import SYSTEMS
        systems = sorted(SYSTEMS)
    return {f"{s}/{jnp.dtype(d)}": nist_gate(s, d, **gate_kw)
            for s in systems for d in dtypes}


def quarantined_systems(sweep: Dict[str, Dict[str, object]]) -> Dict[str, list]:
    """{system: [dtype, ...]} for every quarantined (system, dtype)."""
    out: Dict[str, list] = {}
    for res in sweep.values():
        if res["quarantined"]:
            out.setdefault(res["system"], []).append(res["dtype"])
    return out

"""The traffic generators reproduce from the seed and hit their mix."""
import json

import numpy as np
import pytest

from bench import spec

BIG_SEED = 2 ** 31 + 12345


def _mix(name):
    return json.loads((spec.BENCH / "traffic" / f"{name}.json").read_text())


def _gen(kind):
    return spec.load_module(spec.BENCH / "traffic" / f"{kind}.py",
                            f"bench_traffic_{kind}")


def test_open_zipf_reproduces_from_seed():
    gen, mix = _gen("open_zipf"), _mix("zipf_open")
    a, b = (gen.make(mix, 5, BIG_SEED, 10.0) for _ in range(2))
    c = gen.make(mix, 5, BIG_SEED + 1, 10.0)
    for f in ("due", "tenant", "words"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
        assert not np.array_equal(getattr(a, f), getattr(c, f))
    # another seed: the same work in another order
    assert np.array_equal(np.sort(a.words), np.sort(c.words))
    assert np.allclose(np.sort(np.diff(a.due)), np.sort(np.diff(c.due)))
    assert np.array_equal(np.sort(np.bincount(a.tenant)),
                          np.sort(np.bincount(c.tenant)))


def test_open_zipf_hits_its_parameters():
    gen, mix = _gen("open_zipf"), _mix("zipf_open")
    seconds = 10.0
    p = gen.make(mix, 5, BIG_SEED, seconds)
    total = mix["warmup_s"] + seconds
    assert len(p.due) == round(mix["rate_per_s"] * total)
    assert np.all(np.diff(p.due) > 0) and p.due[-1] == pytest.approx(total)
    lo, hi = mix["size_min_words"], mix["size_max_words"]
    assert p.words.min() == lo and hi - 2 <= p.words.max() <= hi
    # bounded Pareto mean, alpha 1.1 on [64, 4096]: about 242 words
    a = mix["pareto_alpha"]
    mean = (lo ** a / (1 - (lo / hi) ** a)) * a / (a - 1) * (
        lo ** (1 - a) - hi ** (1 - a))
    assert p.words.mean() == pytest.approx(mean, rel=0.01)
    # Zipf exponent from the counts of the 200 most popular tenants
    counts = np.sort(np.bincount(p.tenant))[::-1][:200]
    slope = np.polyfit(np.log(np.arange(1, 201)), np.log(counts), 1)[0]
    assert slope == pytest.approx(-mix["zipf_s"], abs=0.03)
    # each core's share of the draws is the same for every seed
    other = gen.make(mix, 5, BIG_SEED + 99, seconds)
    assert np.array_equal(np.bincount(p.tenant // mix["tenants_per_core"]),
                          np.bincount(other.tenant // mix["tenants_per_core"]))
    t0, t1 = p.window
    in_win = ((p.due >= t0) & (p.due < t1)).sum()
    assert in_win == pytest.approx(mix["rate_per_s"] * seconds, rel=0.01)


def test_closed_reproduces_from_seed():
    gen, mix = _gen("closed"), _mix("bulk")
    a, b = (gen.make(mix, 5, BIG_SEED + k, 10.0) for k in range(2))
    # every seed gets the same work in the same order
    assert np.array_equal(a.order, b.order)
    assert list(a.order) == list(range(5 * mix["tenants_per_core"]))
    assert a.words == mix["draw_words"] and a.outstanding == 2
    assert a.window == (mix["warmup_s"], mix["warmup_s"] + 10.0)

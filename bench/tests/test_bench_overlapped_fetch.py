"""The reader of ``overlapped_fetch_share.bulk``: the share of a window's
fetches that began under a later launch of the same flush."""
import pytest

from bench import spec


def _read(stages):
    return spec.reader("overlapped_fetch_share.bulk")({"stages": stages})


def test_two_fetches_one_overlapped_read_fifty():
    assert _read({"fetches": 2.0, "fetches_overlapped": 1.0}) == 50.0


@pytest.mark.parametrize("stages,share", [
    ({"fetches": 5.0, "fetches_overlapped": 4.0}, 80.0),
    ({"fetches": 3.0, "fetches_overlapped": 0.0}, 0.0)])
def test_the_share_is_overlapped_over_all_fetches(stages, share):
    assert _read(stages) == pytest.approx(share)


@pytest.mark.parametrize("stages", [
    {"fetches": 2.0},                          # the parent's program
    {"launch": 1.0, "flushes": 3.0},
    {"fetches": 0.0, "fetches_overlapped": 0.0}])
def test_nothing_is_read_without_the_counter_or_a_fetch(stages):
    assert _read(stages) is None

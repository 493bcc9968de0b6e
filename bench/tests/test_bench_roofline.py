"""Operation and byte counts of a matrix-powers firing, against a hand
count, and the roofline share they give."""

from __future__ import annotations

import pytest

from benchutil import ROOT  # noqa: F401  (puts the checkout on the path)
from bench import roofline

PEAKS = roofline.load_peaks("TPU v5 lite")


def test_counts_by_hand():
    # n=4, views A and P2 (levels=1), rank 1 at A, rank 2 at P2:
    # FLOPs  A: apply 2*16*1 + products 2*2*16*1;  P2: apply 2*16*2
    # bytes  A: 2*16*4 + factors 4*4*1*4;          P2: 2*16*4
    assert roofline.powers_firing_counts(4, 1, 1) == (32 + 64 + 64,
                                                      128 + 64 + 128)


@pytest.mark.parametrize("levels,rank", [(0, 1), (2, 3), (4, 64)])
def test_counts_grow_with_rank(levels, rank):
    f1, b1 = roofline.powers_firing_counts(256, levels, rank)
    f2, b2 = roofline.powers_firing_counts(256, levels, rank + 1)
    assert f2 > f1 and b2 >= b1


def test_least_time_names_its_bound():
    t, bound = roofline.least_time_s(*roofline.powers_firing_counts(
        16384, 4, 1), PEAKS)
    assert bound == "bandwidth"
    assert t == pytest.approx(10 * 16384 ** 2 * 4 / 819e9, rel=0.01)
    _, bound = roofline.least_time_s(1e15, 1.0, PEAKS)
    assert bound == "compute"


def _share(ranks, busy):
    from bench.harness import load_module
    reader = load_module(ROOT / "bench/metrics/firing_roofline_pct.py",
                         "bench.metrics.firing_roofline_pct")
    rec = {"cfg": {"program": "matrix_powers", "n": 16384, "k": 16},
           "trace": {"busy_in_spans_s": busy}, "device_kind": "TPU v5 lite",
           "window": {"firing_ranks": ranks}}
    return reader.read(rec)


@pytest.mark.parametrize("ranks", [[1] * 5, [43, 50, 38], [1, 64]])
def test_share_at_its_own_bound_is_100(ranks):
    least = sum(roofline.least_time_s(
        *roofline.powers_firing_counts(16384, 4, r), PEAKS)[0] for r in ranks)
    assert _share(ranks, least) == pytest.approx(100.0)
    for slower in (1.0001, 1.5, 10.0):
        assert _share(ranks, least * slower) < 100.0


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.load_peaks("TPU v9 imaginary")

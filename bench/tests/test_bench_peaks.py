"""The peaks table is keyed by device kind; an unknown kind is an error."""

import pytest

from bench.peaks import PEAKS, peaks_for


def test_v5e_peaks_are_the_published_ones():
    p = peaks_for("TPU v5 lite")
    assert (p.bf16_flops, p.int8_ops, p.hbm_bytes) == (197e12, 393e12, 819e9)


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "", "tpu v5 lite"])
def test_unknown_kind_raises(kind):
    assert kind not in PEAKS
    with pytest.raises(KeyError):
        peaks_for(kind)

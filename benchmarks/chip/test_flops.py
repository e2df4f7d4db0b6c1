"""Operation counts from shapes, and the table of peaks."""

import pytest

from benchmarks.chip import flops


def test_sage_flops_hand_count():
    # batch 2, fanouts (3, 2), widths 4 → 5 → 6 classes:
    # layer 0 updates depths 0 and 1: (2 + 6) rows · 2 · (2·4) · 5 = 640
    # layer 1 updates depth 0: 2 rows · 2 · (2·5) · 5 = 200
    # output: 2 · 2 · 5 · 6 = 120
    assert flops.sage_forward_flops(2, (3, 2), 4, 5, 6) == 960
    assert flops.sage_train_flops(2, (3, 2), 4, 5, 6) == 3 * 960


def test_sage_flops_cell_shape():
    # the sage_train cell: batch 1024, fanouts 15/10/5, 100 → 256 → 47
    f = flops.sage_forward_flops(1024, (15, 10, 5), 100, 256, 47)
    rows = [1024, 15360, 153600]
    want = (sum(rows) * 2 * 200 * 256 + sum(rows[:2]) * 2 * 512 * 256
            + rows[0] * 2 * 512 * 256 + 2 * 1024 * 256 * 47)
    assert f == want


def test_gather_bytes_hand_count():
    # batch 2, fanouts (3, 2): 2 + 6 + 12 rows of 5 float32
    assert flops.sage_gather_bytes(2, (3, 2), 5) == 20 * 5 * 4
    # the sage_train cell: 1024·(1 + 15 + 150 + 750) rows of 100 float32
    assert flops.sage_gather_bytes(1024, (15, 10, 5), 100) == 375_193_600


def test_peak_of_v5e():
    assert flops.peak("TPU v5 lite") == 197e12
    assert flops.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "source"])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        flops.peak(kind)


def test_grape_superstep_bytes():
    # 3 arcs · (4 B target + 4 B value) + 2 vertices · (4 B read + 4 B write)
    assert flops.grape_superstep_bytes(2, 3) == 40
    # graph500_22 on four chips: 2^22 vertices, 128,311,468 arcs
    assert flops.grape_superstep_bytes(2 ** 22, 128_311_468) == \
        8 * 128_311_468 + 8 * 2 ** 22

"""The trace reduction: device busy time, idle share and the idle gaps'
attribution, on a hand-made trace and on one recorded on the chip."""

import gzip
import json
import os
from types import SimpleNamespace as NS

import pytest

from benchmarks.chip import trace

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "testdata", "v5e_train_trace.json.gz")


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=e, start_ns=s, duration_ns=d)
                            for e, s, d in evs])
        for ln, evs in lines.items()])


def test_union():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_reduce_hand_made():
    host = _plane("/host:CPU", {"python": [
        ("bench.window", 1000, 10000),          # window [1000, 11000)
        ("bench.submit", 2000, 1000),
        ("bench.wait", 6000, 5000)]})
    dev = _plane("/device:TPU:0", {
        "XLA Ops": [("fusion.1", 0, 1500),      # clipped to [1000, 1500)
                    ("scatter", 3000, 2000),
                    ("fusion.1", 4000, 2000),   # overlaps: union [3000, 6000)
                    ("fusion.2", 10500, 1000)],  # clipped to [10500, 11000)
        "XLA Modules": [("jit_fixpoint", 0, 20000)]})
    out = trace.reduce_planes([host, dev])
    assert out["window_s"] == pytest.approx(10000e-9)
    assert out["busy_s"] == pytest.approx((500 + 3000 + 500) * 1e-9)
    ops = dict(out["device_ops"])
    assert ops["fusion.1"] == pytest.approx(2500e-9)
    assert ops["scatter"] == pytest.approx(2000e-9)
    assert out["op_s"] == pytest.approx({"fusion.1": 2500e-9,
                                         "scatter": 2000e-9,
                                         "fusion.2": 500e-9})
    assert out["op_text"]["fusion.1"] == "fusion.1"
    gaps = dict(out["idle_gaps"])
    # [1500, 3000) mid 2250 in submit; [6000, 10500) mid 8250 in wait
    assert gaps == pytest.approx({"bench.submit": 1500e-9,
                                  "bench.wait": 4500e-9})


def test_no_window_or_no_device():
    dev = _plane("/device:TPU:0", {"XLA Ops": [("f", 0, 10)]})
    host = _plane("/host:CPU", {"python": [("bench.window", 0, 100)]})
    assert trace.reduce_planes([dev]) is None
    assert trace.reduce_planes([host]) is None


def test_recorded_on_the_chip():
    """snb_sf1_3label.sage_train on one TPU v5 lite: the first 0.1 s of
    its traced window, the device's op line and the benchmark's spans."""
    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    planes = [_plane(p["name"], {ln["name"]: ln["events"]
                                 for ln in p["lines"]})
              for p in rec["planes"]]
    out = trace.reduce_planes(planes)
    assert out["busy_s"] == pytest.approx(rec["busy_s"])
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["window_s"] == pytest.approx(rec["window_s"])
    assert [op for op, _ in out["device_ops"]] == rec["top_ops"]
    assert out["device_ops"][0][0] == "fusion.10"     # HLO text cut to a name
    # the whole HLO text is kept: the gathers name the feature table
    reads_table = sorted(n for n, t in out["op_text"].items()
                         if "f32[875001,100]" in t)
    assert reads_table == ["copy.50", "fusion.10", "fusion.11", "fusion.12",
                           "fusion.8"]
    assert sum(out["op_s"].values()) >= out["busy_s"]
    assert [g for g, _ in out["idle_gaps"]] == ["bench.step"]

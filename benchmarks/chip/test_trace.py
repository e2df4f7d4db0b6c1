"""The trace reduction: device busy time, idle share and the idle gaps'
attribution, on a hand-made trace and on one recorded on the chip."""

import gzip
import json
import os
from types import SimpleNamespace as NS

import numpy as np
import pytest

from benchmarks.chip import trace
from benchmarks.chip.harness import metric_reader

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


# Two chips, two supersteps each in a window [0, 10000), inside one loop
# (the while op, whose event spans its body's): a scatter over the
# fragment's 6 arcs, the exchange of the 8-vertex buffer (the second
# chip's is asynchronous: a start and a done), the update, and the
# residual's scalar exchange, which is no superstep
SCATTER = "%fusion.1 = f32[8]{0} fusion(s32[1,6]{1,0} %p0, f32[6]{0} %p1)"
UPDATE = "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p0)"
SYNC = "%all-reduce.2 = f32[8]{0} all-reduce(f32[8]{0} %fusion.1)"
START = "%all-reduce-start.2 = f32[8]{0} all-reduce-start(f32[8]{0} %x)"
DONE = "%all-reduce-done.2 = f32[8]{0} all-reduce-done(f32[8]{0} %y)"
RESIDUAL = "%all-reduce.7 = f32[]{:T(128)} all-reduce(f32[]{:T(128)} %r)"
LOOP = ("%while.5 = (f32[8]{0:T(1024)}, s32[1,6]{1,0:T(1,128)}) "
        "while((f32[8]{0}, s32[1,6]{1,0}) %t), condition=%c, body=%b")
GRAPE = [
    _plane("/host:CPU", {"python": [("bench.window", 0, 10000),
                                    ("bench.bfs", 0, 10000)]}),
    _plane("/device:TPU:0", {"XLA Ops": [
        (LOOP, 0, 7100),
        (SCATTER, 0, 2000), (SYNC, 2000, 500), (UPDATE, 2500, 500),
        (RESIDUAL, 3000, 100),
        (SCATTER, 4000, 2000), (SYNC, 6000, 500), (UPDATE, 6500, 500),
        (RESIDUAL, 7000, 100)]}),
    _plane("/device:TPU:1", {"XLA Ops": [
        (LOOP, 0, 7100),
        (SCATTER, 0, 2400), (START, 2400, 100), (DONE, 2500, 300),
        (UPDATE, 2800, 200), (RESIDUAL, 3000, 100),
        (SCATTER, 4000, 2400), (START, 6400, 100), (DONE, 6500, 300),
        (UPDATE, 6800, 200), (RESIDUAL, 7000, 100)]}),
]


def test_opcode():
    assert trace.opcode(SCATTER) == "fusion"
    assert trace.opcode(DONE) == "all-reduce-done"
    assert trace.opcode(LOOP) == "while"


def test_collectives_count_supersteps():
    out = trace.reduce_planes(GRAPE)
    assert out["op_n"] == {"fusion.1": 2, "all-reduce.2": 1,
                           "all-reduce-start.2": 1, "all-reduce-done.2": 1,
                           "fusion.3": 2, "all-reduce.7": 2, "while.5": 1}
    # every exchange, a device: (2 · 500 + 2 · 100 + 2 · (100 + 300)
    # + 2 · 100) / 2 ns, four calls
    secs, calls = trace.collectives(out)
    assert secs == pytest.approx(1100e-9)
    assert calls == 4
    # the buffer's exchange alone: one call a superstep
    secs, calls = trace.collectives(out, "[8]")
    assert secs == pytest.approx(900e-9)
    assert calls == 2


@pytest.mark.parametrize("metric, want", [
    # 2 supersteps · 6 arcs · 8 B over 4400 ns a chip at 819 GB/s (the
    # loop's own event is not the scatter's)
    ("scatter_roofline", 100 * 2 * 6 * 8 / 4400e-9 / 819e9),
    # 2 · (8 · 12 + 8 · 8) B over 10000 ns and 2 chips' 819 GB/s
    ("superstep_hbm_share", 100 * 2 * 160 / 10000e-9 / (2 * 819e9)),
    # 1100 of 7100 busy ns a chip
    ("exchange_share", 1100 / 7100),
    ("device_idle_share.algo", 1 - 7100 / 10000),
])
def test_grape_readers(metric, want):
    run = NS(trace_summary=trace.reduce_planes(GRAPE),
             dataset={"n": 8, "indices": np.zeros(12, np.int32)},
             cell=NS(chips=2), device_kind="TPU v5 lite")
    reader, suffix = metric_reader(metric)
    assert reader.read(run, suffix) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["scatter_roofline", "superstep_hbm_share",
                                    "exchange_share"])
def test_grape_readers_find_nothing(metric):
    """A trace with no exchange and no op of the fragment's length: the
    readers return nothing, not 0."""
    planes = [GRAPE[0], _plane("/device:TPU:0", {"XLA Ops": [
        (UPDATE, 0, 500)]})]
    run = NS(trace_summary=trace.reduce_planes(planes),
             dataset={"n": 8, "indices": np.zeros(12, np.int32)},
             cell=NS(chips=2), device_kind="TPU v5 lite")
    reader, suffix = metric_reader(metric)
    assert reader.read(run, suffix) is None

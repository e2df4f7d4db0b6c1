"""The program's names in a trace: each op's scope path read from the
serialized trace's event metadata, device seconds by scope, host seconds
and counts by ``flex.*`` span, and idle gaps labelled by the innermost
``bench.*`` or ``flex.*`` span, on hand-made traces."""

import gzip
import json
import os
from types import SimpleNamespace as NS

import pytest

from benchmarks.chip import scopes, trace


def _xspace(planes):
    """A serialized XSpace from ``{plane: {line: [(name, start_ns,
    duration_ns[, op_name path])]}}``. Paths go in as the ``tf_op`` stat
    of each event's metadata, every other one interned as a reference,
    as a TPU trace may hold them."""
    from jax.profiler import ProfileData

    out = []
    for pid, (pname, lines) in enumerate(planes.items(), 1):
        meta, stat_meta = {}, {"tf_op": 1}
        body = [f"id: {pid} name: {json.dumps(pname)}"]
        for lid, (lname, events) in enumerate(lines.items(), 1):
            evs = []
            for e in events:
                mid = meta.setdefault(e[0], (len(meta) + 1, e[3:]))[0]
                evs.append(f"events {{ metadata_id: {mid} offset_ps: "
                           f"{e[1] * 1000} duration_ps: {e[2] * 1000} }}")
            body.append(f"lines {{ id: {lid} name: {json.dumps(lname)} "
                        f"timestamp_ns: 0 {' '.join(evs)} }}")
        for name, (mid, path) in meta.items():
            stat = ""
            if path and mid % 2:
                stat = (f"stats {{ metadata_id: 1 "
                        f"str_value: {json.dumps(path[0])} }}")
            elif path:
                ref = stat_meta.setdefault(path[0], len(stat_meta) + 1)
                stat = f"stats {{ metadata_id: 1 ref_value: {ref} }}"
            body.append(f"event_metadata {{ key: {mid} value {{ id: {mid} "
                        f"name: {json.dumps(name)} {stat} }} }}")
        for name, sid in stat_meta.items():
            body.append(f"stat_metadata {{ key: {sid} value {{ id: {sid} "
                        f"name: {json.dumps(name)} }} }}")
        out.append(f"planes {{ {' '.join(body)} }}")
    return ProfileData.text_proto_to_serialized_xspace(" ".join(out))


RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "testdata", "v5e_sage_scopes_trace.json.gz")

HOP = "jit(s)/sample.hop0/jit(_take)/gather:"
FEAT = "jit(s)/gather.features/jit(_take)/gather:"
FWD = "jit(s)/model.fwd_bwd/transpose(jvp())/dot_general:"

# a window [1000, 11000) holding two steps; the device runs a hop, a
# gather and the model in each, and one op whose path has no scope
STEP_TRACE = {
    "/host:CPU": {
        "python": [("bench.window", 1000, 10000),
                   ("bench.step", 1000, 5000),
                   ("flex.learning.step", 1100, 4800),
                   ("flex.learning.seeds", 1100, 400),     # [1100, 1500)
                   ("flex.learning.dispatch", 1500, 300),  # [1500, 1800)
                   ("flex.learning.loss_wait", 1800, 4000),
                   ("bench.step", 6000, 5000),
                   ("flex.learning.step", 6100, 4800),
                   ("flex.learning.seeds", 6100, 400),
                   ("flex.learning.dispatch", 6500, 300),
                   ("flex.learning.loss_wait", 6800, 4000),
                   ("flex.learning.step", 12000, 500)],    # outside
        "other": [("flex.learning.seeds", 0, 500)]},       # outside
    "/device:TPU:0": {
        "XLA Ops": [
            ("%fusion.9 = s32[8] fusion()", 0, 2000, HOP),  # clipped: 1000
            ("%fusion.10 = f32[8,2] fusion()", 2000, 1000, FEAT),
            ("%fusion.3 = f32[2] fusion()", 3000, 1000, FWD),
            ("%copy.50 = f32[9,2] copy()", 4000, 500, "tables['feats']:"),
            ("%fusion.9 = s32[8] fusion()", 7000, 1000, HOP),
            ("%fusion.10 = f32[8,2] fusion()", 8000, 500, FEAT),
            ("%fusion.3 = f32[2] fusion()", 8500, 500, FWD),
            ("%fusion.3 = f32[2] fusion()", 10500, 1000, FWD)],  # 500 in
        "XLA Modules": [("jit_s", 0, 20000)]},
}


@pytest.fixture
def step_trace_dir(tmp_path):
    where = tmp_path / "plugins" / "profile" / "1"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(_xspace(STEP_TRACE))
    return str(tmp_path)


@pytest.mark.parametrize("path, scope", [
    ("jit(_device_step_fn)/sample.hop0/jit(_take)/gather", "sample"),
    ("jit(_device_step_fn)/sample.hop12/mul:", "sample"),
    ("jit(_device_step_fn)/gather.features/jit(_take)/gather:", "gather"),
    ("jit(f)/model.fwd_bwd/transpose(jvp())/dot_general", "model"),
    ("jit(f)/model.update/sub", "model"),
    ("jit(f)/outer.a/model.update/sub", "outer"),      # the first scope
    ("jit(_device_step_fn)/jit(_threefry_fold_in)/threefry2x32", "unscoped"),
    ("tables['feats']:", "unscoped"),
    ("", "unscoped"),
])
def test_scope_of(path, scope):
    assert scopes.scope_of(path) == scope


def test_op_paths_read_the_event_metadata():
    paths = scopes.op_paths(_xspace(STEP_TRACE))
    assert list(paths) == ["/device:TPU:0"]          # device planes only
    assert paths["/device:TPU:0"] == {
        "%fusion.9 = s32[8] fusion()": HOP,
        "%fusion.10 = f32[8,2] fusion()": FEAT,
        "%fusion.3 = f32[2] fusion()": FWD,
        "%copy.50 = f32[9,2] copy()": "tables['feats']:"}


def test_reduce_hand_made(step_trace_dir):
    out = scopes.reduce(step_trace_dir)
    assert out["scope_s"] == pytest.approx({
        "sample": 2000e-9, "gather": 1500e-9, "model": 2000e-9,
        "unscoped": 500e-9})
    assert out["unscoped_ops"] == [["copy.50", pytest.approx(500e-9)]]
    assert out["span_s"] == pytest.approx({
        "flex.learning.step": 9600e-9, "flex.learning.seeds": 800e-9,
        "flex.learning.dispatch": 600e-9,
        "flex.learning.loss_wait": 8000e-9})
    assert out["span_n"] == {"flex.learning.step": 2,
                             "flex.learning.seeds": 2,
                             "flex.learning.dispatch": 2,
                             "flex.learning.loss_wait": 2}
    # the gap [4500, 7000) runs from step 1's loss wait through the end
    # of step 1 and the start of step 2 into step 2's loss wait; the gap
    # [9000, 10500) lies in step 2's loss wait. trace files both whole
    # under bench.step
    assert dict(out["idle_gaps"]) == pytest.approx({
        "flex.learning.loss_wait": (1300 + 200 + 1500) * 1e-9,
        "flex.learning.step": 100e-9,       # [5800, 5900)
        "bench.step": 200e-9,               # [5900, 6100)
        "flex.learning.seeds": 400e-9,      # [6100, 6500)
        "flex.learning.dispatch": 300e-9})  # [6500, 6800)


def test_reduce_agrees_with_trace(step_trace_dir):
    """Device seconds by scope add up to the op seconds ``trace`` counts,
    and the gaps to its idle time; ``trace`` itself still files the gaps
    under ``bench.step``."""
    out, base = scopes.reduce(step_trace_dir), trace.reduce(step_trace_dir)
    assert sum(out["scope_s"].values()) == pytest.approx(
        sum(base["op_s"].values()))
    assert sum(s for _, s in out["idle_gaps"]) == pytest.approx(
        base["window_s"] - base["busy_s"])
    assert [g for g, _ in base["idle_gaps"]] == ["bench.step"]


def test_gap_labels_innermost_and_unattributed():
    spans = [(0, 100, "bench.step"), (10, 20, "flex.learning.seeds"),
             (15, 18, "flex.learning.x"), (200, 300, "bench.step")]
    times = [5, 12, 16, 19, 150, 250]
    assert scopes.label_times(times, spans) == [
        "bench.step", "flex.learning.seeds", "flex.learning.x",
        "flex.learning.seeds", "unattributed", "bench.step"]
    # the same answer as trace's own labelling, one time at a time
    assert scopes.label_times(times, spans) == [
        trace._label(t, spans) for t in times]


def test_no_window_or_no_device(tmp_path):
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        NS(name="f", start_ns=0, duration_ns=10)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        NS(name="bench.window", start_ns=0, duration_ns=100)])])
    assert scopes.reduce_planes([dev], {}) is None
    assert scopes.reduce_planes([host], {}) is None
    assert scopes.reduce(str(tmp_path)) is None      # no trace file
    assert not os.listdir(tmp_path)


def test_recorded_on_the_chip():
    """ogbn_products.sage_train on one TPU v5 lite: the first 0.1 s of its
    traced window, every device op with its ``tf_op`` path as the trace's
    event metadata held it, and the benchmark's and the program's spans."""
    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    planes = [NS(name=p["name"], lines=[
        NS(name=ln["name"], events=[NS(name=e, start_ns=s, duration_ns=d)
                                    for e, s, d in ln["events"]])
        for ln in p["lines"]]) for p in rec["planes"]]
    out = scopes.reduce_planes(planes, rec["op_paths"])
    base = trace.reduce_planes(planes)
    assert base["busy_s"] == pytest.approx(rec["busy_s"])
    assert base["window_s"] == pytest.approx(rec["window_s"])
    assert [op for op, _ in base["device_ops"]] == rec["top_ops"]
    assert out["scope_s"] == pytest.approx(rec["scope_s"])
    assert sum(out["scope_s"].values()) == pytest.approx(
        sum(base["op_s"].values()))
    # the sampler leads, then the gathers, then the model; the one large
    # op with no scope is the per-step layout copy of the feature table,
    # which carries the argument's name
    assert sorted(out["scope_s"], key=out["scope_s"].get, reverse=True) == [
        "sample", "gather", "model", "unscoped"]
    assert out["unscoped_ops"][0][0] == "copy.50"
    assert rec["op_paths"]["/device:TPU:0"][base["op_text"]["copy.50"]] == (
        "tables['feats']:")
    assert out["span_s"] == pytest.approx(rec["span_s"])
    assert out["span_n"] == rec["span_n"]
    assert set(out["span_n"]) == {
        "flex.learning.step", "flex.learning.seeds",
        "flex.learning.dispatch", "flex.learning.loss_wait"}
    assert [g for g, _ in out["idle_gaps"]] == rec["idle_gaps"]
    assert sum(s for _, s in out["idle_gaps"]) == pytest.approx(
        base["window_s"] - base["busy_s"])
    # what trace files under bench.step goes to the program's spans
    flex = sum(s for g, s in out["idle_gaps"] if g.startswith("flex."))
    assert flex >= 0.9 * dict(base["idle_gaps"])["bench.step"]


def test_trace_reduce_carries_the_scopes(step_trace_dir):
    """``trace.reduce``'s summary, all a metric reader sees, holds the
    seconds and op events by scope and the program's spans; its idle gaps
    stay its own."""
    out, named = trace.reduce(step_trace_dir), scopes.reduce(step_trace_dir)
    for key in ("scope_s", "unscoped_ops", "span_s", "span_n"):
        assert out[key] == named[key]
    # fusion.9 twice, fusion.10 twice, fusion.3 three times (the last one
    # half in the window), copy.50 once
    assert out["scope_n"] == named["scope_n"] == {
        "sample": 2, "gather": 2, "model": 3, "unscoped": 1}
    assert [g for g, _ in out["idle_gaps"]] == ["bench.step"]

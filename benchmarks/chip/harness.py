"""One run of one cell: build the configuration from the seed, warm up,
drive the traffic for ``--seconds``, check the answers against the plain
reference, and print one JSON line.

Everything cell-specific is found by name: the cell in ``BENCHMARK.json``
names a configuration (``configs/<name>.json``, whose ``generator``
names ``data/<generator>.py``) and a traffic mix (``traffic/<name>.json``,
whose ``runner`` and ``check`` name ``runners/<runner>.py`` and
``checks/<check>.py``); each metric of the cell is read by
``metrics/<metric>.py``, or by ``metrics/<prefix>.py`` given the part of
the name after the first dot.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


class NoChip(RuntimeError):
    """The cell's chips are not there: the run refuses to report."""


# ------------------------------------------------------------ files by name
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, here: str = HERE):
    """``<kind>/<name>.py`` under the benchmark, imported by its path."""
    path = os.path.join(here, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    mod_name = f"benchmarks.chip.{kind}.{name.replace('.', '_')}"
    mod = sys.modules.get(mod_name)
    if mod is None:
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, here: str = HERE):
    """(reader module, suffix): ``metrics/<name>.py`` when it exists,
    else ``metrics/<prefix>.py`` reading the name's part after the dot."""
    if os.path.exists(os.path.join(here, "metrics", f"{name}.py")):
        return load_module("metrics", name, here), None
    prefix, _, suffix = name.partition(".")
    return load_module("metrics", prefix, here), suffix or None


def seed_key(seed: int):
    """A JAX key from any non-negative seed, 64 bits and wider included."""
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[str]
    per_layer: List[str]
    units: Dict[str, str]
    here: str                    # the benchmark's directory in this checkout


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    here = os.path.join(root, bench["paths"][0])
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(os.path.join(root, cfg_entry["file"]))
    mix = load_json(os.path.join(here, "traffic", f"{w['traffic']}.json"))
    e2e = [m["name"] for m in bench["end_to_end"]
           if _applies(m, name)]
    per_layer = [m["name"] for m in bench["per_layer"]
                 if _applies(m, name)]
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    return Cell(name, int(w["chips"]), config, mix, e2e, per_layer, units,
                here)


# ------------------------------------------------------------ compile count
class CompileCounter:
    """Programs this process compiled. JAX reports every compile request
    (``backend_compile_duration``), persistent-cache loads among them;
    a compile is a request the cache did not answer. One listener per
    process: JAX keeps listeners globally."""

    _instance: Optional["CompileCounter"] = None
    REQUEST = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.requests = 0
        self.hits = 0
        self._lock = threading.Lock()

    @property
    def compiles(self) -> int:
        return self.requests - self.hits

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            import jax

            cls._instance = c = cls()
            jax.monitoring.register_event_duration_secs_listener(
                c._on_duration)
            jax.monitoring.register_event_listener(c._on_event)
        return cls._instance

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == self.REQUEST:
            with self._lock:
                self.requests += 1

    def _on_event(self, event: str, **_) -> None:
        if event == self.HIT:
            with self._lock:
                self.hits += 1


# ---------------------------------------------------------------- the run
@dataclasses.dataclass
class Run:
    """What one run knows; runners, checks and metric readers read it."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    control: bool
    log: Any
    dataset: Optional[dict] = None
    window_s: float = 0.0
    setup_s: float = 0.0
    trace_summary: Optional[dict] = None
    device_kind: str = ""
    attempted: int = 0
    failed: int = 0
    checks: List[tuple] = dataclasses.field(default_factory=list)
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # the program's objects that the window drives (the trainer)
    program: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def say(self, msg: str) -> None:
        print(msg, file=self.log, flush=True)

    def free_program(self) -> None:
        """Drop the program's state before the reference runs, so that
        the reference neither shares the device with it nor sets the
        peak."""
        self.program.clear()
        gc.collect()


def _device(chips: int, require_chip: bool):
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_chip and (dev.platform != "tpu" or len(devices) < chips):
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {dev.platform} device(s)")
    return devices[:chips]


def _peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def _finite(x) -> Optional[float]:
    return None if x is None or not math.isfinite(x) else float(x)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t0: Optional[float] = None, root: str = ROOT,
             require_chip: bool = True, control: bool = False,
             graph: Optional[dict] = None, mix: Optional[dict] = None,
             log=None) -> dict:
    """One run; returns the result object (the last stdout line).
    ``graph`` and ``mix`` override keys of the configuration's graph and
    of the traffic mix (CPU rehearsals only: the CLI passes neither)."""
    t0 = time.time() if t0 is None else t0
    log = log or sys.stderr
    cell = load_cell(name, root)
    if graph:
        cell.config = {**cell.config, "graph": {**cell.config["graph"],
                                                **graph}}
    if mix:
        cell.mix = {**cell.mix, **mix}
    import jax

    devices = _device(cell.chips, require_chip)
    from repro.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    # every program goes to the persistent cache, however fast it compiled:
    # a later run then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = CompileCounter.get()
    run = Run(cell, seed, seconds, trace, control, log,
              device_kind=devices[0].device_kind)
    run.say(f"cell {name}: seed {seed}, {seconds} s, trace {int(trace)}, "
            f"devices {devices}, compile cache {cache_dir}")

    gen = load_module("data", cell.config["generator"], cell.here)
    run.dataset = gen.generate(cell.config["graph"], seed)
    run.say(f"set-up: data drawn {time.time() - t0!r} s after start")
    runner = load_module("runners", cell.mix["runner"], cell.here)
    runner.setup(run)
    c0, h0 = compiles.compiles, compiles.hits
    run.setup_s = time.time() - t0
    run.say(f"set-up {run.setup_s!r} s: {c0} compiles, {h0} programs "
            f"loaded from the persistent cache")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            runner.window(run)
    finally:
        if trace:
            jax.profiler.stop_trace()
    n_window = compiles.compiles - c0
    loads = compiles.hits - h0
    peak = _peak_bytes(devices)
    if trace:
        from benchmarks.chip import trace as tr

        run.trace_summary = tr.reduce(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    line = (f"compiles in the window: {n_window} ({loads} programs loaded "
            f"from the persistent cache)")
    print(line, flush=True)
    run.say(line)

    load_module("checks", cell.mix["check"], cell.here).check(run)
    run.free_program()

    metrics: Dict[str, dict] = {}
    for mname in (cell.per_layer if trace else cell.end_to_end):
        reader, suffix = metric_reader(mname, cell.here)
        value = _finite(reader.read(run, suffix))
        if value is not None:
            metrics[mname] = {"value": value, "unit": cell.units[mname]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result: Dict[str, Any] = {
        "correct": all(v <= lim for _, v, lim in run.checks)
        and bool(run.checks),
        "attempted": run.attempted, "failed": run.failed,
        "metrics": metrics, "device": device}
    if trace and run.trace_summary is not None:
        device["busy_s"] = run.trace_summary["busy_s"]
        device["window_s"] = run.trace_summary["window_s"]
        result["breakdown"] = {
            "device_ops": run.trace_summary["device_ops"],
            "idle_gaps": run.trace_summary["idle_gaps"]}
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in run.checks}
    for n, v, lim in run.checks:
        run.say(f"check {n}: {v!r} (limit {lim!r})")
    return result


def main(argv=None, t0: Optional[float] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="put the lower-precision reference in the "
                         "program's place: the check must fail")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t0=t0, control=args.control)
    except NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0

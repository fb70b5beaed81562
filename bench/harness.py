"""One run of one benchmark cell, driven by the names in ``BENCHMARK.json``.

The cell names a configuration (``configs/<config>.json``) and a traffic
mix (``traffic/<traffic>.json``); the mix names the driver that runs it
(``drivers/<kind>.py``) and holds its parameters. Each per-layer metric is
read by ``metrics/<name>.py``. Adding any of these is adding files and
entries, never editing this one.

A run: check the device, set up (data, weights, warm-up of every shape the
window uses), measure for ``--seconds``, then check what the timed path
produced against the plain reference. The last line of standard output is
one JSON object; the numbers compared are also printed, each beside its
limit, as the last lines of standard error.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import importlib.util
import json
import math
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
JAX_CACHE = BENCH / ".cache" / "jax"


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def read_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def cell_spec(workload: str) -> Tuple[dict, dict, dict]:
    """(benchmark, cell, configuration) for a cell name."""
    spec = read_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    return spec, cell, read_json(BENCH / "configs" / f"{cell['config']}.json")


class CompileMeter:
    """Backend compile seconds and compile events, from JAX's monitoring
    events (a persistent-cache hit's retrieval counts as its compile).
    One per process (``meter()``): JAX keeps its listeners for good."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


_METER: List[CompileMeter] = []


def meter() -> CompileMeter:
    import jax
    if not _METER:
        _METER.append(CompileMeter(jax))
    return _METER[0]


class Spans:
    """Host spans: a ``jax.profiler.TraceAnnotation`` (so a traced run has
    them on the trace's clock) and an in-memory record (name, start, end)
    in ``time.perf_counter`` seconds, kept for the host-side readers."""

    def __init__(self):
        import jax
        self._annotation = jax.profiler.TraceAnnotation
        self._lock = threading.Lock()
        self.records: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        with self._annotation(name):
            yield
        t1 = time.perf_counter()
        with self._lock:
            self.records.append((name, t0, t1))

    def total(self, name: str, lo: float = -math.inf,
              hi: float = math.inf) -> Tuple[float, int]:
        """(seconds, count) of ``name`` spans that started in [lo, hi)."""
        with self._lock:
            sel = [b - a for n, a, b in self.records
                   if n == name and lo <= a < hi]
        return sum(sel), len(sel)


@dataclass
class Run:
    """What a driver gets, and what it leaves for the metric readers."""
    args: Any
    spec: dict
    cell: dict
    config: dict
    traffic: dict
    devices: list
    spans: Spans
    meter: CompileMeter
    t_start: float
    rehearsal: bool
    setup_s: float = 0.0
    compile_s_setup: float = 0.0
    window: Tuple[float, float] = (0.0, 0.0)
    trace_dir: Optional[str] = None
    trace: Any = None
    facts: Dict[str, Any] = field(default_factory=dict)

    def __post_init__(self):
        self._meter_start = self.meter.seconds

    @property
    def seed(self) -> int:
        return self.args.seed

    def end_setup(self):
        """Set-up ends here: every shape the window uses has run."""
        self.setup_s = time.perf_counter() - self.t_start
        self.compile_s_setup = self.meter.seconds - self._meter_start
        self._compiles = self.meter.compiles

    def begin_window(self):
        """The measured window starts: the profiler runs over it in a
        traced run."""
        import jax
        if self.args.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.trace_dir)
        self._window_span = self.spans("window")
        self._window_span.__enter__()
        self.window = (time.perf_counter(), math.nan)

    def end_window(self):
        """The window ends (after the driver waited for the device); a
        compile inside it is an error."""
        import jax
        self._window_span.__exit__(None, None, None)
        self.window = (self.window[0], time.perf_counter())
        if self.args.trace:
            jax.profiler.stop_trace()
        if self.meter.compiles != self._compiles:
            raise RuntimeError(
                f"{self.meter.compiles - self._compiles} compile(s) inside "
                "the measured window")

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def peaks(self) -> dict:
        """The device's row of ``peaks.json``; an unknown device is an
        error. A rehearsal on the CPU gets unit peaks, and its readers'
        values are never printed."""
        table = read_json(BENCH / "peaks.json")["devices"]
        kind = self.devices[0].device_kind
        if self.rehearsal:
            return {k: 1.0 for k in next(iter(table.values()))}
        if kind not in table:
            raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
        return table[kind]


def _device_check(chips: int, rehearsal: bool):
    import jax
    devices = jax.devices()
    if rehearsal:
        return devices[:chips]
    if devices[0].platform != "tpu":
        raise NoDevice(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoDevice(f"cell needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def _compile_cache(jax):
    jax.config.update("jax_compilation_cache_dir", str(JAX_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _per_layer(run: Run) -> Dict[str, dict]:
    """Every per-layer metric listed for this cell that its reader finds."""
    cell = run.cell["name"]
    e2e_here = {m["name"] for m in run.spec["end_to_end"]
                if cell in m.get("workloads", [cell])}
    out = {}
    for m in run.spec["per_layer"]:
        listed = m.get("workloads")
        if (cell not in listed) if listed is not None \
                else (m["moves"] not in e2e_here):
            continue
        value = _load_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _peak_memory(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def execute(args, t_start: float, rehearsal: bool = False,
            data_override: Optional[dict] = None) -> dict:
    """One run of ``args.workload``; returns its result line (a dict).
    Raises ``NoDevice`` before any work when the chips are missing."""
    spec, cell, config = cell_spec(args.workload)
    if data_override:
        config["data"].update(data_override)
    import jax
    devices = _device_check(cell["chips"], rehearsal)
    if not rehearsal:
        _compile_cache(jax)
    traffic = read_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    traffic.update(getattr(args, "traffic_override", {}))
    run = Run(args=args, spec=spec, cell=cell, config=config,
              traffic=traffic, devices=devices,
              spans=Spans(), meter=meter(), t_start=t_start,
              rehearsal=rehearsal)
    driver = importlib.import_module(f"bench.drivers.{traffic['kind']}")
    result = driver.run(run)
    memory = _peak_memory(devices)
    checks = driver.check(run, result)
    # an answer that never came, or a non-finite one, is not correct
    correct = result["failed"] == 0 and all(v <= lim
                                            for v, lim in checks.values())

    if args.trace:
        from bench import trace as trace_lib
        run.trace = trace_lib.reduce_dir(
            run.trace_dir, len(devices), keep=getattr(args, "keep_trace", ""),
            cpu=rehearsal)
    if args.trace:
        metrics = _per_layer(run)
    else:
        metrics = {"setup_s": {"value": run.setup_s, "unit": "s"}}
        for m in spec["end_to_end"]:
            if m["name"] in result["e2e"]:
                metrics[m["name"]] = {"value": result["e2e"][m["name"]],
                                      "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory}
    line = {"correct": bool(correct), "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics,
            "device": device}
    if args.trace:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        line["breakdown"] = {"device_ops": run.trace.top_ops,
                             "idle_gaps": run.trace.idle_by_span}
    if rehearsal:
        # a CPU run: counts and checks only, never a device metric
        line = {"rehearsal": True, "correct": line["correct"],
                "attempted": line["attempted"], "failed": line["failed"],
                "metric_names": sorted(metrics),
                "device": {"platform": dev.platform, "count": len(devices)}}
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    return line


def run_cell(args, t_start: float, rehearsal: bool = False,
             data_override: Optional[dict] = None) -> int:
    try:
        line = execute(args, t_start, rehearsal, data_override)
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    sys.stdout.flush()
    for k, c in line["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    return 0


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    args.variant = "program"
    return args


def main(argv, t_start: float) -> int:
    return run_cell(parse(argv), t_start)

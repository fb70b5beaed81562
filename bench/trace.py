"""Reduce a profiler trace (``.xplane.pb``) to the device's busy time, its
busiest operations, collective time, and its idle gaps attributed to the
benchmark's host spans.

Device planes are ``/device:TPU:<n>``; their op events sit on the line
``XLA Ops``. The traced window is the host span ``window`` the harness
opens around the measured window; device time outside it is cut off.
Each idle gap inside the window is charged to the benchmark host span
(``sample``, ``dispatch``, ...) that overlaps it most, or to ``none``.
"""
from __future__ import annotations

import glob
import os
import shutil
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
COLLECTIVE_MARKS = ("all-gather", "all-reduce", "reduce-scatter",
                    "collective-permute", "all-to-all")
HOST_SPANS = ("sample", "stage", "dispatch", "hook", "plan", "answer",
              "generator", "pass")


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                    # mean over the devices
    collective_s: float              # mean over the devices
    top_ops: List[Tuple[str, float]]
    idle_by_span: List[Tuple[str, float]]
    n_devices: int
    op_events: int = 0
    device_planes: List[str] = field(default_factory=list)


def short_name(op: str) -> str:
    """``%fusion.3 = f32[512,64]{1,0:T(8,128)} fusion(...)`` ->
    ``%fusion.3 f32[512,64]``: the op and its result shape."""
    name, _, rest = op.partition(" = ")
    shape = "(tuple)" if rest.startswith("(") else \
        rest.split(" ", 1)[0].split("{", 1)[0]
    return f"{name} {shape}".strip()


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _overlap(a0, a1, spans):
    best, name = 0.0, "none"
    for n, s0, s1 in spans:
        o = min(a1, s1) - max(a0, s0)
        if o > best:
            best, name = o, n
    return name


def device_ops(pd, cpu: bool = False) -> Dict[str, list]:
    """{device plane name: [(name, start_ns, end_ns), ...]}. ``cpu``: a
    rehearsal's trace, whose XLA ops run on host threads ``tf_XLA*``."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:" if cpu else "/device:"):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE or (cpu and
                                         line.name.startswith("tf_XLA")):
                out.setdefault(plane.name, []).extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.duration_ns > 0)
    return out


def host_spans(pd) -> List[Tuple[str, float, float]]:
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == "window" or e.name in HOST_SPANS:
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    return out


def reduce(pd, n_devices: Optional[int] = None,
           cpu: bool = False) -> TraceSummary:
    spans = host_spans(pd)
    windows = [(a, b) for n, a, b in spans if n == "window"]
    if not windows:
        raise RuntimeError("trace holds no 'window' span")
    w0, w1 = windows[0]
    spans = [s for s in spans if s[0] != "window"]
    devs = device_ops(pd, cpu)
    if not devs:
        raise RuntimeError("trace holds no device op events")
    n = n_devices or len(devs)
    busy = coll = 0.0
    per_op: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    events = 0
    for name in sorted(devs)[:n]:
        ops = [(o, max(a, w0), min(b, w1)) for o, a, b in devs[name]
               if b > w0 and a < w1]
        events += len(ops)
        merged = _union([(a, b) for _, a, b in ops])
        busy += sum(b - a for a, b in merged)
        coll += sum(b - a for a, b in _union(
            [(a, b) for o, a, b in ops
             if any(k in o.lower() for k in COLLECTIVE_MARKS)]))
        for o, a, b in ops:
            k = short_name(o)
            per_op[k] = per_op.get(k, 0.0) + (b - a)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                k = _overlap(a, b, spans)
                idle[k] = idle.get(k, 0.0) + (b - a)
    scale = 1e-9 / n
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy * scale,
        collective_s=coll * scale,
        top_ops=[[k, v * scale] for k, v in top],
        idle_by_span=[[k, v * scale] for k, v in gaps],
        n_devices=n, op_events=events, device_planes=sorted(devs)[:n])


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def reduce_dir(trace_dir: str, n_devices: int, keep: str = "",
               cpu: bool = False) -> TraceSummary:
    """Reduce the one trace under ``trace_dir``, then delete the directory
    (``keep``: copy the .xplane.pb there first)."""
    from jax.profiler import ProfileData
    try:
        path = find_xplane(trace_dir)
        if keep:
            shutil.copy(path, keep)
        return reduce(ProfileData.from_file(path), n_devices, cpu)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)

"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics and the ``breakdown`` read.

What a TPU trace holds, as read by hand from one (jax 0.9, TPU v5 lite):
a plane ``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one
event per program run, named ``jit_<function>(<fingerprint>)``) and
``XLA Ops`` (one event per HLO operation, named by its HLO text
``%fusion.3 = f32[...] fusion(...)``), and a plane ``/host:CPU`` whose
lines are host threads; the benchmark's own spans
(``jax.profiler.TraceAnnotation``) sit there under their names.  All
times are nanoseconds on one clock; device and host events line up to
about a millisecond.

Busy time is the union of the ``XLA Ops`` intervals of a chip inside the
benchmark's ``window`` span, averaged over the chips; an idle gap is a
stretch of the window with no operation running, named by the innermost
benchmark span the host was in at its middle.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

# the benchmark's own host spans; a gap outside all of them is "outside"
SPANS = ("window", "warmup", "step", "upload", "infer")
_FINGERPRINT = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]


@dataclass
class Device:
    ops: List[Tuple[float, float, str]] = field(default_factory=list)
    modules: List[Tuple[float, float, str]] = field(default_factory=list)


@dataclass
class Summary:
    busy_s: float                        # mean over chips
    window_s: float
    device_ops: List[Tuple[str, float]]  # top by total seconds
    idle_gaps: List[Tuple[str, float]]   # longest gaps
    module_s: Dict[str, float]           # program name -> device seconds
    module_runs: Dict[str, int]
    steps: List[Dict[str, float]]        # per step span: prep to longest


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def module_name(event_name: str) -> str:
    """``jit_agg(6825050033741016407)`` -> ``jit_agg``."""
    return _FINGERPRINT.sub("", event_name)


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(path: str):
    """(devices, spans) from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: List[Device] = []
    spans: Dict[str, List[Interval]] = {n: [] for n in SPANS}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = Device()
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops = [(e.start_ns, e.start_ns + e.duration_ns,
                                e.name) for e in line.events]
                elif line.name == "XLA Modules":
                    dev.modules = [(e.start_ns, e.start_ns + e.duration_ns,
                                    module_name(e.name))
                                   for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in spans:
                        spans[e.name].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    return devices, spans


def union(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    """Merged intervals clipped to [lo, hi]."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans: Dict[str, List[Interval]], t: float) -> str:
    """Name of the shortest benchmark span other than the window that
    holds time ``t``, or ``outside``."""
    best, width = "outside", float("inf")
    for name, ivs in spans.items():
        if name == "window":
            continue
        for a, b in ivs:
            if a <= t <= b and b - a < width:
                best, width = name, b - a
    return best


def reduce(devices: List[Device], spans: Dict[str, List[Interval]],
           top: int = 10) -> Summary:
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    if spans["window"]:
        lo, hi = spans["window"][0]
    else:
        ts = [t for d in devices for iv in d.ops + d.modules
              for t in iv[:2]]
        lo, hi = min(ts), max(ts)
    busy_total = 0.0
    by_op: Dict[str, float] = {}
    module_s: Dict[str, float] = {}
    module_runs: Dict[str, int] = {}
    all_gaps: List[Interval] = []
    for d in devices:
        busy = union([(a, b) for a, b, _ in d.ops], lo, hi)
        busy_total += sum(b - a for a, b in busy)
        all_gaps += gaps(busy, lo, hi)
        mods = sorted(m for m in d.modules if lo <= m[0] < hi)
        starts = [m[0] for m in mods]
        for a, b, name in d.ops:
            if not lo <= a < hi:
                continue
            i = bisect.bisect_right(starts, a) - 1
            mod = mods[i][2] if i >= 0 and a <= mods[i][1] else "?"
            key = f"{mod}/{op_name(name)}"
            by_op[key] = by_op.get(key, 0.0) + (b - a) / 1e9
        for a, b, name in mods:
            module_s[name] = module_s.get(name, 0.0) + (b - a) / 1e9
            module_runs[name] = module_runs.get(name, 0) + 1
    steps = []
    mods0 = sorted(devices[0].modules)
    for a, b in sorted(spans["step"]):
        if b < lo or a > hi:
            continue
        inside = [m for m in mods0 if a <= m[0] <= b]
        if inside:
            m = max(inside, key=lambda m: m[1] - m[0])
            steps.append({"prep_s": (m[0] - a) / 1e9,
                          "program": m[2], "program_s": (m[1] - m[0]) / 1e9,
                          "span_s": (b - a) / 1e9})
    named = sorted(((innermost(spans, (a + b) / 2), (b - a) / 1e9)
                    for a, b in all_gaps), key=lambda g: -g[1])
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])
    return Summary(busy_s=busy_total / len(devices) / 1e9,
                   window_s=(hi - lo) / 1e9, device_ops=ops[:top],
                   idle_gaps=named[:top], module_s=module_s,
                   module_runs=module_runs, steps=steps)


def summarize(trace_dir: str) -> Summary:
    return reduce(*load(find_xplane(trace_dir)))

"""Shared pieces of the chip benchmark: loading a cell by name, the
device check, the compile clock, host spans, statistics and the result
line.

Everything that belongs to one configuration, one traffic mix, one cell's
limits or one per-layer metric sits in a file of its own and is found by
the name ``BENCHMARK.json`` gives it:

    configs/<config>.json     sizes of one deployment (and its source)
    traffic/<traffic>.json    the mix: ``driver`` names drivers/<driver>.py
    limits/<cell>.json        the limit of each number ``correct`` compares
    metrics/<metric>.py       ``read(ctx)`` -> float or None

This module imports no JAX at import time, so the load generator's child
process can share it without touching a JAX backend.
"""
from __future__ import annotations

import importlib.util
import json
import math
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]          # the checkout: BENCHMARK.json lives here

# XLA's own compile, and loads from the persistent cache in its place
# (trace and lowering events nest, so summing them would double-count)
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class BenchError(Exception):
    """The run cannot produce a result (no chip, unknown device, bad cell)."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a file whose name may hold dots (``metrics/mfu.train.py``)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise BenchError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One entry of ``BENCHMARK.json``'s ``workloads`` with its files."""
    name: str
    chips: int
    dirs: List[Path]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def reports(metric: Dict[str, Any], cell: str) -> bool:
    """Whether ``cell`` reports the per-layer ``metric``: every entry
    names the cells it is read in under ``workloads``."""
    if "workloads" not in metric:
        raise BenchError(f"per-layer metric {metric['name']!r} names no "
                         "workloads")
    return cell in metric["workloads"]


def find(dirs: List[Path], *parts: str) -> Path:
    """The first of ``dirs`` that holds ``parts``."""
    for d in dirs:
        p = d.joinpath(*parts)
        if p.exists():
            return p
    raise BenchError(f"no {'/'.join(parts)} under {[str(d) for d in dirs]}")


def load_cell(name: str, root: Path = ROOT, dirs=None) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, its files looked up
    in ``dirs`` (default: this directory)."""
    dirs = list(dirs) if dirs else [HERE]
    bench = load_json(root / "BENCHMARK.json")
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = entries[0]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    per_layer = [m for m in bench["per_layer"] if reports(m, name)]
    return Cell(name=name, chips=int(w["chips"]), dirs=dirs,
                config=load_json(find(dirs, "configs",
                                      f"{w['config']}.json")),
                traffic=load_json(find(dirs, "traffic",
                                       f"{w['traffic']}.json")),
                limits=load_json(find(dirs, "limits", f"{name}.json")),
                end_to_end=e2e, per_layer=per_layer)


def peak_of(device_kind: str, dirs=None) -> Dict[str, float]:
    """The peaks of one chip, or an error: a device missing from the
    table has no default."""
    table = load_json(find(list(dirs) if dirs else [HERE],
                           "peaks.json"))["devices"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in peaks.json "
                         f"(known: {sorted(table)})")
    return table[device_kind]


def require_tpu(chips: int):
    """The devices, or BenchError: there is no CPU fallback."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise BenchError(f"no accelerator found ({e})") from None
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU found (JAX platform is "
                         f"{devs[0].platform!r}); the benchmark measures "
                         "the chip and has no CPU fallback")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, with
    every program cached, so that only a cell's first run compiles."""
    import jax
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Seconds XLA spent compiling (or loading compiled programs from the
    persistent cache) since the last :meth:`lap`, and how many backend
    compiles ran, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds, self.events = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration
            self.events += event.endswith("backend_compile_duration")

    def lap(self):
        out = (self.seconds, self.events)
        self.seconds, self.events = 0.0, 0
        return out


def span(name: str, **kw):
    """A host span on the profiler's clock (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name, **kw)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) of a non-empty sample."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[rank - 1])


@dataclass
class Phases:
    """Set-up split by phase on the host clock, from process start."""
    t_start: float
    marks: Dict[str, float] = field(default_factory=dict)
    _last: float = 0.0

    def __post_init__(self):
        self._last = self.t_start

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.marks[name] = self.marks.get(name, 0.0) + now - self._last
        self._last = now

    def since_start(self) -> float:
        return time.perf_counter() - self.t_start


@dataclass
class Outcome:
    """What a driver hands back to ``run.py``."""
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    checks: Dict[str, Dict[str, float]]       # name -> {value, limit}
    counters: Dict[str, Any]                  # for the per-layer readers
    memory_peak_bytes: int
    trace_path: Optional[str] = None


def judge(checks: Dict[str, Dict[str, float]]) -> bool:
    """Every compared number under its limit, and every one a number."""
    return all(isinstance(c["value"], (int, float))
               and math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def check_entry(value: float, limit: float) -> Dict[str, float]:
    return {"value": float(value), "limit": float(limit)}

#!/usr/bin/env python3
"""Where a traced round's time goes, by the program's own host spans and
device scopes.

    python3 benchmarks/chip/programtrace.py --workload <cell> --seed <n> \\
        --seconds <s>

makes one run of a cell exactly as ``run.py ... --trace 1`` does, and
prints its result line with one key more, ``program``: the breakdown
below, read from the same trace before ``run.py`` removes it.  The
per-layer metrics of ``BENCHMARK.json`` do not read these spans and
scopes; ``tracefile.py`` knows only the benchmark's own spans.

Host: the program's spans (``repro.spans.HOST_SPANS``) are
``jax.profiler.TraceAnnotation``s on the host plane, with their counts as
event stats.  Each one's milliseconds per round are its seconds inside
the benchmark's ``window`` span over the ``step`` spans there.  The copy
of the cohort to the chip runs after ``round/dispatch`` returns, so no
host span holds it: ``transfer_wait_ms`` is, per step, the time from the
end of ``round/dispatch`` to the start of the round program (the longest
device program of the step).

Device: a scope (``repro.spans.DEVICE_SCOPES``) reaches the compiled
program only as the ``op_name`` metadata of each HLO instruction, and a
fused op's own ``op_name`` is its root's alone.  The trace's
``/host:metadata`` plane holds each program's ``HloProto``;
:func:`hlo_ops` reads it with a small protobuf wire walker (no new
dependency) and labels each instruction by the scopes of every
instruction it fuses: one scope, several joined by ``+`` (a fusion that
mixes stages), or ``""`` (unscoped).  The round program's busy time is
then split by label: each instant goes to the innermost running op that
carries a label (a ``while`` op holds its body's ops), ``unscoped`` where
none does, so the parts add up to its busy time.
"""
from __future__ import annotations

import argparse
import bisect
import heapq
import json
import re
import sys
from dataclasses import asdict, dataclass, field
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

import tracefile  # noqa: E402
from repro.spans import (DEVICE_SCOPES, DISPATCH, GATHER,  # noqa: E402
                         HOST_SPANS)

UNSCOPED = "unscoped"
MIX = "+"
_WRAPPER = re.compile(r"[A-Za-z_]\w*\((.*)\)")
_PROGRAM_ID = re.compile(r"\((-?\d+)\)$")

Interval = Tuple[float, float]


# -- scopes in an op name ------------------------------------------------

def segments(path: str) -> List[str]:
    """The name-stack segments of an ``op_name``, transform wrappers
    stripped: ``jit(f)/transpose(jvp(vmap(transform/topk)))/mul`` ->
    ``[f, transform, topk, mul]``."""
    out, depth, seg = [], 0, ""
    for ch in path + "/":
        if ch == "/" and depth == 0:
            m = _WRAPPER.fullmatch(seg)
            out += segments(m.group(1)) if m else [seg] if seg else []
            seg = ""
            continue
        depth += (ch == "(") - (ch == ")")
        seg += ch
    return out


def scope_of(op_name: str) -> Optional[str]:
    """The innermost program scope that ``op_name`` carries as whole path
    segments, or None."""
    segs = segments(op_name)
    best, at = None, -1
    for sc in DEVICE_SCOPES:
        parts = sc.split("/")
        for i in range(len(segs) - len(parts) + 1):
            if segs[i:i + len(parts)] == parts and i >= at:
                best, at = sc, i
    return best


# -- the raw XSpace and HloProto: just enough protobuf -------------------
# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 and
# .stat_metadata = 5 (maps: key = 1, value = 2); XEventMetadata.id = 1,
# .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1,
# .bytes_value = 6.  HloProto.hlo_module = 1; HloModuleProto.computations
# = 3; HloComputationProto.instructions = 2, .id = 5;
# HloInstructionProto.name = 1, .opcode = 2, .metadata = 7,
# .called_computation_ids = 38; OpMetadata.op_name = 2.

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int, or a memoryview for
    a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif kind in (1, 5):
            w = 8 if kind == 1 else 4
            v, i = int.from_bytes(buf[i:i + w], "little"), i + w
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def _ints(v) -> List[int]:
    """A repeated integer field's value: one varint, or a packed run."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


@dataclass
class HloOp:
    label: str        # its scopes, "+"-joined; "" if it carries none
    op_name: str      # its own op_name metadata


def module_ops(hlo_proto) -> Dict[str, HloOp]:
    """HLO instruction name -> :class:`HloOp` for one ``HloProto``.  A
    fusion is labelled by the scopes of every instruction it fuses (its
    called computations, recursively); any other op by its own."""
    module = dict(_fields(hlo_proto)).get(1, b"")
    comps: Dict[int, list] = {}
    for num, comp in _fields(module):
        if num != 3:
            continue
        cid, instrs = None, []
        for cnum, v in _fields(comp):
            if cnum == 5:
                cid = v
            elif cnum == 2:
                name, opcode, opn, called = "", "", "", []
                for inum, x in _fields(v):
                    if inum == 1:
                        name = _text(x)
                    elif inum == 2:
                        opcode = _text(x)
                    elif inum == 7:
                        opn = _text(dict(_fields(x)).get(2, b""))
                    elif inum == 38:
                        called += _ints(x)
                instrs.append((name, opcode, opn, called))
        comps[cid] = instrs
    memo: Dict[int, frozenset] = {}

    def inside(cid: int) -> frozenset:
        if cid not in memo:
            memo[cid] = frozenset()           # no computation calls itself
            out = set()
            for _, _, opn, called in comps.get(cid, ()):
                out.add(scope_of(opn))
                for c in called:
                    out |= inside(c)
            memo[cid] = frozenset(out)
        return memo[cid]

    ops: Dict[str, HloOp] = {}
    for instrs in comps.values():
        for name, opcode, opn, called in instrs:
            scopes = {scope_of(opn)}
            if opcode == "fusion":
                for c in called:
                    scopes |= inside(c)
            ops[name] = HloOp(MIX.join(sorted(s for s in scopes if s)), opn)
    return ops


def hlo_ops(path: str) -> Dict[int, Dict[str, HloOp]]:
    """Program id -> its ops (:func:`module_ops`), for every program
    whose ``HloProto`` the trace's ``/host:metadata`` plane holds."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out: Dict[int, Dict[str, HloOp]] = {}
    for num, plane in _fields(data):
        if num != 1:
            continue
        fields = list(_fields(plane))
        if not any(n == 2 and _text(v) == "/host:metadata"
                   for n, v in fields):
            continue
        hlo_id = None
        for n, v in fields:
            if n == 5:
                entry = dict(_fields(v))
                md = dict(_fields(entry.get(2, b"")))
                if _text(md.get(2, b"")) == "Hlo Proto":
                    hlo_id = entry.get(1)
        for n, v in fields:
            if n != 4:
                continue
            md = list(_fields(dict(_fields(v)).get(2, b"")))
            prog = dict(md).get(1, 0)
            for mn, stat in md:
                st = dict(_fields(stat)) if mn == 5 else {}
                if st.get(1) == hlo_id and 6 in st:
                    out[prog] = module_ops(st[6])
    return out


# -- the trace's events --------------------------------------------------

def program_id(event_name: str) -> Optional[int]:
    """``jit_agg(6825050033741016407)`` -> 6825050033741016407, as the
    metadata plane keys it (unsigned)."""
    m = _PROGRAM_ID.search(event_name)
    return int(m.group(1)) % (1 << 64) if m else None


Op = Tuple[float, float, str]                    # start, end, HLO name
Module = Tuple[float, float, str, Optional[int]]  # ... name, program id


def load(path: str):
    """(devices, spans, stats) of an ``.xplane.pb``: per TPU plane its
    ops and programs; the benchmark's and the program's host spans; and
    each program span's counts, in the order of its intervals."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: List[Tuple[List[Op], List[Module]]] = []
    spans: Dict[str, List[Interval]] = {
        n: [] for n in tracefile.SPANS + HOST_SPANS}
    stats: Dict[str, List[Dict[str, float]]] = {n: [] for n in HOST_SPANS}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops: List[Op] = []
            mods: List[Module] = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns, e.start_ns + e.duration_ns,
                            tracefile.op_name(e.name)) for e in line.events]
                elif line.name == "XLA Modules":
                    mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                                   tracefile.module_name(e.name),
                                   program_id(e.name)) for e in line.events)
            devices.append((ops, mods))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in spans:
                        spans[e.name].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
                        if e.name in stats:
                            stats[e.name].append(dict(e.stats))
    return devices, spans, stats


# -- the breakdown -------------------------------------------------------

def scope_times(ops: List[Tuple[float, float, str]],
                lo: float, hi: float) -> Dict[str, float]:
    """Nanoseconds of [lo, hi] by label: each instant in which an op of
    ``(start, end, label)`` runs goes to the innermost (latest started)
    running op with a label, to ``unscoped`` if none has one."""
    ops = sorted((max(a, lo), min(b, hi), lab) for a, b, lab in ops
                 if min(b, hi) > max(a, lo))
    times = sorted({t for a, b, _ in ops for t in (a, b)})
    out: Dict[str, float] = {}
    live: List[float] = []                       # ends of running ops
    labelled: List[Tuple[float, float, str]] = []  # (-start, end, label)
    i = 0
    for t, nxt in zip(times, times[1:]):
        while i < len(ops) and ops[i][0] <= t:
            a, b, lab = ops[i]
            heapq.heappush(live, b)
            if lab:
                heapq.heappush(labelled, (-a, b, lab))
            i += 1
        while live and live[0] <= t:
            heapq.heappop(live)
        while labelled and labelled[0][1] <= t:
            heapq.heappop(labelled)
        if live:
            key = labelled[0][2] if labelled else UNSCOPED
            out[key] = out.get(key, 0.0) + nxt - t
    return out


def inside(ivs: List[Interval], a: float, b: float) -> List[Interval]:
    return [iv for iv in ivs if a <= iv[0] and iv[1] <= b]


@dataclass
class Breakdown:
    rounds: int                      # step spans inside the window
    step_ms: float                   # mean step span
    host_ms: Dict[str, float]        # program span -> ms per round
    host_share_of_step: float        # their sum over step_ms
    transfer_wait_ms: float          # dispatch's end -> round program
    gather_gb_per_s: Optional[float]
    device_busy_ms: float            # all programs, per round
    round_program_ms: float          # its busy time, per round
    device_ms: Dict[str, float] = field(default_factory=dict)  # by label
    ops: List[list] = field(default_factory=list)  # longest round ops
    idle_gaps: List[list] = field(default_factory=list)


def breakdown(devices, spans, stats, hlo: Dict[int, Dict[str, HloOp]],
              top: int = 12) -> Breakdown:
    """Per round, over the ``step`` spans inside the ``window`` span (or
    all of them, where the run has no window)."""
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    lo, hi = spans["window"][0] if spans["window"] else (
        min(a for a, _ in spans["step"]), max(b for _, b in spans["step"]))
    steps = sorted(inside(spans["step"], lo, hi))
    if not steps:
        raise ValueError("no step span inside the window")
    n = len(steps)
    host_ms = {name: 1e-6 * sum(b - a for st in steps
                                for a, b in inside(spans[name], *st)) / n
               for name in HOST_SPANS}
    gathered = [(b - a, kw.get("bytes", 0.0))
                for (a, b), kw in zip(spans[GATHER], stats[GATHER])
                if any(s[0] <= a and b <= s[1] for s in steps)]
    secs = sum(t for t, _ in gathered) / 1e9
    waits, busy, prog_busy = [], 0.0, 0.0
    by_label: Dict[str, float] = {}
    by_op: Dict[Tuple[str, str], float] = {}
    known: Dict[Tuple[str, str], HloOp] = {}
    split = True
    for d, (ops, mods) in enumerate(devices):
        starts = [o[0] for o in ops]

        def between(a, b):
            return ops[bisect.bisect_left(starts, a):
                       bisect.bisect_right(starts, b)]
        for a, b in steps:
            busy += sum(y - x for x, y in tracefile.union(
                [o[:2] for o in between(a, b)], a, b))
            runs = [m for m in mods if a <= m[0] <= b]
            if not runs:
                continue
            m0, m1, mod, prog = max(runs, key=lambda m: m[1] - m[0])
            if d == 0:
                ends = [y for x, y in inside(spans[DISPATCH], a, b)
                        if y <= m0]
                if ends:
                    waits.append(m0 - max(ends))
            body = between(m0, m1)
            prog_busy += sum(y - x for x, y in tracefile.union(
                [o[:2] for o in body], m0, m1))
            hlo_of = hlo.get(prog)
            split = split and hlo_of is not None
            for x, y, name in body:
                by_op[mod, name] = by_op.get((mod, name), 0.0) + y - x
                if hlo_of and name in hlo_of:
                    known[mod, name] = hlo_of[name]
            if hlo_of:
                for lab, ns in scope_times(
                        [(x, y, hlo_of[nm].label if nm in hlo_of else "")
                         for x, y, nm in body], m0, m1).items():
                    by_label[lab] = by_label.get(lab, 0.0) + ns
    per = 1e-6 / n / len(devices)
    all_gaps: List[Interval] = []
    for ops, _ in devices:
        all_gaps += tracefile.gaps(
            tracefile.union([o[:2] for o in ops], lo, hi), lo, hi)
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:10]
    step_ms = 1e-6 * sum(b - a for a, b in steps) / n
    longest_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return Breakdown(
        rounds=n, step_ms=step_ms, host_ms=host_ms,
        host_share_of_step=sum(host_ms.values()) / step_ms,
        transfer_wait_ms=1e-6 * sum(waits) / len(waits) if waits else 0.0,
        gather_gb_per_s=sum(b for _, b in gathered) / secs / 1e9
        if secs else None,
        device_busy_ms=busy * per, round_program_ms=prog_busy * per,
        device_ms={k: v * per for k, v in sorted(by_label.items())}
        if split else {},
        ops=[[f"{mod}/{name}", known[mod, name].label
              if (mod, name) in known else None,
              known[mod, name].op_name if (mod, name) in known else None,
              ns * per] for (mod, name), ns in longest_ops],
        idle_gaps=[[tracefile.innermost(spans, (a + b) / 2), (b - a) / 1e9]
                   for a, b in longest])


def read(path: str) -> Breakdown:
    return breakdown(*load(path), hlo_ops(path))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    args.trace = 1
    import run
    found: Dict[str, object] = {}
    summarize = tracefile.summarize

    def both(trace_dir: str):
        found.update(asdict(read(tracefile.find_xplane(trace_dir))))
        return summarize(trace_dir)

    tracefile.summarize = both           # run.py then removes the trace
    result = run.execute(args)
    result["program"] = found
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

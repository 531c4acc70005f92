"""Reduction of a profiler trace (``.xplane.pb``) to the device numbers.

* busy seconds per chip: the union of the intervals in which an operation
  ran on that chip ("XLA Ops" line of each ``/device:TPU:<n>`` plane);
* seconds per device operation, summed over chips, and per kernel: the
  operations whose name or any string statistic contains the kernel name;
* idle gaps on each chip, each labelled by the innermost host span (from
  the benchmark's own annotations and the runtime's) open on the thread
  that drove the run at the gap's midpoint.
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
MAIN_SPAN = "bench."          # prefix of the benchmark's own host spans


@dataclasses.dataclass
class Op:
    name: str
    start: int      # ns
    dur: int        # ns
    text: str       # name and string statistics, for kernel matching


@dataclasses.dataclass
class TraceSummary:
    busy_s: list            # per chip
    op_seconds: dict        # op name -> seconds, summed over chips
    kernel_seconds: dict    # kernel -> seconds, summed over chips
    idle_by_host: dict      # host span -> idle seconds, summed over chips
    n_chips: int


def find_xplane(log_dir) -> Path:
    found = sorted(Path(log_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _stat_text(ev) -> str:
    parts = [ev.name]
    for k, v in ev.stats:
        if isinstance(v, str):
            parts.append(f"{k}={v}")
    return " ".join(parts)


def _events(line):
    return [Op(e.name, int(e.start_ns), int(e.duration_ns), _stat_text(e))
            for e in line.events]


def device_ops(pd) -> list[list[Op]]:
    """Operations per chip, in chip order."""
    chips = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX) and \
                plane.name[len(DEVICE_PREFIX):].isdigit():
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE] or [
                ln for ln in plane.lines if "Ops" in ln.name]
            ops = [op for line in lines for op in _events(line)]
            chips.append((int(plane.name[len(DEVICE_PREFIX):]), ops))
    return [ops for _, ops in sorted(chips)]


def host_spans(pd) -> list[Op]:
    """Spans of the host thread that carries the benchmark's annotations."""
    best = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            evs = _events(line)
            if any(e.name.startswith(MAIN_SPAN) for e in evs) and \
                    len(evs) > len(best):
                best = evs
    return best


def union(intervals) -> list[tuple[int, int]]:
    """Merged (start, end) of possibly overlapping (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _label(spans, t) -> str:
    """'outer>inner' of the host spans open at time ``t``."""
    open_ = [s for s in spans if s.start <= t < s.start + s.dur]
    if not open_:
        return "host idle"
    open_.sort(key=lambda s: (s.start, -s.dur))
    names = [s.name for s in open_]
    return ">".join(names[:1] + names[-1:]) if len(names) > 1 else names[0]


def summarize(path, kernels=()) -> TraceSummary:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    per_chip = device_ops(pd)
    spans = host_spans(pd)
    spans_sorted = sorted(spans, key=lambda s: s.start)
    busy, op_s = [], defaultdict(float)
    k_s = defaultdict(float)
    idle = defaultdict(float)
    for ops in per_chip:
        merged = union((o.start, o.start + o.dur) for o in ops)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for o in ops:
            op_s[o.name] += o.dur * 1e-9
            for k in kernels:
                if k in o.text:
                    k_s[k] += o.dur * 1e-9
        if spans_sorted and merged:
            lo = spans_sorted[0].start
            hi = max(s.start + s.dur for s in spans_sorted)
            edges = [(max(a_end, lo), min(b_start, hi)) for (_, a_end), (
                b_start, _) in zip(merged, merged[1:])]
            for s, e in edges:
                if e > s:
                    idle[_label(spans, (s + e) // 2)] += (e - s) * 1e-9
    return TraceSummary(busy, dict(op_s), dict(k_s), dict(idle),
                        len(per_chip))


def top(d: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]

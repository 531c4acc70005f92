"""Device idle time split by the program's own ``serve.*`` host spans.

``trace.summarize`` names each idle gap of a chip after the host span open
at the gap's midpoint, so a gap that outlasts several phases of a step is
booked whole to one of them.  Here each idle interval is split instead:
every instant of it goes to the innermost ``serve.*`` span open on the
thread that drove the run, and to ``OUTSIDE`` where none is open.  The
spans are written by ``ContinuousBatcher`` while its ``trace`` is on.
"""
from __future__ import annotations

import sys
from bisect import bisect_right
from collections import defaultdict

from harness import trace

PREFIX = "serve."
OUTSIDE = "outside serve"


def innermost(spans) -> list[tuple[int, int, str]]:
    """Time-ordered ``(start, end, name)`` pieces, each naming the
    innermost of the nested ``(start, end, name)`` spans open in it; a
    span reaching past its parent's end is cut there."""
    pieces, stack, t = [], [], None
    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            pieces.append((t, end, top))
            t = end
        if stack:
            pieces.append((t, s, stack[-1][1]))
            e = min(e, stack[-1][0])
        stack.append((e, name))
        t = s
    while stack:
        end, top = stack.pop()
        pieces.append((t, end, top))
        t = end
    return [p for p in pieces if p[1] > p[0]]


def split_idle(gaps, spans) -> dict[str, float]:
    """Nanoseconds of the idle ``(start, end)`` gaps under each innermost
    span of ``spans`` (``(start, end, name)``), ``OUTSIDE`` for the rest;
    the values sum to the gaps' total."""
    pieces = innermost(spans)
    starts = [p[0] for p in pieces]
    out: dict[str, float] = defaultdict(float)
    for gs, ge in gaps:
        covered = 0
        i = max(bisect_right(starts, gs) - 1, 0)
        while i < len(pieces) and pieces[i][0] < ge:
            a, b, name = pieces[i]
            overlap = min(b, ge) - max(a, gs)
            if overlap > 0:
                out[name] += overlap
                covered += overlap
            i += 1
        if ge - gs > covered:
            out[OUTSIDE] += (ge - gs) - covered
    return dict(out)


def idle_by_span(path, log=None) -> dict[str, float]:
    """Idle seconds of every chip in the trace at ``path``, summed over
    chips, by innermost ``serve.*`` span; one ``[idle-by-span]`` line per
    span to ``log`` (standard error by default), largest first.  Gaps are the intervals between device
    operations inside the driving thread's spans, as in
    ``trace.summarize``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    host = trace.host_spans(pd)
    if not host:
        return {}
    lo = min(s.start for s in host)
    hi = max(s.start + s.dur for s in host)
    serve = [(s.start, s.start + s.dur, s.name) for s in host
             if s.name.startswith(PREFIX)]
    total: dict[str, float] = defaultdict(float)
    for ops in trace.device_ops(pd):
        merged = trace.union((o.start, o.start + o.dur) for o in ops)
        gaps = [(max(a_end, lo), min(b_start, hi))
                for (_, a_end), (b_start, _) in zip(merged, merged[1:])]
        for name, ns in split_idle([g for g in gaps if g[1] > g[0]],
                                   serve).items():
            total[name] += ns * 1e-9
    for name, s in sorted(total.items(), key=lambda kv: -kv[1]):
        print(f"[idle-by-span] {name} {s:.6f} s", file=log or sys.stderr)
    return dict(total)

"""The program's host spans in a traced window, on the device trace's clock.

The program names its host work with ``jax.profiler.TraceAnnotation``
spans called ``<layer>.<what>`` (``sched.round``, ``stream.fetch_wait``,
``codec.parse``, ...); the benchmark's ``Probe`` adds ``bench.*`` spans
around the entry points it wraps.  Both land on the host plane's thread
lines of the same ``.xplane.pb`` that ``trace_reduce`` reads, so they share
the device planes' nanosecond clock.

``reduce`` keeps the spans inside the window that the ``bench.wave`` spans
define (clipped to it), and gives for each name its count ``n``, seconds
``s`` and self seconds ``self_s``: the duration less the union of the spans
nested in it on the same line.  It also names each device idle gap by the
innermost span, program or ``bench.*``, that covers the gap's midpoint.
Both come from one sorted sweep with a stack of open spans per line.

Run as ``python3 bench/spans.py <trace dir or .xplane.pb>`` to print the
reduction as JSON.
"""
from __future__ import annotations

import json
import os
import sys
import time
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(HERE))

from bench import trace_reduce  # noqa: E402

PREFIXES = ("bench.", "sched.", "stream.", "codec.", "engine.")
WAVE = "bench.wave"
OUTSIDE = "outside spans"
# where ``bench/run.py`` writes the trace of a ``--trace 1`` run
TRACE_DIR = os.path.join(HERE, "out", "trace")

Span = Tuple[float, float, str]  # (start ns, end ns, name)


def _host_lines(pd) -> List[List[Span]]:
    """Named spans of each host thread line, sorted parents first."""
    out = []
    for plane in pd.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            evs = [(ev.start_ns, ev.end_ns, ev.name) for ev in line.events
                   if ev.name.startswith(PREFIXES)]
            if evs:
                out.append(sorted(evs, key=lambda e: (e[0], -e[1])))
    return out


def _device_busy(pd) -> List[List[Tuple[float, float]]]:
    """Union of the operation intervals on each device plane."""
    out = []
    for plane in pd.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        iv = [(ev.start_ns, ev.end_ns) for line in plane.lines
              if line.name == trace_reduce.OPS_LINE for ev in line.events]
        out.append(trace_reduce._union(iv))
    return out


def _clip(line: List[Span], lo: float, hi: float) -> List[Span]:
    return [(max(s, lo), min(e, hi), n) for s, e, n in line if e > lo and s < hi]


def span_table(lines: List[List[Span]]) -> Dict[str, Dict[str, float]]:
    """``{name: {"n", "s", "self_s"}}`` over lines of properly nested spans
    sorted by (start, -end)."""
    table: Dict[str, Dict[str, float]] = defaultdict(lambda: dict(n=0, s=0.0, self_s=0.0))
    for line in lines:
        stack: List[list] = []  # open spans: [end, name, seconds, children's seconds]
        for s, e, name in line + [(float("inf"), float("inf"), "")]:
            while stack and stack[-1][0] <= s:
                _, done, dur, children = stack.pop()
                table[done]["self_s"] += dur - children
            if not name:
                break
            if stack:
                # a child never outlasts its parent on one thread line
                stack[-1][3] += (min(e, stack[-1][0]) - s) * 1e-9
            table[name]["n"] += 1
            table[name]["s"] += (e - s) * 1e-9
            stack.append([e, name, (e - s) * 1e-9, 0.0])
    return {k: dict(v) for k, v in table.items()}


def name_gaps(lines: List[List[Span]], gaps: List[Tuple[float, float]]) -> Dict[str, float]:
    """Seconds of the gaps by the innermost span over each gap's midpoint
    (the shortest such span of any line)."""
    out: Dict[str, float] = defaultdict(float)
    nxt = [0] * len(lines)  # per line: the first span not yet opened
    stacks: List[List[Span]] = [[] for _ in lines]  # per line: open spans
    for t0, t1 in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = (t0 + t1) / 2
        best: Optional[Span] = None
        for i, (line, stack) in enumerate(zip(lines, stacks)):
            while nxt[i] < len(line) and line[nxt[i]][0] <= mid:
                sp = line[nxt[i]]
                while stack and stack[-1][1] < sp[0]:
                    stack.pop()
                stack.append(sp)
                nxt[i] += 1
            while stack and stack[-1][1] < mid:
                stack.pop()
            if stack and (best is None or stack[-1][1] - stack[-1][0] < best[1] - best[0]):
                best = stack[-1]
        out[best[2] if best else OUTSIDE] += (t1 - t0) * 1e-9
    return dict(out)


def reduce(path: str) -> Dict:
    """Span table and idle gaps by innermost span, inside the window of
    the ``bench.wave`` spans (of the device operations where there are
    none)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    lines = _host_lines(pd)
    waves = [(s, e) for line in lines for s, e, n in line if n == WAVE]
    busy = _device_busy(pd)
    if waves:
        lo, hi = min(s for s, _ in waves), max(e for _, e in waves)
    else:
        # as ``trace_reduce``: the extent of the device operations
        ends = [t for dev in busy for iv in dev for t in iv]
        lo, hi = (min(ends), max(ends)) if ends else (0.0, 0.0)
    lines = [c for c in (_clip(line, lo, hi) for line in lines) if c]
    gap_s: Dict[str, float] = defaultdict(float)
    for dev in busy:
        gaps, t = [], lo
        for s, e in [(max(s, lo), min(e, hi)) for s, e in dev if e > lo and s < hi] + [(hi, hi)]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        for name, v in name_gaps(lines, gaps).items():
            gap_s[name] += v / len(busy)
    return dict(
        window_s=(hi - lo) * 1e-9,
        spans=span_table(lines),
        idle_gaps=sorted(([k, v] for k, v in gap_s.items()), key=lambda kv: -kv[1]),
    )


def of(rec) -> Optional[Dict[str, Dict[str, float]]]:
    """The span table of a traced run (``rec.trace`` set): kept in
    ``rec.trace["spans"]``, read from the run's trace on first use.  None
    for an untraced run or a trace with no spans."""
    if rec.trace is None:
        return None
    if "spans" not in rec.trace:
        try:
            path = trace_reduce.find_trace(TRACE_DIR)
        except FileNotFoundError:
            return None
        rec.trace["spans"] = reduce(path)["spans"]
    return rec.trace["spans"] or None


def main(argv: List[str]) -> int:
    target = argv[0] if argv else TRACE_DIR
    path = target if target.endswith(".xplane.pb") else trace_reduce.find_trace(target)
    t = time.perf_counter()
    out = reduce(path)
    out["reduce_s"] = time.perf_counter() - t
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

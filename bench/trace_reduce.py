"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

Device planes are named ``/device:TPU:<n>``.  On each, the ``XLA Ops``
line holds one event per operation that ran, named by its HLO text
(``%kv_lossless_tokens_pallas.1 = bf16[...] custom-call(...)``, kept here as
``kv_lossless_tokens_pallas``), and the ``XLA Modules`` line one event per
program run (``jit__decode_rows_impl(<hash>)``, kept as
``jit__decode_rows_impl``).  Busy time is the union of the
operation intervals; idle gaps are what the traced window leaves between
them.  Host spans are the benchmark's ``bench.*`` annotations on the host
plane; each idle gap is named by the innermost span that covers it.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
TOP = 10


def find_trace(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _module_name(name: str) -> str:
    """``jit_foo(123)`` -> ``jit_foo``."""
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(name: str) -> str:
    """``%fusion.23 = u32[...] fusion(...)`` -> ``fusion``."""
    return re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].strip().lstrip("%"))


def reduce(path: str) -> Dict:
    """Busy and idle seconds, device seconds per program and per operation,
    and host-span names of the idle gaps."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")
    waves = [(s, e) for s, e, n in spans if n == SPAN_PREFIX + "wave"]
    programs: Dict[str, float] = defaultdict(float)
    ops: Dict[str, float] = defaultdict(float)
    busy_total = 0.0
    per_device_busy = []
    lo = hi = None
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        iv = []
        for ev in lines[OPS_LINE].events if OPS_LINE in lines else []:
            iv.append((ev.start_ns, ev.end_ns))
            ops[_op_name(ev.name)] += ev.duration_ns * 1e-9
        for ev in lines[MODULES_LINE].events if MODULES_LINE in lines else []:
            programs[_module_name(ev.name)] += ev.duration_ns * 1e-9
        busy = _union(iv)
        per_device_busy.append(busy)
        if busy:
            lo = busy[0][0] if lo is None else min(lo, busy[0][0])
            hi = busy[-1][1] if hi is None else max(hi, busy[-1][1])
    if waves:
        lo, hi = min(s for s, _ in waves), max(e for _, e in waves)
    if lo is None:
        raise ValueError(f"{path}: no device operation in the trace")
    window_ns = hi - lo
    gap_by_span: Dict[str, float] = defaultdict(float)
    for busy in per_device_busy:
        clipped = [(max(s, lo), min(e, hi)) for s, e in busy if e > lo and s < hi]
        busy_total += sum(e - s for s, e in clipped)
        t = lo
        for s, e in clipped + [(hi, hi)]:
            if s > t:
                gap_by_span[_innermost(spans, t, s)] += (s - t) * 1e-9
            t = max(t, e)
    n_dev = len(devices)
    top_programs = sorted(programs.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(((k, v / n_dev) for k, v in gap_by_span.items()), key=lambda kv: -kv[1])
    return dict(
        busy_s=busy_total * 1e-9 / n_dev,
        window_s=window_ns * 1e-9,
        programs=dict(programs),
        ops=dict(ops),
        top_programs=[[k, v] for k, v in top_programs],
        idle_gaps=[[k, v] for k, v in gaps[:TOP]],
        n_devices=n_dev,
    )


def _innermost(spans, t0: float, t1: float) -> str:
    """Name of the shortest host span covering the gap's midpoint."""
    mid = (t0 + t1) / 2
    best = None
    for s, e, name in spans:
        if s <= mid <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "outside bench spans"


def reduce_dir(trace_dir: str) -> Dict:
    return reduce(find_trace(trace_dir))


def seconds_matching(table: Dict[str, float], pattern: str) -> float:
    """Total seconds of the entries whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(v for k, v in table.items() if rx.search(k))

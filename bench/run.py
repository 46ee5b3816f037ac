#!/usr/bin/env python3
"""CacheGen serving benchmark on one TPU host.

Usage, from the root of a checkout:

    python3 bench/run.py --workload smollm-360m.doc-reuse --seed 7 \\
        --seconds 30 --trace 0

``--workload`` names an entry of ``BENCHMARK.json``; its configuration,
traffic mix and cell files are found under ``bench/`` by name.  The run
builds everything from ``--seed``, warms up, serves waves for ``--seconds``
seconds, checks the served tokens against the plain reference, and prints
one JSON line last on standard output: with ``--trace 0`` the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics (read from a
profiler trace of the window) and a breakdown of device time.  The numbers
compared for ``correct`` are printed, each beside its limit, as the last
lines of standard error and under ``checks`` in the JSON line.

It runs only on a TPU: without one (or with fewer chips than the cell asks
for) it exits with code 2 and prints no result.  JAX's persistent
compilation cache lives in ``bench/.jax_cache`` unless
``JAX_COMPILATION_CACHE_DIR`` is set.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def metrics_for(kind: str, workload: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in benchmark()[kind]
            if workload in m.get("workloads", [workload])]


def read_per_layer(rec, workload: str) -> dict:
    out = {}
    for m in metrics_for("per_layer", workload):
        path = os.path.join(HERE, "metrics", f"{m['name']}.py")
        spec = importlib.util.spec_from_file_location(f"bench_metric_{len(out)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(rec)
        if value is not None:
            out[m["name"]] = dict(value=float(value), unit=m["unit"])
    return out


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_cache() -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(HERE, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def judge(got: dict, failed: int, limits: dict):
    """``correct`` and the numbers compared, each beside its limit, from a
    check's readings (``Cell.check``) and the window's failed requests."""
    checks = dict(
        logit_gap_widest=dict(value=got["gap"], limit=float(limits["logit_gap_widest"])),
        logit_gap_share=dict(value=got["gap_share"], limit=float(limits["logit_gap_share"])),
        failed_requests=dict(value=failed, limit=0),
    )
    correct = all(c["value"] <= c["limit"] for c in checks.values()) and got["n_requests"] > 0
    return bool(correct), checks


def main(argv=None, *, require_tpu: bool = True, cell_factory=None) -> int:
    args = parse(argv)
    if importlib.util.find_spec("repro") is None:
        log("bench: the system under test (src/repro) is not in this checkout")
        return 2
    try:
        from bench.cell import Cell, workload_entry

        entry = workload_entry(args.workload)
    except (OSError, KeyError) as e:
        log(f"bench: {e}")
        return 2

    import jax

    enable_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        log(f"bench: JAX found no TPU (platform {platform!r}); this benchmark runs only on a TPU")
        return 2
    if len(devices) < int(entry["chips"]):
        log(f"bench: the cell needs {entry['chips']} chips, JAX sees {len(devices)}")
        return 2

    cell = (cell_factory or Cell)(args.workload, args.seed, log)
    cell.setup()
    setup_s = time.perf_counter() - T_START
    trace_dir = os.path.join(HERE, "out", "trace") if args.trace else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    res = cell.window(args.seconds, trace_dir)
    device = dict(platform=platform, kind=devices[0].device_kind,
                  count=int(entry["chips"]),
                  memory_peak_bytes=res["memory_peak_bytes"])
    out = dict(correct=False, attempted=res["attempted"], failed=res["failed"])
    if args.trace:
        from bench import trace_reduce

        summary = trace_reduce.reduce_dir(trace_dir)
        cell.record.trace = summary
        metrics = read_per_layer(cell.record, args.workload)
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        out["breakdown"] = dict(device_ops=summary["top_programs"],
                                idle_gaps=summary["idle_gaps"])
    else:
        values = dict(res, setup_s=setup_s)
        metrics = {m["name"]: dict(value=float(values[m["name"]]), unit=m["unit"])
                   for m in metrics_for("end_to_end", args.workload)
                   if values.get(m["name"]) is not None}
    for k, v in cell.phases.items():
        log(f"[setup] {k} {v:.3f} s")
    log(f"[setup] total {setup_s:.3f} s")
    cell.free_program()
    t = time.perf_counter()
    got = cell.check()
    log(f"[check] reference over {got['n_requests']} requests, "
        f"{got['n_tokens']} served tokens: {time.perf_counter() - t:.3f} s")
    log(f"[check] served tokens: widest gap {got['gap']!r}, mean gap {got['gap_mean']!r}, "
        f"share not the reference's first choice {got['flip_share']!r}; "
        f"control {cell.cell['control']}: widest gap {got['control_gap']!r}, "
        f"mean gap {got['control_gap_mean']!r}")
    out["correct"], checks = judge(got, res["failed"], cell.cell["limits"])
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

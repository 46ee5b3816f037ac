#!/usr/bin/env python3
"""Readings from which a cell's ``logit_gap_share`` limit is set.

For each seed, in one process that holds the chip: build the cell, serve a
short window at the cell's own load, then compare the sampled served tokens
with the plain reference, as a run's check does (the program's reading).
For each control seed the same, with the program serving from its weights
rounded to the dtype of the cell's ``control``.  The benchmark's own runs
never run these.

Beside the cell's control, the reference also runs the sides in ``SIDES``
on the same prompts and tokens: its shares say how far plain arithmetic at
each precision lies from the float32 reference, against the control.

    python3 bench/calibrate.py --workload smollm-360m.doc-reuse \\
        --seeds 11,12,13 --control-seeds 21,22,23 --seconds 10

Prints one JSON line per run and a last line with, for each number, the
largest program reading (``lower``) and the smallest control reading
(``upper``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

F8 = "float8_e4m3fn"
BF16 = "bfloat16"
# name: (weights dtype, (matmul operands, stored values)); see dense_lm.F32
SIDES = {
    "bf16": (BF16, (None, BF16)),  # the served precision, plainly computed
    "w8": (F8, (None, None)),  # float8 weights, float32 arithmetic
    "all8": (F8, (F8, F8)),  # float8 operands and stored values
}
NUMBERS = ("gap_share", "gap_mean", "gap_sq_mean", "gap", "flip_share",
           *(f"{s}_share" for s in SIDES))


def seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", default="", help="comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    import jax

    from bench.cell import Cell
    from bench.run import enable_cache, log

    enable_cache()
    if jax.devices()[0].platform != "tpu":
        log("calibrate: JAX found no TPU")
        return 2
    rows = {False: [], True: []}
    runs = [(s, False) for s in seeds(args.seeds)] + [(s, True) for s in seeds(args.control_seeds)]
    for seed, control in runs:
        cell = Cell(args.workload, seed, log, control=control)
        cell.setup()
        res = cell.window(args.seconds, None)
        cell.free_program()
        got = cell.check(SIDES)
        row = dict(seed=seed, control=control, attempted=res["attempted"],
                   failed=res["failed"], **got)
        rows[control].append(row)
        print(json.dumps(row), flush=True)
        del cell
    summary = {"seeds": len(rows[False]), "control_seeds": len(rows[True])}
    for name in NUMBERS:
        summary[name] = dict(
            lower=max((r[name] for r in rows[False]), default=None),
            upper=min((r[name] for r in rows[True]), default=None))
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Record ``data/serve_spans.xplane.pb`` on a TPU: one wave of the
``smollm-360m.doc-reuse`` cell at published widths, cut to one client, one
document of one 256-token chunk and two output tokens, traced with the
benchmark's own profiler options and ``bench.*`` spans around the
program's.  Set-up and warm-up run before the trace starts.

    python3 bench/tests/record_span_trace.py <output .xplane.pb> [--seed N]
"""
import argparse
import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

OVERRIDES = {
    "traffic": {"doc_chunks": [1, 1], "output_tokens": [2, 2]},
    "cell": {"clients": 1, "documents": 1},
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("out")
    ap.add_argument("--seed", type=int, default=13)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from bench.cell import Cell
    from bench.run import enable_cache

    if jax.devices()[0].platform != "tpu":
        print("record_span_trace: needs a TPU", file=sys.stderr)
        return 2
    enable_cache()
    cell = Cell("smollm-360m.doc-reuse", args.seed,
                lambda m: print(m, file=sys.stderr), overrides=OVERRIDES)
    cell.setup()
    cell.probe.annotate = True
    wave = cell._new_wave(np.random.default_rng([args.seed, 2]))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.wave"):
        cell._run_wave(wave)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
    shutil.copy(path, args.out)
    print(f"{args.out}: {os.path.getsize(args.out)} bytes", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The generation step's share of its roofline: the least time to read the
weights and each active row's cached K and V (and compute its tokens) at
the chip's peaks, over the step program's device time."""

from bench import costs, trace_reduce
from bench.peaks import peaks

PROGRAM = r"^jit_+decode_rows_impl$"


def read(rec):
    if rec.trace is None or not rec.step_lengths:
        return None
    s = trace_reduce.seconds_matching(rec.trace["programs"], PROGRAM) / rec.trace["n_devices"]
    if not s:
        return None
    least = sum(costs.least_seconds(costs.decode_step(rec.cfg, lengths), peaks(rec.device_kind))
                for lengths in rec.step_lengths)
    return 100.0 * least / s

"""The whole generation step's share of the chip's peak FLOP/s: model
operations of the active rows over the step program's device time."""

from bench import costs, trace_reduce
from bench.peaks import peaks

PROGRAM = r"^jit_+decode_rows_impl$"


def read(rec):
    if rec.trace is None or not rec.step_lengths:
        return None
    s = trace_reduce.seconds_matching(rec.trace["programs"], PROGRAM) / rec.trace["n_devices"]
    if not s:
        return None
    flops = sum(costs.decode_step(rec.cfg, lengths)["flops"] for lengths in rec.step_lengths)
    return 100.0 * flops / (s * peaks(rec.device_kind)["flops_bf16"])

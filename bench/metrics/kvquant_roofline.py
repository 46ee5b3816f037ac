"""The token kernels' share of their roofline: the least time for their
bytes and operations at the chip's peaks over their device time."""

from bench import costs, trace_reduce
from bench.peaks import peaks

KERNELS = r"^kv_(lossless|dequant)_tokens_pallas$"


def read(rec):
    if rec.trace is None:
        return None
    s = trace_reduce.seconds_matching(rec.trace["ops"], KERNELS) / rec.trace["n_devices"]
    if not s:
        return None
    work = costs.token_kernels(rec.cfg, rec.codec, rec.chunk_tokens,
                               rec.chunks_lossless, rec.chunks_lossy)
    return 100.0 * costs.least_seconds(work, peaks(rec.device_kind)) / s

"""The load path's share of the chip's peak FLOP/s: the token kernels'
operations over the device time of every load program (rANS scans,
assemble, insert)."""

from bench import costs, trace_reduce
from bench.peaks import peaks

PROGRAMS = r"^jit_+(decode_impl|assemble_chunks|insert_codec_runs)$"


def read(rec):
    if rec.trace is None:
        return None
    s = trace_reduce.seconds_matching(rec.trace["programs"], PROGRAMS) / rec.trace["n_devices"]
    if not s:
        return None
    work = costs.token_kernels(rec.cfg, rec.codec, rec.chunk_tokens,
                               rec.chunks_lossless, rec.chunks_lossy)
    return 100.0 * work["flops"] / (s * peaks(rec.device_kind)["flops_bf16"])

"""Device milliseconds of the codec's decode programs (the rANS scans and
the assemble with the token kernels) per chunk decoded."""

from bench import trace_reduce

PROGRAMS = r"^jit_+(decode_impl|assemble_chunks)$"


def read(rec):
    chunks = rec.chunks_lossless + rec.chunks_lossy
    if rec.trace is None or not chunks:
        return None
    s = trace_reduce.seconds_matching(rec.trace["programs"], PROGRAMS)
    return s * 1e3 / chunks / rec.trace["n_devices"] if s else None

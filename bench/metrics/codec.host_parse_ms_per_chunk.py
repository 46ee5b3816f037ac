"""Host milliseconds parsing bitstreams before the rANS scans (the
program's ``codec.parse`` spans: header, checksum and stream unpacking)
per chunk decoded."""

from bench import spans


def read(rec):
    table = spans.of(rec)
    chunks = rec.chunks_lossless + rec.chunks_lossy
    if not table or "codec.parse" not in table or not chunks:
        return None
    return table["codec.parse"]["s"] * 1e3 / chunks

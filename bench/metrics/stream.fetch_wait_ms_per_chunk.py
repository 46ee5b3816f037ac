"""Host milliseconds spent waiting on fetch handles (the program's
``stream.fetch_wait`` spans) per chunk fetched."""

from bench import spans


def read(rec):
    table = spans.of(rec)
    chunks = rec.chunks_lossless + rec.chunks_lossy
    if not table or "stream.fetch_wait" not in table or not chunks:
        return None
    return table["stream.fetch_wait"]["s"] * 1e3 / chunks

"""Stored bytes of every fetched chunk at the level Algorithm 1 chose for
it, per context token served."""


def read(rec):
    return rec.wire_bytes / rec.context_tokens if rec.context_tokens else None

"""Scheduler load rounds per request (the program's ``n_rounds``)."""


def read(rec):
    return rec.n_rounds / rec.n_requests if rec.n_requests else None

"""Rows stacked in each generation step: generated tokens over steps."""


def read(rec):
    return rec.n_gen_tokens / rec.n_gen_steps if rec.n_gen_steps else None

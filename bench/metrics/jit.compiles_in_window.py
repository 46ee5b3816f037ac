"""Programs built inside the measured window, compiled or loaded from the
persistent compilation cache (``jax.monitoring`` events); a warmed-up run
builds none."""


def read(rec):
    return rec.compiles_in_window

"""Plain references, one module per architecture.

A configuration names its module by its ``reference`` key, and everything
the benchmark knows of an architecture comes from that module,
``bench.reference.<reference>``.  For a configuration file's dict ``cfg``
the module gives:

- ``make_key(seed)``: a PRNG key from any non-negative seed;
- ``init_params(cfg, key)``: the served weights, in the program's parameter
  tree and served dtype (called under ``jax.jit``: one program);
- ``served_gaps(params, cfg, requests, codec, calib_tokens, *, max_tokens,
  sides)``: the served tokens' logit gaps below the reference's best, and
  those of each side (a weights dtype and numerics) put in its place;
- ``F32``: the numerics of the plain reference, as ``sides`` take them;
- ``program_fields(cfg)``: the program's ``ArchConfig`` fields that the
  configuration's keys imply; the cell puts them over the ``registry``
  entry, so a cut to the chip's share, written in those keys and listed in
  ``reduced``, reaches the program and the reference alike;
- for ``bench/costs.py``, the least work of the served path:
  ``decode_step(cfg, lengths)``, the FLOPs and HBM bytes of one decode step
  of rows holding ``lengths`` tokens (the experts a step routes to, the
  cache it reads), and ``token_block(cfg)``, the (layer, stream) rows and
  the channels of one stored chunk.

A module imports nothing of the program and takes nothing the program made.
"""
from __future__ import annotations

import importlib


def load(name: str, config_file: str = "the configuration"):
    """The reference module ``name``; exits, naming the module and
    ``config_file``, where there is none."""
    module = f"{__name__}.{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise SystemExit(f"{config_file}: reference {name!r}: there is no module {module}") from None

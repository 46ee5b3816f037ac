"""A cell small enough for a CPU: smollm-360m's family at the program's
``-tiny`` sizes, short documents, few clients."""

TINY = {
    "config": {
        "registry": "smollm-360m-tiny", "num_hidden_layers": 4, "hidden_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "intermediate_size": 256, "vocab_size": 512, "capacity": 512,
        # four random layers with unit-gain attention all but ignore their
        # context, so no fault of the cache would change a served token;
        # sharper attention makes the tokens depend on what the cache holds
        "random_init": {"embed_std": 0.02, "qk_gain": 2.0},
    },
    "traffic": {
        "chunk_tokens": 64, "doc_chunks": [1, 3], "output_tokens": [4, 8],
        "calibration_tokens": 64, "max_run_tokens": 64,
    },
    "cell": {"clients": 4, "documents": 2, "check_tokens": 40},
}
WORKLOAD = "smollm-360m.doc-reuse"


def tiny_cell(control=False, **extra):
    """A ``cell_factory`` for ``bench.run.main`` that builds the tiny cell;
    ``extra`` adds overrides (for example a cell's limit), ``control`` serves
    from weights rounded to the dtype of the cell's ``control``."""
    from bench.cell import Cell

    over = {k: dict(v, **extra.get(k, {})) for k, v in TINY.items()}

    def make(workload, seed, log):
        return Cell(workload, seed, log, overrides=over, control=control)

    return make

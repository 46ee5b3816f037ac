"""Host milliseconds per generation step outside the step's device wait:
the self time of the program's ``sched.gen_step`` spans, which leaves out
the nested ``sched.logits_sync`` (and, under the benchmark's probe, the
``bench.decode_step_rows`` call that syncs on the logits first)."""

from bench import spans


def read(rec):
    table = spans.of(rec)
    if not table or "sched.gen_step" not in table:
        return None
    steps = table["sched.gen_step"]
    return steps["self_s"] * 1e3 / steps["n"]

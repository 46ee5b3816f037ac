"""Host milliseconds of scheduler work per load round: the self time of
the program's ``sched.admit``, ``sched.round``, ``stream.step`` and
``stream.decide`` spans, over the ``sched.round`` count.  Self time leaves
out every span nested in them: the fetch waits and bitstream parses, which
have metrics of their own, and the codec, insert and completion calls,
where the host can wait on the device."""

from bench import spans

HOST = ("sched.admit", "sched.round", "stream.step", "stream.decide")


def read(rec):
    table = spans.of(rec)
    if not table or "sched.round" not in table:
        return None
    host = sum(table[k]["self_s"] for k in HOST if k in table)
    return host * 1e3 / table["sched.round"]["n"]

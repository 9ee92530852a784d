"""``rollup_ms``: device time of the per-chunk rollup's summary fold
(``jit__chunk_summary``: the KLL, HLL and top-k fold of the chunk), per
keyed chunk step, over the executions of ``jit_update_chunk`` inside the
traced window.  The rollup store's observation runs as an anonymous
``jit__lambda`` program, a name that any jitted lambda shares, so it is
left to the breakdown."""

PROGRAM = "jit__chunk_summary"
PER = "jit_update_chunk"


def read(rec):
    progs = (rec.get("trace") or {}).get("programs", {})
    steps = progs.get(PER, {}).get("runs", 0)
    if not steps or PROGRAM not in progs:
        return None
    return 1e3 * progs[PROGRAM]["seconds"] / steps

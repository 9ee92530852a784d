"""``keyed_step_ms``: device time of one keyed chunk step, the program
``jit_update_chunk`` (``KeyedWindowStore.update_chunk`` with event
times), averaged over its executions inside the traced window."""

PROGRAM = "jit_update_chunk"


def read(rec):
    p = (rec.get("trace") or {}).get("programs", {}).get(PROGRAM)
    if not p or not p["runs"]:
        return None
    return 1e3 * p["seconds"] / p["runs"]

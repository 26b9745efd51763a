"""Device time of one batched decode step, in ms: the device seconds of the
paged engine's decode program (``trace.modules["jit_decode_multi"]``) in
the traced window over the decode steps dispatched in it, the sum of ``k``
over its ``engine.decode_dispatch`` spans.  Nothing where no trace was
taken or the window dispatched no decode."""
PROGRAM = "jit_decode_multi"
SPAN = "engine.decode_dispatch"


def read(rec):
    tr = rec["trace"]
    if not tr:
        return None
    program = tr["modules"].get(PROGRAM)
    steps = tr["spans"].get(SPAN, {}).get("args", {}).get("k")
    if not program or not steps:
        return None
    return 1e3 * program["seconds"] / steps

"""Device time of one prefill chunk, in ms: the device seconds of the paged
engine's prefill program (``trace.modules["jit_prefill_paged"]``) in the
traced window over its events there.  Nothing where no trace was taken or
the window ran no prefill."""
PROGRAM = "jit_prefill_paged"


def read(rec):
    tr = rec["trace"]
    program = tr["modules"].get(PROGRAM) if tr else None
    if not program:
        return None
    return 1e3 * program["seconds"] / program["count"]

"""Decode model steps per jitted decode dispatch (``stats.decode_steps`` /
``stats.decode_dispatches``): 1 when fusion never engages, up to the
engine's ``fused_steps``."""


def read(rec):
    st = rec["stats"]
    if not st or not st["decode_dispatches"]:
        return None
    return st["decode_steps"] / st["decode_dispatches"]

"""Percent of the KV arena's usable blocks (all but the junk block) in use
at the session's peak (``stats.blocks_peak``)."""


def read(rec):
    st = rec["stats"]
    if not st:
        return None
    return 100.0 * st["blocks_peak"] / (rec["cfg"]["engine"]["n_blocks"] - 1)

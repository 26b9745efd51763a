"""90th percentile, over the requests of the window that got a first token,
of the engine's prefill wait: seconds from admission to the dispatch of the
last prefill chunk (``stats.prefill_wait_p90_s``).  None where the engine
does not report it."""


def read(rec):
    st = rec["stats"]
    return st.get("prefill_wait_p90_s") if st else None

"""90th percentile, over the requests of the window that got a first token,
of the engine's first-token lag: seconds from the dispatch of the last
prefill chunk to the first token's value reaching the host
(``stats.first_lag_p90_s``).  None where the engine does not report it."""


def read(rec):
    st = rec["stats"]
    return st.get("first_lag_p90_s") if st else None

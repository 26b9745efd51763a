"""90th percentile, over the completed requests of the window with two or
more tokens, of (last token - first token) / (tokens - 1), in ms, as the
client received them."""
from record import percentile


def read(rec):
    return percentile([1e3 * (r["times"][-1] - r["times"][0])
                       / (len(r["times"]) - 1)
                       for r in rec["requests"]
                       if r["done"] and len(r["times"]) > 1], 90)

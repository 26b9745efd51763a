"""90th percentile, over every request sent in the window, of the seconds
from its scheduled arrival to its first token reaching the client.  A
request that never produced a token counts with the time until the drain
ended, so failures sit in the tail."""
from record import percentile


def read(rec):
    end = rec["closed_s"]
    return percentile([(r["times"][0] if r["times"] else end) - r["t_s"]
                       for r in rec["requests"]], 90)

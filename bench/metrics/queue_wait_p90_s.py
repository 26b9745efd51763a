"""90th percentile of the engine's queue delay (arrival to admission), over
the requests of the window that completed."""
from record import percentile


def read(rec):
    return percentile([r["queue_delay_s"] for r in rec["responses"]], 90)

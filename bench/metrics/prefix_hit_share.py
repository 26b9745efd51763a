"""Percent of admitted prompt tokens served by the radix prefix cache:
``stats.prefix_hit_tokens`` over the prompt tokens of the completed
requests."""
from record import hit_share


def read(rec):
    h = hit_share(rec)
    return None if h is None else 100.0 * h

"""Tokens that reached the clients inside the window, over the window."""


def read(rec):
    T = rec["seconds"]
    return sum(1 for r in rec["requests"] for t in r["times"] if t < T) / T

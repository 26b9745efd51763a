"""What one run leaves for the per-layer readers (``bench/metrics``).

A run record is a dict:

* ``cfg``, ``m``: the configuration file and its model dimensions
  (``models/<family>.dims``);
* ``family``: the configuration's family module (``models/<family>.py``),
  whose ``prefill`` and ``decode_token`` count the model's FLOPs;
* ``chips``: the chips the engine spans;
* ``seconds``: the measured window;
* ``requests``: one dict per request sent in the window: ``t_s`` (scheduled
  arrival), ``prompt_len``, ``n_out``, ``times`` (seconds after the window
  opened at which each token reached the client) and ``done``;
* ``responses``: the engine's responses, as dicts of their fields;
* ``stats``: ``RealEngine.stats()`` of the session (None if it never
  drained);
* ``trace``: ``trace_reduce.reduce`` of the traced window (None without a
  trace);
* ``peaks``: the device's row of ``peaks.json``.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """Nearest-rank percentile; None for no values."""
    if not values:
        return None
    v = sorted(values)
    return float(v[max(math.ceil(p / 100.0 * len(v)) - 1, 0)])


def prompt_tokens(rec) -> int:
    return sum(r["prompt_len"] for r in rec["requests"] if r["done"])


def hit_share(rec) -> Optional[float]:
    """Share of admitted prompt tokens that the prefix cache served."""
    st, total = rec["stats"], prompt_tokens(rec)
    if st is None or not total:
        return None
    return st["prefix_hit_tokens"] / total

"""The per-layer readers of the engine's TTFT split: each reads its number
from the engine's stats in a run record, and reads nothing where the
program under test does not report it."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_smoke import RUN  # noqa: E402

# reader -> the engine's stats key it reads
READS = {"prefill_wait_p90_s": "prefill_wait_p90_s",
         "first_token_lag_p90_s": "first_lag_p90_s"}


def record(stats):
    return {"responses": [{"rid": 0, "queue_delay_s": 0.1}], "stats": stats,
            "trace": None}


@pytest.mark.parametrize("name", sorted(READS))
def test_ttft_split_reader_reads_the_engine_stats(name):
    read = RUN.reader(name).read
    stats = {"served": 10, "prefill_wait_p90_s": 0.09,
             "first_lag_p90_s": 0.48, "queue_delay_p95_s": 0.3}
    assert read(record(stats)) == stats[READS[name]]


@pytest.mark.parametrize("name", sorted(READS))
def test_ttft_split_reader_reads_nothing_without_the_split(name):
    read = RUN.reader(name).read
    # an engine without the split, a session that never drained, and one
    # in which no request got a first token
    assert read(record({"served": 10, "queue_delay_p95_s": 0.3})) is None
    assert read(record(None)) is None
    assert read(record({"served": 0, READS[name]: None})) is None

"""The per-layer readers of the engine's TTFT split: each reads its number
from the engine's stats in a run record, and reads nothing where the
program under test does not report it.  ``step_mfu`` holds the work to the
peak of every chip used, and ``collective_share`` reads the trace's
collective time."""
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


def traced(chips, busy_s=2.0, collective_s=0.5):
    m = {"L": 2, "d": 64, "H": 4, "K": 2, "dh": 16, "ff": 128, "V": 256}
    req = {"prompt_len": 100, "times": [0.1, 0.2, 0.3], "done": True}
    return {"m": m, "seconds": 1.0, "chips": chips, "stats": None,
            "requests": [req], "peaks": {"bf16_flops_per_s": 1e9},
            "trace": {"busy_s": busy_s, "window_s": 4.0,
                      "collective_s": collective_s}}


def test_step_mfu_holds_the_work_to_every_chip_used():
    read = RUN.reader("step_mfu").read
    one, two = read(traced(1)), read(traced(2))
    assert one > 0 and two == pytest.approx(one / 2)
    assert RUN.reader("step_mfu.ttft").read(traced(2)) == two


def test_collective_share_of_busy_time():
    read = RUN.reader("collective_share").read
    assert read(traced(4)) == pytest.approx(25.0)
    # no trace, or one chip: nothing to read
    assert read(dict(traced(4), trace=None)) is None
    assert read(traced(1)) is None
    # a mesh whose trace shows no collective reads 0, which a sharded model
    # cannot do: the fault shows in the result line
    assert read(traced(4, collective_s=0.0)) == 0.0

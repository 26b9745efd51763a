"""The per-layer readers of the engine's TTFT split: each reads its number
from the engine's stats in a run record, and reads nothing where the
program under test does not report it.  ``step_mfu`` holds the work to the
peak of every chip used, and ``collective_share`` reads the trace's
collective time.  On a record built round the trace fixture every reader
that was there before the trace kept device programs and program spans
reads what it read then, and the readers of those read them."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_smoke import BENCH, RUN, read_json, trace_fixture  # noqa: E402
from models import dense_lm  # noqa: E402
import trace_reduce  # noqa: E402

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
    return {"m": m, "family": dense_lm, "seconds": 1.0, "chips": chips,
            "stats": None,
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


def fixture_record(chips, reduce=trace_reduce.reduce):
    """A run record of qwen3-1.7b round the trace fixture's 10 ms window,
    on ``chips`` of its device planes: two requests done, one not, and the
    engine's stats."""
    cfg = read_json(os.path.join(BENCH, "configs", "qwen3-1.7b.json"))
    return {
        "cfg": cfg, "m": dense_lm.dims(cfg), "family": dense_lm,
        "chips": chips, "seconds": 0.01,
        "requests": [
            {"rid": 0, "t_s": 0.0, "prompt_len": 100, "n_out": 4,
             "times": [0.001, 0.002, 0.003, 0.0045], "done": True},
            {"rid": 1, "t_s": 0.002, "prompt_len": 300, "n_out": 3,
             "times": [0.006, 0.008, 0.012], "done": True},
            {"rid": 2, "t_s": 0.005, "prompt_len": 50, "n_out": 8,
             "times": [], "done": False}],
        "responses": [{"rid": 0, "queue_delay_s": 0.0004},
                      {"rid": 1, "queue_delay_s": 0.0011}],
        "stats": {"decode_steps": 40, "decode_dispatches": 7,
                  "prefix_hit_tokens": 96, "blocks_peak": 300,
                  "prefill_wait_p90_s": 0.0021, "first_lag_p90_s": 0.0017},
        "closed_s": 0.02, "setup_s": 21.5, "lateness": [0.0],
        # a peak that keeps so small a window's work under 100%
        "peaks": {"bf16_flops_per_s": 1e15},
        "trace": reduce(trace_fixture(), chips)}


# What each reader of the parent commit read on ``fixture_record``.
PARENT = {
    1: {"ttft_p90_s": 0.015, "tpot_p90_ms": 3.0, "output_tokens_per_s": 600.0,
        "setup_s": 21.5, "queue_wait_p90_s": 0.0011, "prefix_hit_share": 24.0,
        "steps_per_dispatch": 5.714285714285714,
        "kv_blocks_peak_share": 21.428571428571427,
        "step_mfu": 22.0709134336, "step_mfu.ttft": 22.0709134336,
        "device_idle_share": 60.0, "prefill_wait_p90_s": 0.0021,
        "first_token_lag_p90_s": 0.0017, "collective_share": None},
    2: {"ttft_p90_s": 0.015, "tpot_p90_ms": 3.0, "output_tokens_per_s": 600.0,
        "setup_s": 21.5, "queue_wait_p90_s": 0.0011, "prefix_hit_share": 24.0,
        "steps_per_dispatch": 5.714285714285714,
        "kv_blocks_peak_share": 21.428571428571427,
        "step_mfu": 6.305975266742857, "step_mfu.ttft": 6.305975266742857,
        "device_idle_share": 30.000000000000004, "prefill_wait_p90_s": 0.0021,
        "first_token_lag_p90_s": 0.0017, "collective_share": 0.0},
}


@pytest.mark.parametrize("chips,name", [(c, n) for c in sorted(PARENT)
                                        for n in sorted(PARENT[c])])
def test_accepted_readers_read_as_before_on_the_fixture(chips, name):
    assert RUN.reader(name).read(fixture_record(chips)) == PARENT[chips][name]


def test_decode_step_and_prefill_chunk_read_the_programs():
    rec = fixture_record(1)
    # decode: 2 ms of jit_decode_multi over 8 + 2 + 1 steps dispatched;
    # prefill: 2 ms of jit_prefill_paged in one event
    decode = RUN.reader("decode_step_ms").read(rec)
    assert decode == pytest.approx(2.0 / 11)
    assert RUN.reader("prefill_chunk_ms").read(rec) == pytest.approx(2.0)
    # a step's device time, times the steps, fits the busy time
    assert decode * 11 <= 1e3 * rec["trace"]["busy_s"] + 1e-9


@pytest.mark.parametrize("name", ["decode_step_ms", "prefill_chunk_ms"])
def test_program_readers_read_nothing_without_their_program(name):
    read = RUN.reader(name).read
    rec = fixture_record(1)
    assert read(dict(rec, trace=None)) is None
    empty = dict(rec["trace"], modules={}, spans={})
    assert read(dict(rec, trace=empty)) is None

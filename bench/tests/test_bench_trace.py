"""trace_reduce on a small recorded trace: busy time is the union of device
operations inside the window span, idle time is named by the host span that
overlaps each gap most, the ranking sums each operation's time, and
collective time is the union of the collectives' intervals."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import trace_reduce  # noqa: E402


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    with open(os.path.join(BENCH, "tests", "fixtures",
                           "trace_small.pbtxt")) as f:
        text = "".join(l for l in f if not l.lstrip().startswith("#"))
    return ProfileData.from_text_proto(text)


def test_busy_and_window_on_one_chip(profile):
    r = trace_reduce.reduce(profile, n_devices=1)
    # busy: [0, 2] + [3, 4.5] + [9.5, 10] = 4 ms of a 10 ms window
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.004)


def test_operations_ranked_by_time_inside_the_window(profile):
    r = trace_reduce.reduce(profile, n_devices=1)
    # named by instruction and result shape; the loop around two of them
    # is left out, so nothing counts twice
    secs = dict(r["device_ops"])
    assert list(secs) == ["%fusion.1 = f32[28,1401,16,8,128]",
                          "%convolution.2 = bf16[32,2048]", "%copy.3 = s32[8]"]
    assert list(secs.values()) == [pytest.approx(0.003), pytest.approx(0.001),
                                   pytest.approx(0.0005)]


def test_idle_gaps_named_by_the_host_span_overlapping_most(profile):
    r = trace_reduce.reduce(profile, n_devices=1)
    # gap [2, 3]: step covers all of it; gap [4.5, 9.5]: sleep 3.5 ms,
    # submit 0.5 ms, a span not the harness's 0.1 ms
    assert r["idle_gaps"] == [["bench.sleep", pytest.approx(0.005)],
                              ["bench.step", pytest.approx(0.001)]]


def test_busy_is_averaged_over_the_chips_used(profile):
    r = trace_reduce.reduce(profile, n_devices=2)
    assert r["busy_s"] == pytest.approx((0.004 + 0.010) / 2)


def test_no_window_or_no_device_reads_nothing():
    from jax.profiler import ProfileData
    host_only = ProfileData.from_text_proto(
        'planes { id: 1 name: "/host:CPU" lines { id: 1 name: "python" '
        'timestamp_ns: 1 events { metadata_id: 1 duration_ps: 1000 } } '
        'event_metadata { key: 1 value { id: 1 name: "bench.window" } } }')
    assert trace_reduce.reduce(host_only) is None


def ops_plane(pid, ops):
    """A device plane whose "XLA Ops" line holds ``ops``: (name, start ms,
    duration ms)."""
    names = sorted({n for n, _, _ in ops})
    events = "".join(
        f"events {{ metadata_id: {names.index(n) + 1} offset_ps: {int(s * 1e9)}"
        f" duration_ps: {int(d * 1e9)} }} " for n, s, d in ops)
    meta = "".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                   f'name: "{n}" }} }} ' for i, n in enumerate(names))
    return (f'planes {{ id: {pid} name: "/device:TPU:{pid - 2}" lines {{ id: 1 '
            f'name: "XLA Ops" timestamp_ns: 1000000 {events}}} {meta}}} ')


def test_collective_time_is_the_union_of_collectives_in_the_window():
    from jax.profiler import ProfileData
    host = ('planes { id: 1 name: "/host:CPU" lines { id: 1 name: "python" '
            'timestamp_ns: 1000000 events { metadata_id: 1 duration_ps: '
            '10000000000 } } event_metadata { key: 1 value { id: 1 name: '
            '"bench.window" } } } ')
    ar = ("%all-reduce-start.3 = bf16[32,4096]{1,0} "
          "all-reduce-start(bf16[32,4096]{1,0} %x)")
    done = ("%all-reduce-done.3 = bf16[32,4096]{1,0} "
            "all-reduce-done(bf16[32,4096]{1,0} %ar)")
    ag = ("%all-gather-fusion.1 = f32[8,128]{1,0} "
          "fusion(f32[4,128]{1,0} %y), kind=kOutput")
    user = ("%fusion.9 = f32[8]{0} "
            "fusion(bf16[32,4096]{1,0} %all-reduce.2), kind=kLoop")
    pd = ProfileData.from_text_proto(host + ops_plane(2, [
        (user, 0, 2), (ar, 2, 1), (done, 2.5, 1), (user, 4, 1), (ag, 9, 3)])
        + ops_plane(3, [(ag, 0, 1), (user, 1, 4)]))
    r = trace_reduce.reduce(pd, n_devices=2)
    # chip 0: [2, 3.5] and [9, 10] inside the window; chip 1: [0, 1]; an
    # operation that only reads a collective's result does not count
    assert r["collective_s"] == pytest.approx((0.0025 + 0.001) / 2)
    assert r["busy_s"] == pytest.approx((0.0055 + 0.005) / 2)


def test_a_trace_without_collectives_reads_none_of_their_time(profile):
    assert trace_reduce.reduce(profile, n_devices=2)["collective_s"] == 0.0

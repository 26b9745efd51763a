"""trace_reduce on a small recorded trace: busy time is the union of device
operations inside the window span, idle time is named by the host span that
overlaps each gap most, the ranking sums each operation's time, and
collective time is the union of the collectives' intervals.  For the
per-layer readers it keeps every operation, kernels by name, each device
program's time and events, and each program span's count, time and summed
arguments, all inside the window."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_smoke import trace_fixture  # noqa: E402
import trace_reduce  # noqa: E402


@pytest.fixture(scope="module")
def profile():
    return trace_fixture()


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


# What the parent of the per-layer keys read on this fixture, on one chip
# and on two: the keys that the breakdown prints and accepted metrics read
# stay bit for bit.
PARENT = {
    1: {"busy_s": 0.004, "window_s": 0.01, "collective_s": 0.0,
        "device_ops": [["%fusion.1 = f32[28,1401,16,8,128]", 0.003],
                       ["%convolution.2 = bf16[32,2048]", 0.001],
                       ["%copy.3 = s32[8]", 0.0005]],
        "idle_gaps": [["bench.sleep", 0.005], ["bench.step", 0.001]]},
    2: {"busy_s": 0.007, "window_s": 0.01, "collective_s": 0.0,
        "device_ops": [["fusion.7", 0.005],
                       ["%fusion.1 = f32[28,1401,16,8,128]", 0.0015],
                       ["%convolution.2 = bf16[32,2048]", 0.0005],
                       ["%paged_attention.5 = bf16[8,16,128]", 0.0005],
                       ["%copy.3 = s32[8]", 0.00025]],
        "idle_gaps": [["bench.sleep", 0.0025], ["bench.step", 0.0005]]},
}


@pytest.mark.parametrize("chips", sorted(PARENT))
def test_printed_and_accepted_keys_read_as_before(profile, chips):
    r = trace_reduce.reduce(profile, n_devices=chips)
    assert {k: r[k] for k in PARENT[chips]} == PARENT[chips]


def test_modules_are_device_time_and_events_by_program(profile):
    r = trace_reduce.reduce(profile, n_devices=1)
    # prefill [-0.5, 2] counts its 2 ms inside the window; decode [3, 4.5]
    # and [9.5, 11] count 1.5 + 0.5 ms, two events; ids dropped from names
    assert r["modules"] == {
        "jit_prefill_paged": {"seconds": pytest.approx(0.002), "count": 1},
        "jit_decode_multi": {"seconds": pytest.approx(0.002), "count": 2}}
    # every program inside the window, as busy time is here
    assert sum(m["seconds"] for m in r["modules"].values()) == \
        pytest.approx(r["busy_s"])
    # averaged over the chips used: the second has no program line
    two = trace_reduce.reduce(profile, n_devices=2)["modules"]
    assert two["jit_decode_multi"] == {"seconds": pytest.approx(0.001),
                                       "count": 1}


def test_program_spans_count_time_and_sum_their_arguments(profile):
    r = trace_reduce.reduce(profile, n_devices=1)
    # the dispatch before the window is left out, the one across its end
    # counts its 0.2 ms inside; a text argument is not summed, and the
    # harness's own spans are not program spans
    assert r["spans"] == {"engine.decode_dispatch": {
        "count": 3, "seconds": pytest.approx(0.0006),
        "args": {"k": 11, "rows": 7}}}


def test_ops_hold_every_operation_and_kernels_by_name(profile):
    one = trace_reduce.reduce(profile, n_devices=1, top=1)
    assert len(one["device_ops"]) == 1
    assert one["ops"] == {"%fusion.1 = f32[28,1401,16,8,128]":
                          pytest.approx(0.003),
                          "%convolution.2 = bf16[32,2048]": pytest.approx(0.001),
                          "%copy.3 = s32[8]": pytest.approx(0.0005)}
    two = trace_reduce.reduce(profile, n_devices=2)["ops"]
    # the second chip's Pallas kernel, under its instruction and its name
    assert two["%paged_attention.5 = bf16[8,16,128]"] == pytest.approx(0.0005)
    assert two["paged_attention"] == pytest.approx(0.0005)
    assert two["fusion.7"] == pytest.approx(0.005)
    assert len(two) == 6


def test_kernel_name_of_a_custom_call():
    plain = ('%flash_fwd.12 = bf16[8,128]{1,0} custom-call(bf16[8,128]{1,0} '
             '%x), custom_call_target="tpu_custom_call"')
    assert trace_reduce.kernel_name(plain) == "flash_fwd"
    named = plain + ', kernel_name="paged_decode"'
    assert trace_reduce.kernel_name(named) == "paged_decode"
    assert trace_reduce.kernel_name(
        "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %custom-call.2)") is None
    # XLA's own custom calls carry no kernel name
    assert trace_reduce.kernel_name(
        '%custom-call.20 = s32[8,64]{1,0} custom-call(f32[8,64]{1,0} %x), '
        'custom_call_target="TopK"') is None
    assert trace_reduce.module_name("jit_decode_multi(123)") == \
        "jit_decode_multi"

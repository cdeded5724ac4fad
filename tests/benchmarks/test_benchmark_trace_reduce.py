"""The trace reduction on a synthesised capture whose lines overlap the
way a TPU's do: steps contain modules contain operations, on two device
planes, beside a host plane. Pins ``0 < busy_s <= window_s``, union and
not sum, operations clipped to the window, and that a device without
events (in the capture, or inside the window) is an error."""

import pytest

from benchmarks.lib import trace_reduce
from benchmarks.lib.trace_reduce import TraceError

MS = 1_000_000_000  # picoseconds in a millisecond
T0 = 1_000_000        # the lines' timestamp_ns: an event's offset counts from it


def window(lo_ms, hi_ms):
    """A window in the capture's nanoseconds, given in the events'
    milliseconds."""
    return (T0 + lo_ms * 1_000_000, T0 + hi_ms * 1_000_000)


def _line(name, events, metadata_ids, line_id):
    rows = "\n".join(
        f"events {{ metadata_id: {metadata_ids[n]} offset_ps: {s * MS} "
        f"duration_ps: {d * MS} }}" for n, s, d in events)
    return (f"lines {{ id: {line_id} name: \"{name}\" "
            f"timestamp_ns: 1000000 {rows} }}")


def _plane(plane_id, name, lines):
    names = sorted({n for _, evs in lines for n, _, _ in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    meta = "\n".join(
        f"event_metadata {{ key: {i} value {{ id: {i} name: \"{n}\" }} }}"
        for n, i in ids.items())
    body = "\n".join(_line(ln, evs, ids, k + 1)
                     for k, (ln, evs) in enumerate(lines))
    return f"planes {{ id: {plane_id} name: \"{name}\" {body} {meta} }}"


def capture(device1_ops=None):
    """Milliseconds (start, duration). Device 0: ops 0-10, 5-20
    (overlapping), 30-40; the step and module lines cover 0-40 whole."""
    ops0 = [("fusion.1", 0, 10), ("fusion.2", 5, 15),
            ("all-reduce.3", 30, 10)]
    ops1 = [("fusion.1", 0, 10), ("all-reduce.3", 30, 10)] \
        if device1_ops is None else device1_ops
    dev = lambda ops: [("Steps", [("step 7", 0, 40)]),
                       ("XLA Modules", [("jit_chunk", 0, 40)]),
                       ("XLA Ops", ops)]
    host = [("python3", [("next(data_iter)", 20, 10), ("main", 0, 50)])]
    return "\n".join([_plane(1, "/device:TPU:0", dev(ops0)),
                      _plane(2, "/device:TPU:1", dev(ops1)),
                      _plane(3, "/host:CPU", host)])


def load(text):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(text)


def test_busy_is_the_union_on_the_ops_line_averaged_over_devices():
    red = trace_reduce.reduce(load(capture()), window_ns=window(0, 50))
    assert red["per_device_busy_s"] == pytest.approx([0.030, 0.020])
    assert red["busy_s"] == pytest.approx(0.025)
    assert 0 < red["busy_s"] <= red["window_s"] == pytest.approx(0.050)
    # not the sum of the ops (35 ms), of all lines (115 ms) or of devices
    assert red["busy_s"] < 0.035
    assert red["outside_window_s"] == [0, 0]


def test_a_window_wider_than_the_capture_counts_as_idle():
    red = trace_reduce.reduce(load(capture()), window_ns=(0, T0 + 99_000_000))
    assert red["window_s"] == pytest.approx(0.100)
    assert red["per_device_busy_s"] == pytest.approx([0.030, 0.020])
    assert (red["first_op_s"], red["last_op_s"]) == \
        pytest.approx((0.001, 0.041))


def test_breakdown_groups_families_and_names_gaps_by_the_host():
    red = trace_reduce.reduce(load(capture()), window_ns=window(0, 50))
    ops = dict(map(tuple, red["device_ops"]))
    # fusion.2 (5-20 ms) starts inside fusion.1 (0-10 ms): each counts for
    # what it alone covers, and the families add up to the busy time
    assert ops["fusion"] == pytest.approx(0.020)
    assert ops["all-reduce"] == pytest.approx(0.010)
    assert sum(ops.values()) == pytest.approx(red["per_device_busy_s"][0])
    assert red["idle_gaps"][0][0] == "next(data_iter)"
    assert red["idle_gaps"][0][1] == pytest.approx(0.010)


def test_a_device_without_events_is_an_error_not_zero():
    with pytest.raises(TraceError):
        trace_reduce.reduce(load(capture(device1_ops=[])),
                            window_ns=window(0, 50))


def test_a_capture_without_device_planes_is_an_error():
    host_only = _plane(3, "/host:CPU", [("python3", [("main", 0, 50)])])
    with pytest.raises(TraceError):
        trace_reduce.reduce(load(host_only), window_ns=window(0, 50))


def test_operations_are_clipped_to_the_window():
    # 8 to 34 ms: device 0 ran 8-20 and 30-34, device 1 ran 8-10 and 30-34
    red = trace_reduce.reduce(load(capture()), window_ns=window(8, 34))
    assert red["window_s"] == pytest.approx(0.026)
    assert red["per_device_busy_s"] == pytest.approx([0.016, 0.006])
    assert red["outside_window_s"] == pytest.approx([0.014, 0.014])
    ops = dict(map(tuple, red["device_ops"]))
    assert ops["all-reduce"] == pytest.approx(0.004)
    # a window that a device fills whole reads busy_s == window_s, not more
    full = trace_reduce.reduce(load(capture()), window_ns=window(2, 18))
    assert full["per_device_busy_s"][0] == pytest.approx(0.016)
    assert full["per_device_busy_s"][0] <= full["window_s"]


@pytest.mark.parametrize("lo_ms,hi_ms", [(21, 29), (50, 60), (30, 30)])
def test_a_window_in_which_a_device_ran_nothing_is_an_error(lo_ms, hi_ms):
    with pytest.raises(TraceError):
        trace_reduce.reduce(load(capture()), window_ns=window(lo_ms, hi_ms))


def test_a_container_op_counts_only_its_own_time():
    ops = [("while", 0, 100), ("fusion", 10, 40), ("fusion", 50, 90),
           ("copy", 60, 70), ("tail", 100, 120)]
    assert trace_reduce.self_time_by_family(ops) == {
        "while": 30, "fusion": 60, "copy": 10, "tail": 20}


def test_merge_and_union():
    assert trace_reduce.merge([(5, 20), (0, 10), (30, 40), (35, 36)]) == \
        [(0, 20), (30, 40)]
    assert trace_reduce.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert trace_reduce.union_ns([]) == 0


def test_no_capture_file_is_an_error(tmp_path):
    with pytest.raises(TraceError):
        trace_reduce.find_capture(str(tmp_path))


def test_callers_spans_name_gaps_beside_the_captures_host_events():
    span = ("between dispatches", *window(22, 30))  # 8 of the gap's 10 ms
    red = trace_reduce.reduce(load(capture()), window_ns=window(0, 50),
                              host_spans=[span])
    assert red["idle_gaps"][0] == ["between dispatches",
                                   pytest.approx(0.010)]

"""The trace reduction on the small recorded trace under
benchmark/testdata/: busy union, idle share, kernel time by name,
top operations without containers, idle gaps named by host span."""

import json

import pytest

from benchmark import spec, tracered

TRACE = json.load(open(spec.ROOT / "benchmark/testdata/small_trace.json"))


@pytest.fixture()
def events():
    return tracered.device_op_events(TRACE)


def test_only_operation_lines_of_device_planes_are_read(events):
    assert sorted(events) == [0, 1]
    assert all(name != "jit__decode_step(1)"
               for name, _, _ in events[0])
    assert len(events[0]) == 7 and len(events[1]) == 2


def test_busy_is_the_union_of_intervals_averaged_over_devices(events):
    # device 0: [1000,5000) + [6000,7000) + [8000,10000) = 7000 ns
    # device 1: [1000,3000) + [6000,9000) = 5000 ns
    assert tracered.busy_seconds(events) == pytest.approx(6000e-9)
    assert tracered.busy_seconds({0: events[0]}) == \
        pytest.approx(7000e-9)
    # over the slice [1000, 10000) device 0 is idle 2/9 of the time
    idle_share = 1 - tracered.busy_seconds({0: events[0]}) / 9000e-9
    assert idle_share == pytest.approx(2 / 9)


def test_kernel_time_by_name(events):
    seconds, calls = tracered.kernel_seconds(
        {0: events[0]}, "_decode_attend_paged.* = .*custom-call")
    assert (seconds, calls) == (pytest.approx(2000e-9), 2)
    assert tracered.kernel_seconds(events, "no_such_kernel") == (0.0, 0)


def test_top_operations_leave_out_containers(events):
    top = dict(tracered.top_ops({0: events[0]}))
    assert "while" not in top
    kernel = "attn._decode_attend_paged custom-call"
    assert top[kernel] == pytest.approx(2000e-9)
    # fusion.1 and fusion.5 add up under one name
    assert top["fusion"] == pytest.approx(3000e-9)
    assert list(top)[0] == "fusion"


def test_idle_gaps_are_named_by_the_host_span_that_covers_them(events):
    spans = tracered.host_spans(TRACE)
    assert [s[0] for s in spans] == ["engine.step", "engine.step"]
    gaps = dict(tracered.idle_gaps(events, spans))
    # device 0 gaps: [5000,6000) middle 5500 -> between spans;
    # [7000,8000) middle 7500 -> inside the second engine.step
    assert gaps == {"between spans": pytest.approx(1000e-9),
                    "engine.step": pytest.approx(1000e-9)}


def test_interval_arithmetic():
    assert tracered.merge([[5, 7], [1, 3], [2, 4]]) == [[1, 4], [5, 7]]
    assert tracered.subtract([[0, 10]], [[2, 3], [5, 12]]) == \
        [[0, 2], [3, 5]]
    assert tracered.total([[0, 2], [3, 5]]) == 4


def test_the_events_span_runs_from_first_start_to_last_end(events):
    # device 0 [1000, 10000), device 1 [1000, 9000): 9000 ns over both
    assert tracered.span_seconds(events) == 9000e-9
    assert tracered.span_seconds({1: events[1]}) == 8000e-9
    assert tracered.span_seconds({}) == 0.0
    assert tracered.busy_seconds(events) <= tracered.span_seconds(events)


def _slice_trace(busy_ns: list, mark: list = None) -> dict:
    """A one-device trace whose operations are busy for ``busy_ns[0]``,
    idle for ``busy_ns[1]``, busy for ``busy_ns[2]``, ... from 5 ms on
    the trace's clock, with the harness's slice mark [start, end] on a
    host line (none where ``mark`` is None)."""
    events, cursor = [], 5_000_000
    for i, ns in enumerate(busy_ns):
        if i % 2 == 0:
            events.append([f"fusion.{i}", cursor, ns])
        cursor += ns
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": events}]}] if events else []
    if mark:
        planes.append({"name": "/host:CPU", "lines": [{
            "name": "main", "events": [
                ["bench:" + tracered.SLICE_MARK, mark[0],
                 mark[1] - mark[0]]]}]})
    return {"planes": planes}


# PR 38's recorded edges (chiprun_out/runE, four traced runs of one
# saturated cell): the events' span, the gaps inside it, the host's
# slice. (a) the span is the longer and busy passed the host's slice;
# (b) the host's slice is the longer, as in every unsaturated cell.
SLICE_CASES = {
    "a_span_longer_than_the_hosts_slice": dict(
        busy_ns=[2_000_000_000, 1_635_968, 1_950_940_026],
        mark=[6_700_000, 3_957_536_739], host_s=3.950836739,
        window_s=3.952575994, busy_s=3.950940026, span_s=3.952575994,
        edges_s=[-0.0017, 0.000039255]),
    "b_hosts_slice_longer_than_the_span": dict(
        busy_ns=[1_941_702_975, 5_725_662, 2_000_000_000],
        mark=[2_000_000, 3_953_365_224], host_s=3.951365224,
        window_s=3.951365224, busy_s=3.941702975, span_s=3.947428637,
        edges_s=[0.003, -0.000936587]),
    "c_one_event_fills_the_slice": dict(
        busy_ns=[4_000_000_000], mark=[5_000_000, 4_005_000_000],
        host_s=4.0, window_s=4.0, busy_s=4.0, span_s=4.0,
        edges_s=[0.0, 0.0]),
    "d_no_device_event": dict(
        busy_ns=[], mark=[5_000_000, 4_005_000_000], host_s=4.0,
        window_s=4.0, busy_s=0.0, span_s=0.0, edges_s=None),
    "e_a_trace_without_the_mark": dict(
        busy_ns=[1_000_000_000, 1_000, 1_000_000_000], mark=None,
        host_s=2.5, window_s=2.5, busy_s=2.0, span_s=2.000001,
        edges_s=None),
}


@pytest.mark.parametrize("case", list(SLICE_CASES))
def test_a_traced_slices_window_holds_its_busy_time(case):
    want = SLICE_CASES[case]
    profile = tracered.reduce_slice(
        _slice_trace(want["busy_ns"], want["mark"]), want["host_s"])
    # to the last digit: where the host's slice is the longer the
    # reading is the one the slice's two stamps gave before
    assert profile["window_s"] == want["window_s"]
    assert profile["busy_s"] == want["busy_s"]
    assert profile["span_s"] == want["span_s"]
    assert profile["host_slice_s"] == want["host_s"]
    assert profile["busy_s"] <= profile["window_s"]
    assert (profile["busy_s"] > 0) == bool(want["busy_ns"])
    assert profile["edges_s"] == (
        want["edges_s"] and pytest.approx(want["edges_s"]))
    # the mark is the harness's own and names no idle gap
    gaps = dict(profile["breakdown"]["idle_gaps"])
    assert tracered.SLICE_MARK not in gaps
    if len(want["busy_ns"]) > 1:
        assert gaps == {"between spans": pytest.approx(
            want["busy_ns"][1] / 1e9)}
    line = tracered.describe_slice(profile)
    assert line.startswith("traced slice: host ")
    assert f"window_s {want['window_s']:.9f} s" in line
    assert "\n" not in line

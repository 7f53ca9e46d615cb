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

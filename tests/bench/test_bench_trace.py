"""The trace reduction of the on-chip benchmark on a small trace written
by hand: busy and idle time, the host label of each device operation,
kernel time, and which host span each idle gap falls in."""
import pytest

import bench_tiny  # noqa: F401  (puts the benchmark on sys.path)
from harness import trace_reduce as TR

# host spans (seconds): one engine step with its phases, then the
# harness waiting for arrivals
SPANS = [
    (0.0, 10.0, "step"),
    (0.0, 4.0, "schedule"),
    (0.5, 2.0, "descriptor"),
    (2.0, 4.0, "lookup"),
    (2.5, 3.5, "probe:local"),
    (4.0, 6.0, "admit"),
    (4.5, 5.0, "prefill_chunk"),
    (6.0, 10.0, "decode"),
    (11.0, 12.0, "arrivals"),
]
OPS = [
    TR.Op("fusion.1", 1.0, 1.5, "fusion.1"),            # descriptor
    TR.Op("custom-call.2", 2.6, 3.0, "similarity_topk"),  # probe:local
    TR.Op("fusion.3", 4.6, 5.4, "fusion.3"),            # ends in admit
    TR.Op("custom-call.4", 6.5, 7.0, "paged_attention"),
    TR.Op("fusion.5", 7.0, 9.0, "fusion.5"),            # decode
    TR.Op("fusion.6", 8.0, 8.5, "fusion.6"),            # overlaps fusion.5
]


@pytest.fixture
def red():
    return TR.reduce([OPS], SPANS, 0.0, 12.0)


def test_busy_is_the_union_of_op_intervals(red):
    # 0.5 + 0.4 + 0.8 + 0.5 + 2.0 (fusion.6 lies inside fusion.5)
    assert red.busy_s == pytest.approx(4.2)
    assert red.window_s == 12.0


def test_ops_take_the_innermost_span_open_at_their_end(red):
    labels = {op.name: lb for op, lb in red.ops}
    assert labels == {"fusion.1": "descriptor", "custom-call.2": "probe:local",
                      "fusion.3": "admit", "custom-call.4": "decode",
                      "fusion.5": "decode", "fusion.6": "decode"}
    # fusion.6 lies inside fusion.5: counted once
    assert red.device_s_by_label["decode"] == pytest.approx(2.5)


def test_kernel_time_by_name_and_label(red):
    assert red.kernel_s("paged_attention") == pytest.approx(0.5)
    assert red.kernel_s("paged_attention", "decode") == pytest.approx(0.5)
    assert red.kernel_s("paged_attention", "admit") == 0.0
    assert red.kernel_s("similarity_topk") == pytest.approx(0.4)


def test_idle_gaps_split_over_the_host_spans_they_overlap(red):
    idle = red.idle_by_label
    assert sum(idle.values()) == pytest.approx(12.0 - 4.2)
    assert idle["schedule"] == pytest.approx(0.5)        # 0.0-0.5
    assert idle["descriptor"] == pytest.approx(1.0)      # 0.5-1.0, 1.5-2.0
    assert idle["lookup"] == pytest.approx(1.0)          # 2.0-2.5, 3.5-4.0
    assert idle["probe:local"] == pytest.approx(0.6)     # 2.5-2.6, 3.0-3.5
    assert idle["admit"] == pytest.approx(1.1)           # 4.0-4.5, 5.4-6.0
    assert idle["prefill_chunk"] == pytest.approx(0.1)   # 4.5-4.6
    assert idle["decode"] == pytest.approx(1.5)          # 6.0-6.5, 9.0-10.0
    assert idle["harness"] == pytest.approx(1.0)         # 10.0-11.0
    assert idle["arrivals"] == pytest.approx(1.0)        # 11.0-12.0


def test_window_clips_ops_and_spans(red):
    part = TR.reduce([OPS], SPANS, 6.0, 8.0)
    assert part.busy_s == pytest.approx(1.5)
    assert part.span_count == {"decode": 1}
    assert sum(part.idle_by_label.values()) == pytest.approx(0.5)


def test_breakdown_names_ops_by_label_and_gaps_by_span(red):
    b = red.breakdown()
    assert b["device_ops"][0] == ["decode/fusion.5", pytest.approx(2.0)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0][0] == "decode"

"""The benchmark's reduction from trace events to per-layer numbers.

``trace_v5e_scale16.json`` is a trace recorded on one TPU v5e: three
``skipper_match`` calls on a Graph500 graph of scale 16 (W=2048, T=256,
degree reorder) inside a ``window`` span, reduced to the device's ``XLA
Ops`` events and the benchmark's host spans.
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import tracing  # noqa: E402
from bench.tracing import Event, HOST_PLANE  # noqa: E402

DEV = "/device:TPU:0"
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "trace_v5e_scale16.json")


def _op(name, start, dur, plane=DEV):
    return Event(plane, tracing.OPS_LINE, name, float(start), float(dur))


def _span(name, start, dur):
    return Event(HOST_PLANE, "python", name, float(start), float(dur))


def test_op_name_takes_the_instruction_name():
    text = ("%skipper_boundary_kernel.1 = (u8[32,16,128]) custom-call("
            "s32[2958]{0} %copy-done.2), custom_call_target=\"tpu_custom_call\"")
    assert tracing.op_name(text) == "skipper_boundary_kernel.1"
    assert tracing.op_base("skipper_boundary_kernel.1") == "skipper_boundary_kernel"
    assert tracing.op_name("%fusion = u8[4] fusion(u8[4] %x)") == "fusion"


def test_union_merges_overlaps_and_nesting():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (6, 6.5), (3, 4)]) == [
        (0, 4), (5, 7)]


def test_summarize_worked_example():
    """Window [0, 100) ns; ops busy [10, 40) and [60, 70); the gaps are
    named by the host span that overlaps them most."""
    events = [
        _span("window", 0, 100),
        _span("skipper_match", 0, 50),
        _span("fetch_mask", 50, 50),
        _op("skipper_pipeline_kernel.1", 10, 20),
        _op("fusion.2", 25, 15),                  # overlaps the kernel
        _op("skipper_boundary_kernel.3", 60, 4),
        _op("skipper_boundary_kernel.4", 64, 6),
        _op("fusion.9", 150, 5),                  # after the window
    ]
    s = tracing.summarize(events)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(40e-9)
    assert s.idle_share == pytest.approx(0.6)
    assert s.kernel("skipper_boundary_kernel") == (pytest.approx(10e-9), 2)
    assert s.kernel("skipper_pipeline_kernel") == (pytest.approx(20e-9), 1)
    assert "fusion.9" not in s.op_s
    assert s.top_ops()[0] == ["skipper_pipeline_kernel.1", pytest.approx(20e-9)]
    # [40, 60) overlaps both spans by 10 ns: the first one names it
    assert s.idle_gaps == [["fetch_mask", pytest.approx(30e-9)],
                           ["skipper_match", pytest.approx(20e-9)],
                           ["skipper_match", pytest.approx(10e-9)]]
    assert s.span_s == {"skipper_match": [pytest.approx(50e-9)],
                        "fetch_mask": [pytest.approx(50e-9)]}


def test_busy_is_the_mean_over_devices():
    events = [_span("window", 0, 100), _op("a.1", 0, 50),
              _op("a.1", 0, 10, plane="/device:TPU:1")]
    assert tracing.summarize(events).busy_s == pytest.approx(30e-9)


def test_summarize_needs_one_window_and_a_device():
    with pytest.raises(ValueError, match="window"):
        tracing.summarize([_op("a.1", 0, 1)])
    with pytest.raises(ValueError, match="device"):
        tracing.summarize([_span("window", 0, 1)])


def test_recorded_v5e_trace():
    with open(RECORDED) as f:
        events = [Event(*e) for e in json.load(f)]
    s = tracing.summarize(events)
    assert s.span_s["skipper_match"] and len(s.span_s["skipper_match"]) == 3
    assert 0.0 < s.busy_s < s.window_s
    for kernel in ("skipper_pipeline_kernel", "skipper_boundary_kernel"):
        seconds, n = s.kernel(kernel)
        assert n == 3                      # one event per call
        expect = sum(e.dur_ns for e in events
                     if tracing.op_base(e.name) == kernel) * 1e-9
        assert seconds == pytest.approx(expect)
        assert seconds < s.busy_s
    top = [name for name, _ in s.top_ops()]
    assert "skipper_boundary_kernel.1" in top[:4]
    assert sum(g for _, g in s.idle_gaps) <= s.window_s - s.busy_s + 1e-12
    assert {name for name, _ in s.idle_gaps} <= {
        "skipper_match", "fetch_mask", "other"}

"""The per-layer metrics that read what the program records about itself:
its spans and counters (``repro.spans``) and the named scopes of its
pipeline's device ops. Each reader on a made-up run over a recorded
snapshot, each with nothing to read, each against a program that records
nothing, and a tiny traced run of each mix in which every such metric of
the cell is present."""
import json
import os
import re
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, tracing  # noqa: E402

V5E = "TPU v5 lite"
SPAN_METRICS = {"schedule_canonical_s": "schedule.canonical",
                "schedule_rows_s": "schedule.rows",
                "schedule_pairs_s": "schedule.pairs",
                "schedule_map_s": "schedule.stream_map",
                "schedule_copy_s": "match.to_device"}
SCOPE_METRICS = {"decision_gather_s": "decision_gather",
                 "conflict_gather_s": "conflict_gather",
                 "state_unpermute_s": "state_unpermute"}
COUNT_METRICS = ("schedule_h2d_gb",)
NEW = sorted(SPAN_METRICS) + sorted(SCOPE_METRICS) + list(COUNT_METRICS)
TINY = {"g500-s22.warm": {
            "generator": "kronecker",
            "graph": {"scale": 10, "edgefactor": 16, "a": 0.57, "b": 0.19,
                      "c": 0.19, "graph_seed": 1},
            "schedule": {"window": 256, "tile_size": 128,
                         "reorder": "degree"}},
        "rgg_n_2_21_s0.cold": {
            "generator": "rgg", "graph": {"scale": 10, "graph_seed": 0},
            "schedule": {"window": 256, "tile_size": 128,
                         "reorder": "none"}}}
SEED = (1 << 33) + 11


def _run(calls, trace=None):
    return harness.Run(setup_s=0.0, calls=calls, medges_per_s=0.0,
                       peak_bytes=0, schedule={}, trace=trace,
                       peaks=harness.lookup_peaks(V5E))


def _s(ns):
    return ns * 1e-9


@pytest.fixture(scope="module")
def recorded():
    """Two calls of the program on a small graph, timed as the loop times
    them: a made-up run over them and the pipeline's op scopes."""
    import jax.numpy as jnp
    import numpy as np

    from repro import spans
    from repro.graphs import EdgeList, build_window_schedule
    from repro.kernels.skipper_match import ops, skipper_match

    rng = np.random.default_rng(5)
    u = rng.integers(0, 1024, 4096).astype(np.int32)
    v = rng.integers(0, 1024, 4096).astype(np.int32)
    calls = []
    for _ in range(2):
        t0 = time.perf_counter()
        s = build_window_schedule(EdgeList(jnp.asarray(u), jnp.asarray(v),
                                           1024), window=256, tile_size=128)
        skipper_match(schedule=s, backend="xla")
        calls.append((t0, time.perf_counter()))
    with spans.span("schedule.rows"):        # after the calls: left out
        pass
    return calls, ops.op_scopes()[1], s, spans


@pytest.mark.parametrize("metric", sorted(SPAN_METRICS))
def test_span_metric_is_the_mean_span_of_the_calls(metric, recorded):
    calls, _, _, spans = recorded
    snap = spans.snapshot(round(calls[0][0] * 1e9), round(calls[-1][1] * 1e9))
    took = [_s(b - a) for n, a, b in snap.spans if n == SPAN_METRICS[metric]]
    assert len(took) == 2
    value = harness.read_metric(metric, _run(calls))
    assert value == pytest.approx(sum(took) / 2)
    assert 0 < value < max(b - a for a, b in calls)


def test_h2d_metric_is_the_copied_gigabytes_per_call(recorded):
    calls, _, s, _ = recorded
    copied = sum(a.nbytes for a in (
        s.u_tiles, s.v_tiles, s.stream_src, s.boundary_blk_u,
        s.boundary_blk_v, s.boundary_ulocal, s.boundary_vlocal,
        s.window_ids))                       # perm is made on the device
    assert harness.read_metric("schedule_h2d_gb", _run(calls)) == (
        pytest.approx(copied / 1e9))


def _scope_trace(ops, runs_per_op):
    """A window of two calls' device ops: each op in ``ops`` ran
    ``runs_per_op`` times for 100 ns, beside an op in no scope."""
    ev = [tracing.Event(tracing.HOST_PLANE, "python", "window", 0.0, 1e6),
          tracing.Event("/device:TPU:0", tracing.OPS_LINE, "unscoped.1", 0.0,
                        900.0)]
    for i, op in enumerate(ops):
        for k in range(runs_per_op):
            ev.append(tracing.Event("/device:TPU:0", tracing.OPS_LINE, op,
                                    1000.0 * (i + 1) + k * 1e5, 100.0))
    return tracing.summarize(ev)


@pytest.mark.parametrize("metric", sorted(SCOPE_METRICS))
def test_scope_metric_sums_the_scopes_device_ops_per_call(metric, recorded):
    calls, scopes, _, _ = recorded
    mine = sorted(op for op, sc in scopes.items()
                  if sc == SCOPE_METRICS[metric])
    assert mine and "unscoped.1" not in scopes
    run = _run(calls, trace=_scope_trace(mine, 2))
    assert harness.read_metric(metric, run) == pytest.approx(
        len(mine) * 2 * 100e-9 / 2)


@pytest.mark.parametrize("metric", sorted(SCOPE_METRICS))
def test_scope_metric_reads_none_where_an_op_ran_other_than_once_per_call(
        metric, recorded):
    """An op that ran more often than the calls is another program's too:
    its time is not the scope's."""
    calls, scopes, _, _ = recorded
    mine = sorted(op for op, sc in scopes.items()
                  if sc == SCOPE_METRICS[metric])
    run = _run(calls, trace=_scope_trace(mine, 3))
    assert harness.read_metric(metric, run) is None


@pytest.mark.parametrize("metric", NEW)
def test_nothing_recorded_reads_none(metric, recorded):
    ev = [tracing.Event(tracing.HOST_PLANE, "python", "window", 0.0, 1e6),
          tracing.Event("/device:TPU:0", tracing.OPS_LINE, "fusion.77", 0.0,
                        5.0)]
    t = time.perf_counter()
    run = _run([(t, t + 1e-6)], trace=tracing.summarize(ev))
    assert harness.read_metric(metric, run) is None
    assert harness.read_metric(metric, _run([])) is None


@pytest.mark.parametrize("metric", NEW)
def test_a_program_that_records_nothing_reads_none(metric, recorded,
                                                   monkeypatch):
    """The program at a commit before its spans, counters and scopes: every
    reader gives None and none raises."""
    import repro
    from repro.kernels.skipper_match import ops

    calls, scopes, _, _ = recorded
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    monkeypatch.delattr(repro, "spans", raising=False)
    monkeypatch.delattr(ops, "op_scopes")
    ev = [tracing.Event(tracing.HOST_PLANE, "python", "window", 0.0, 1e6)]
    ev += [tracing.Event("/device:TPU:0", tracing.OPS_LINE, op, 10.0, 5.0)
           for op in scopes]
    run = _run(calls, trace=tracing.summarize(ev))
    assert harness.read_metric(metric, run) is None


# -- a tiny traced run of each mix -------------------------------------------

_HLO_NAME = re.compile(r"^[A-Za-z_][\w.\-]*$")


def _cpu_ops_as_device(read_xplane):
    """The CPU has no device plane: take the XLA ops that its threads ran
    (events named by their HLO instruction) for a device's ``XLA Ops``."""
    def read(path, spans):
        from jax.profiler import ProfileData

        events = read_xplane(path, spans)
        for plane in ProfileData.from_file(path).planes:
            if plane.name != tracing.HOST_PLANE:
                continue
            for line in plane.lines:
                if not line.name.startswith("tf_XLA"):
                    continue
                events += [tracing.Event("/device:TPU:0", tracing.OPS_LINE,
                                         e.name, float(e.start_ns),
                                         float(e.duration_ns))
                           for e in line.events if _HLO_NAME.match(e.name)]
        return events
    return read


@pytest.mark.parametrize("workload", sorted(TINY))
def test_traced_tiny_run_reports_every_new_metric(workload, monkeypatch):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    per_layer = [m for m in spec["per_layer"]
                 if m["name"] in NEW and workload in m["workloads"]]
    assert per_layer
    real = harness.load_cell(workload)
    cell = harness.Cell("tiny", 1, TINY[workload], real.traffic,
                        real.end_to_end, per_layer)
    monkeypatch.setattr(tracing, "read_xplane",
                        _cpu_ops_as_device(tracing.read_xplane))
    result = harness.run_cell(cell, SEED, 0.3, True, time.perf_counter(),
                              harness.lookup_peaks(V5E))
    assert result["correct"]
    assert set(result["metrics"]) == {m["name"] for m in per_layer}
    for name, m in result["metrics"].items():
        assert m["value"] >= 0, name

"""The program's own spans and counters (``repro.spans``): the bounded
table and its interval filter, counts of device scalars, the schedule
build's phase spans, the schedule copy's span and byte counter, the window
tier's fallback count, the pipeline's named scopes with ``op_scopes``, and
the one executable each pipeline compiles to."""
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.graphs import grid_graph
from repro.graphs.windows import build_window_schedule
from repro.kernels.skipper_match import ops, skipper_match

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src", "repro")
SCHEDULE_SPANS = ("schedule.canonical", "schedule.rows", "schedule.pairs",
                  "schedule.stream_map")


def _now():
    return time.perf_counter_ns()


def _named(snap, name):
    return [s for s in snap.spans if s[0] == name]


def _counted(snap, name):
    return [v for n, _, v in snap.counts if n == name]


# -- the table ----------------------------------------------------------------

def test_table_is_bounded_and_snapshot_filters_by_interval():
    for i in range(spans.MAXLEN + 10):
        spans.count("test.fill", i)
    assert len(spans._counts) == spans.MAXLEN
    t0 = _now()
    with spans.span("test.inner"):
        spans.count("test.value", 7)
    t1 = _now()
    with spans.span("test.later"):
        pass
    snap = spans.snapshot(t0, t1)
    assert [s[0] for s in snap.spans] == ["test.inner"]
    assert _counted(snap, "test.value") == [7]
    assert not _counted(snap, "test.fill")
    (name, a, b), = snap.spans
    assert t0 <= a <= b <= t1
    assert [s[0] for s in spans.snapshot(t1).spans] == ["test.later"]


def test_a_device_scalar_count_is_fetched_when_a_snapshot_reads_it():
    t0 = _now()
    spans.count("test.device", jnp.asarray(5, jnp.int32) + 2)
    spans.count("test.host", 3)
    (name, _, value), = [c for c in spans._counts if c[0] == "test.device"
                         and c[1] >= t0]
    assert isinstance(value, jax.Array)          # logged without a fetch
    snap = spans.snapshot(t0)
    assert _counted(snap, "test.device") == [7]
    assert _counted(snap, "test.host") == [3]
    assert all(type(v) is int for _, _, v in snap.counts)


def test_a_span_that_raises_is_recorded():
    t0 = _now()
    with pytest.raises(ValueError):
        with spans.span("test.raised"):
            raise ValueError("inside")
    assert _named(spans.snapshot(t0), "test.raised")


def test_program_span_names_are_dotted_and_not_the_benchmarks():
    """A caller's profile reads its own undotted spans by name: no span of
    the program may be taken for one of them."""
    sys.path.insert(0, ROOT)
    try:
        from bench.loops.closed import SPANS
        from bench.tracing import WINDOW
    finally:
        sys.path.remove(ROOT)
    names = set()
    for base, _, files in os.walk(SRC):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(base, f)) as fh:
                    names |= set(re.findall(r'spans\.span\("([^"]+)"\)',
                                            fh.read()))
    assert set(SCHEDULE_SPANS) | {"match.to_device"} <= names
    assert all("." in n for n in names)
    assert not names & (set(SPANS) | {WINDOW})


# -- the schedule build and the schedule copy --------------------------------

def test_build_records_each_phase_once_inside_the_call():
    g = grid_graph(20, 20)
    t0 = _now()
    build_window_schedule(g, window=64, tile_size=32, reorder="degree")
    t1 = _now()
    snap = spans.snapshot(t0, t1)
    assert [s[0] for s in snap.spans] == list(SCHEDULE_SPANS)
    phases = sum(b - a for _, a, b in snap.spans)
    assert 0 < phases <= t1 - t0
    for (_, _, end), (_, start, _) in zip(snap.spans, snap.spans[1:]):
        assert end <= start                # they tile the call, in order


@pytest.mark.parametrize("reorder", ["none", "degree"])
def test_h2d_bytes_are_the_copied_arrays(reorder):
    g = grid_graph(16, 16)
    s = build_window_schedule(g, window=64, tile_size=32, reorder=reorder)
    copied = [s.u_tiles, s.v_tiles, s.stream_src, s.boundary_blk_u,
              s.boundary_blk_v, s.boundary_ulocal, s.boundary_vlocal,
              s.window_ids]
    if s.perm is not None:                 # made on the device otherwise
        copied.append(s.perm)
    t0 = _now()
    skipper_match(schedule=s, backend="xla")
    snap = spans.snapshot(t0)
    assert _counted(snap, "match.h2d_bytes") == [
        sum(a.nbytes for a in copied)]
    (_, a, b), = _named(snap, "match.to_device")
    assert a < b


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_window_fallback_tiles_are_logged_as_counted(backend):
    """The call logs its ``Counters.fallback_tiles`` as the device scalar
    it is; the snapshot reads it back as the backends' one count."""
    g = grid_graph(16, 16)
    s = build_window_schedule(g, window=64, tile_size=32)
    t0 = _now()
    res = skipper_match(schedule=s, backend=backend)
    (logged,) = [c[2] for c in spans._counts
                 if c[0] == "match.window_fallback_tiles" and c[1] >= t0]
    assert logged is res.counters.fallback_tiles
    assert _counted(spans.snapshot(t0), "match.window_fallback_tiles") == [
        int(skipper_match(schedule=s, backend="xla").counters.fallback_tiles)]


# -- the named scopes ----------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_pipeline_carries_each_scope_and_op_scopes_finds_the_gathers(backend):
    g = grid_graph(12, 12)
    s = build_window_schedule(g, window=32, tile_size=32)
    assert s.num_boundary_padded > 0
    fn = ops._build_pipeline(
        s.num_windows, s.num_rows, s.tiles_per_window, s.tile_size, s.window,
        s.num_boundary_padded, s.num_edges, s.num_vertices, 1, True, backend,
        "auto", None, ops.DEFAULT)
    sd = [jax.ShapeDtypeStruct(a.shape, jnp.int32) for a in (
        s.u_tiles, s.v_tiles, s.stream_src, s.boundary_blk_u,
        s.boundary_blk_v, s.boundary_ulocal, s.boundary_vlocal,
        s.window_ids, np.zeros(s.num_vertices))]
    text = fn.lower(*sd).as_text(debug_info=True)
    for scope in ops.SCOPES:
        assert f"/{scope}/" in text, scope
    skipper_match(schedule=s, backend=backend)
    module, scopes = ops.op_scopes()
    assert {"decision_gather", "conflict_gather",
            "state_unpermute"} == set(scopes.values())
    executable = ops._COMPILED[fn]
    text = executable.as_text()
    assert text.startswith(f"HloModule {module}")
    assert all(re.search(rf"%?{re.escape(name)} = ", text) for name in scopes)


def test_each_pipeline_compiles_once_and_runs_that_executable():
    g = grid_graph(10, 10)
    s = build_window_schedule(g, window=32, tile_size=32)
    first = skipper_match(schedule=s, backend="xla")
    traces = ops.pipeline_trace_count()
    (fn, executable), = list(ops._COMPILED.items())[-1:]
    again = skipper_match(schedule=s, backend="xla")
    assert ops.pipeline_trace_count() == traces
    assert ops._COMPILED[fn] is executable
    assert next(reversed(ops._COMPILED)) is fn
    np.testing.assert_array_equal(first.match_mask, again.match_mask)

"""The schedule's device copy that ``skipper_match`` keeps between calls:
a call on the resident schedule copies nothing and answers bit-identically
to a call that copies; any other schedule, and one whose arrays can be
written, is copied; at most one schedule's copy is live on the device
between calls; a collected schedule takes its copy with it."""
import dataclasses
import gc
import time

import jax
import numpy as np
import pytest

from repro import spans
from repro.core import FaultPlan
from repro.graphs import grid_graph
from repro.graphs.windows import build_window_schedule
from repro.kernels.skipper_match import ops, skipper_match

BACKENDS = ["xla", "pallas"]
G = grid_graph(16, 16)


def _build(g=G, reorder="degree"):
    return build_window_schedule(g, window=64, tile_size=32, reorder=reorder)


def _recorded(call):
    """``call()``'s result and what the schedule copy counted in it:
    (``match.h2d_bytes``, ``match.schedule_resident``)."""
    t0 = time.perf_counter_ns()
    out = call()
    counts = spans.snapshot(t0).counts
    (copied,) = [v for n, _, v in counts if n == "match.h2d_bytes"]
    (resident,) = [v for n, _, v in counts if n == "match.schedule_resident"]
    return out, (copied, resident)


def _host_bytes(s):
    arrays = [s.u_tiles, s.v_tiles, s.stream_src, s.boundary_blk_u,
              s.boundary_blk_v, s.boundary_ulocal, s.boundary_vlocal,
              s.window_ids]
    return sum(a.nbytes for a in arrays + ([s.perm] if s.perm is not None
                                           else []))


def _assert_same(a, b):
    """Two results, leaf by leaf: mask, state, every ``Counters`` field,
    and whatever else the call returned (conflicts, a report)."""
    a = a if isinstance(a, tuple) else (a,)
    b = b if isinstance(b, tuple) else (b,)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        if hasattr(x, "match_mask"):
            np.testing.assert_array_equal(x.match_mask, y.match_mask)
            np.testing.assert_array_equal(x.state, y.state)
            assert x.state.dtype == y.state.dtype
            for f in dataclasses.fields(x.counters):
                np.testing.assert_array_equal(
                    np.asarray(getattr(x.counters, f.name)),
                    np.asarray(getattr(y.counters, f.name)), f.name)
        elif dataclasses.is_dataclass(x):
            assert x == y
        else:
            np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("reorder", ["none", "degree"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_second_call_copies_nothing_and_answers_as_a_fresh_copy(
        backend, reorder):
    s = _build(reorder=reorder)
    _, first = _recorded(lambda: skipper_match(schedule=s, backend=backend))
    assert first == (_host_bytes(s), 0)
    resident, second = _recorded(
        lambda: skipper_match(schedule=s, backend=backend))
    assert second == (0, 1)
    fresh_s = _build(reorder=reorder)
    fresh, third = _recorded(
        lambda: skipper_match(schedule=fresh_s, backend=backend))
    assert third == (_host_bytes(fresh_s), 0)
    _assert_same(resident, fresh)


@pytest.mark.parametrize("make", ["replace", "rebuild"])
def test_another_schedule_object_is_copied(make):
    s = _build()
    skipper_match(schedule=s, backend="xla")
    other = dataclasses.replace(s) if make == "replace" else _build()
    assert other is not s
    _, counted = _recorded(lambda: skipper_match(schedule=other,
                                                 backend="xla"))
    assert counted == (_host_bytes(s), 0)
    # ``s`` is no longer resident: it is copied again
    _, counted = _recorded(lambda: skipper_match(schedule=s, backend="xla"))
    assert counted == (_host_bytes(s), 0)


def _writable(s):
    """``s`` with every numpy array a writable copy."""
    return dataclasses.replace(s, **{
        f.name: np.array(getattr(s, f.name)) for f in dataclasses.fields(s)
        if isinstance(getattr(s, f.name), np.ndarray)})


def _read_only_view_of_writable(s):
    """``s`` with ``u_tiles`` a read-only view of an array that can be
    written: not immutable, since the base can change it."""
    base = np.array(s.u_tiles)
    view = base.view()
    view.setflags(write=False)
    return dataclasses.replace(s, u_tiles=view)


@pytest.mark.parametrize("hand_built", [_writable,
                                        _read_only_view_of_writable])
@pytest.mark.parametrize("backend", BACKENDS)
def test_schedule_that_can_be_written_is_copied_on_every_call(
        backend, hand_built):
    s = hand_built(_build())
    for _ in range(3):
        res, counted = _recorded(
            lambda: skipper_match(schedule=s, backend=backend))
        assert counted == (_host_bytes(s), 0)
    assert ops._RESIDENT is None
    _assert_same(res, skipper_match(schedule=_build(), backend=backend))


def test_a_write_to_a_copied_schedule_is_seen_by_the_next_call():
    """Why a writable schedule is copied on every call: a change made in
    place between calls reaches the device."""
    s = _writable(_build())
    before = skipper_match(schedule=s, backend="xla")
    s.u_tiles[...] = -1
    s.v_tiles[...] = -1
    after = skipper_match(schedule=s, backend="xla")
    assert int(before.match_mask.sum()) > int(after.match_mask.sum())


@pytest.mark.parametrize("reorder", ["none", "degree"])
def test_built_schedule_arrays_refuse_writes(reorder):
    s = _build(reorder=reorder)
    arrays = {f.name: getattr(s, f.name) for f in dataclasses.fields(s)
              if isinstance(getattr(s, f.name), np.ndarray)}
    assert {"u_tiles", "v_tiles", "stream_src", "window_ids"} <= set(arrays)
    assert (reorder == "degree") == ("perm" in arrays and "inv" in arrays)
    for name, a in arrays.items():
        assert not a.flags.writeable, name
        assert ops._immutable(a), name
        with pytest.raises(ValueError, match="read-only"):
            a[...] = 0


def _live_bytes():
    """Bytes of the live device arrays, less the device scalars that the
    spans table holds (each call logs its fallback count)."""
    gc.collect()
    logged = {id(c[2]) for c in spans._counts}
    return sum(a.nbytes for a in jax.live_arrays() if id(a) not in logged)


def test_live_device_bytes_stay_at_one_schedules_copy():
    """Calls on distinct schedules, every one of them kept alive: after each
    call, the live device bytes are the baseline plus the newest schedule's
    copy (and the ``arange`` that stands for an absent ``perm``)."""
    graphs = [grid_graph(16, 16), grid_graph(12, 20), grid_graph(20, 20),
              grid_graph(10, 24)]
    schedules = [_build(g, reorder=r) for g in graphs
                 for r in ("none", "degree")]
    ops._drop_resident()
    base = _live_bytes()
    for s in schedules:
        res = skipper_match(schedule=s, backend="xla")
        del res
        copy = _host_bytes(s) + (4 * s.num_vertices if s.perm is None else 0)
        assert _live_bytes() - base == copy
    # the resident copy serves the last schedule; earlier ones are copied
    _, counted = _recorded(lambda: skipper_match(schedule=schedules[-1],
                                                 backend="xla"))
    assert counted == (0, 1)
    _, counted = _recorded(lambda: skipper_match(schedule=schedules[0],
                                                 backend="xla"))
    assert counted == (_host_bytes(schedules[0]), 0)


def test_collecting_the_resident_schedule_frees_its_copy():
    s = _build(reorder="none")
    ops._drop_resident()
    base = _live_bytes()
    skipper_match(schedule=s, backend="xla")
    assert ops._RESIDENT is not None and ops._RESIDENT[0]() is s
    assert _live_bytes() > base
    del s
    gc.collect()
    assert ops._RESIDENT is None
    assert _live_bytes() == base


@pytest.mark.parametrize("backend", BACKENDS)
def test_conflicts_and_faults_on_the_resident_copy_answer_as_a_fresh_copy(
        backend):
    calls = {
        "conflicts": dict(with_conflicts=True),
        "faults": dict(faults=FaultPlan(seed=7, drop_proposals=0.3),
                       on_fault="report", edges=G),
        "verify": dict(verify=True, edges=G),
    }
    for name, kw in calls.items():
        s = _build()
        skipper_match(schedule=s, backend=backend)
        resident, counted = _recorded(
            lambda: skipper_match(schedule=s, backend=backend, **kw))
        assert counted == (0, 1), name
        fresh_s = _build()
        fresh, counted = _recorded(
            lambda: skipper_match(schedule=fresh_s, backend=backend, **kw))
        assert counted == (_host_bytes(fresh_s), 0), name
        _assert_same(resident, fresh)

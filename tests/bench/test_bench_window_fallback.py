"""The window tier's fallback count and the metric that reads it
(``window_fallback_tile_share``). On the benchmark's own graph families at
small sizes, the Pallas path (interpret mode) and the XLA twin count the
same tiles and give bit-identical answers, which the plain reference
passes. The reader on a made-up run over recorded calls, with nothing to
read, against a program that counts nothing, and a tiny traced run of the
cell that lists it."""
import json
import os
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src"), HERE]

from bench import generators, harness, reference, tracing  # noqa: E402
from test_bench_program_metrics import (  # noqa: E402
    NEW, SEED, V5E, _cpu_ops_as_device)

METRIC = "window_fallback_tile_share"
CELL = "rgg_n_2_23_s0.warm"
TINY = {"generator": "rgg", "graph": {"scale": 10, "graph_seed": 0},
        "schedule": {"window": 256, "tile_size": 128, "reorder": "none"}}
GRAPHS = {
    "rgg-morton": ({"generator": "rgg",
                    "graph": {"scale": 11, "graph_seed": 0}}, "none"),
    "kronecker": ({"generator": "kronecker",
                   "graph": {"scale": 10, "edgefactor": 16, "a": 0.57,
                             "b": 0.19, "c": 0.19, "graph_seed": 1}},
                  "degree"),
}


@pytest.mark.parametrize("seed", [(1 << 33) + 3, 17])
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_backends_count_the_same_fallback_tiles(graph, seed):
    from repro.graphs import EdgeList, build_window_schedule
    from repro.kernels.skipper_match import skipper_match

    cfg, reorder = GRAPHS[graph]
    make, n = generators.stream_maker(cfg)
    u, v = make(seed, 0)
    s = build_window_schedule(EdgeList(jnp.asarray(u), jnp.asarray(v), n),
                              window=256, tile_size=128, reorder=reorder)
    pallas = skipper_match(schedule=s, backend="pallas", interpret=True)
    xla = skipper_match(schedule=s, backend="xla")
    np.testing.assert_array_equal(pallas.match_mask, xla.match_mask)
    np.testing.assert_array_equal(pallas.state, xla.state)
    taken = int(pallas.counters.fallback_tiles)
    assert taken == int(xla.counters.fallback_tiles)
    assert 0 <= taken <= s.num_rows * s.tiles_per_window
    readings = reference.check(u, v, n, np.asarray(pallas.match_mask),
                               np.asarray(pallas.state))
    assert reference.passes(readings), readings


def _run(calls, trace=None, schedule=None):
    return harness.Run(setup_s=0.0, calls=calls, medges_per_s=0.0,
                       peak_bytes=0, schedule=schedule or {}, trace=trace,
                       peaks=harness.lookup_peaks(V5E))


@pytest.fixture(scope="module")
def recorded():
    """Two calls of the program on a small random graph, timed as the loop
    times them, with their results and the schedule's counts."""
    from bench.loops import schedule_stats
    from repro.graphs import EdgeList, build_window_schedule
    from repro.kernels.skipper_match import skipper_match

    rng = np.random.default_rng(5)
    u = rng.integers(0, 1024, 4096).astype(np.int32)
    v = rng.integers(0, 1024, 4096).astype(np.int32)
    calls, results = [], []
    for _ in range(2):
        t0 = time.perf_counter()
        s = build_window_schedule(EdgeList(jnp.asarray(u), jnp.asarray(v),
                                           1024), window=256, tile_size=128)
        results.append(skipper_match(schedule=s, backend="xla"))
        calls.append((t0, time.perf_counter()))
    return calls, results, s, schedule_stats(s)


def test_fallback_share_is_the_counted_tiles_over_the_calls_tiles(
        recorded):
    calls, results, s, stats = recorded
    taken = [int(r.counters.fallback_tiles) for r in results]
    assert taken[0] > 0          # random edges over 256-vertex windows
    share = harness.read_metric(METRIC, _run(calls, schedule=stats))
    assert share == pytest.approx(
        100 * sum(taken) / (2 * s.num_rows * s.tiles_per_window))
    assert 0 < share <= 100


def test_nothing_counted_reads_none(recorded):
    """A window in which the program counted nothing, and a run with no
    calls, read None."""
    _, _, _, stats = recorded
    t = time.perf_counter()
    assert harness.read_metric(METRIC, _run([(t, t + 1e-6)],
                                            schedule=stats)) is None
    assert harness.read_metric(METRIC, _run([], schedule=stats)) is None


def test_a_program_that_counts_nothing_reads_none(recorded, monkeypatch):
    """The program at a commit before the count: the reader gives None and
    does not raise."""
    import repro

    calls, _, _, stats = recorded
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    monkeypatch.delattr(repro, "spans", raising=False)
    assert harness.read_metric(METRIC, _run(calls, schedule=stats)) is None


def test_traced_tiny_run_of_the_cell_reports_every_program_metric(
        monkeypatch):
    """The cell at a tiny size, traced on the CPU: every metric that it
    lists and that reads what the program records is present."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    per_layer = [m for m in spec["per_layer"]
                 if m["name"] in NEW + [METRIC] and CELL in m["workloads"]]
    assert METRIC in {m["name"] for m in per_layer}
    real = harness.load_cell(CELL)
    cell = harness.Cell("tiny", 1, TINY, real.traffic, real.end_to_end,
                        per_layer)
    monkeypatch.setattr(tracing, "read_xplane",
                        _cpu_ops_as_device(tracing.read_xplane))
    result = harness.run_cell(cell, SEED, 0.3, True, time.perf_counter(),
                              harness.lookup_peaks(V5E))
    assert result["correct"]
    assert set(result["metrics"]) == {m["name"] for m in per_layer}
    for name, m in result["metrics"].items():
        assert m["value"] >= 0, name

"""The schedule copy's metrics where the program keeps a schedule's device
copy between calls: ``schedule_resident_share`` on a made-up run over
recorded calls, with nothing to read and against a program that counts
nothing; and tiny traced runs, in which a warm cell finds its schedule
resident on every window call and copies nothing, and a cold cell copies
on every call."""
import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src"), HERE]

from bench import harness, tracing  # noqa: E402
from test_bench_program_metrics import (  # noqa: E402
    SEED, TINY, V5E, _cpu_ops_as_device)

METRIC = "schedule_resident_share"
COPY_METRICS = (METRIC, "schedule_h2d_gb", "schedule_copy_s")
TINY_RGG_WARM = {"generator": "rgg", "graph": {"scale": 10, "graph_seed": 0},
                 "schedule": {"window": 256, "tile_size": 128,
                              "reorder": "none"}}
TINY_CELLS = {"g500-s22.warm": TINY["g500-s22.warm"],
              "rgg_n_2_23_s0.warm": TINY_RGG_WARM,
              "rgg_n_2_21_s0.cold": TINY["rgg_n_2_21_s0.cold"]}


def _run(calls):
    return harness.Run(setup_s=0.0, calls=calls, medges_per_s=0.0,
                       peak_bytes=0, schedule={}, trace=None,
                       peaks=harness.lookup_peaks(V5E))


@pytest.fixture(scope="module")
def recorded():
    """Three calls timed as the loop times them: one on a new schedule,
    then two on the same one."""
    import jax.numpy as jnp
    import numpy as np

    from repro.graphs import EdgeList, build_window_schedule
    from repro.kernels.skipper_match import skipper_match

    rng = np.random.default_rng(5)
    u = rng.integers(0, 1024, 4096).astype(np.int32)
    v = rng.integers(0, 1024, 4096).astype(np.int32)
    s = build_window_schedule(EdgeList(jnp.asarray(u), jnp.asarray(v), 1024),
                              window=256, tile_size=128)
    calls = []
    for _ in range(3):
        t0 = time.perf_counter()
        skipper_match(schedule=s, backend="xla")
        calls.append((t0, time.perf_counter()))
    return calls


@pytest.mark.parametrize("first,share", [(1, 100.0), (0, 200.0 / 3)])
def test_share_is_the_resident_calls_over_the_calls(recorded, first, share):
    """A window from the ``first`` call on: the first call copies, the
    others find the schedule resident and copy nothing."""
    run = _run(recorded[first:])
    assert harness.read_metric(METRIC, run) == pytest.approx(share)
    assert (harness.read_metric("schedule_h2d_gb", run) == 0) == (first == 1)


def test_nothing_counted_reads_none():
    t = time.perf_counter()
    assert harness.read_metric(METRIC, _run([(t, t + 1e-6)])) is None
    assert harness.read_metric(METRIC, _run([])) is None


def test_a_program_that_counts_nothing_reads_none(recorded, monkeypatch):
    """The program at a commit before the counter: the reader gives None
    and does not raise."""
    import repro

    monkeypatch.setitem(sys.modules, "repro.spans", None)
    monkeypatch.delattr(repro, "spans", raising=False)
    assert harness.read_metric(METRIC, _run(recorded)) is None


@pytest.mark.parametrize("workload", sorted(TINY_CELLS))
def test_traced_tiny_run_reads_the_schedule_copy(workload, monkeypatch):
    """The cell at a tiny size, traced on the CPU: a warm window finds its
    schedule resident on every call and copies no byte; a cold window
    copies every call's schedule."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    per_layer = [m for m in spec["per_layer"] if m["name"] in COPY_METRICS]
    warm = workload.endswith(".warm")
    listed = {m["name"] for m in spec["per_layer"]
              if workload in m.get("workloads", [])}
    assert (METRIC in listed) == warm
    real = harness.load_cell(workload)
    cell = harness.Cell("tiny", 1, TINY_CELLS[workload], real.traffic,
                        real.end_to_end, per_layer)
    monkeypatch.setattr(tracing, "read_xplane",
                        _cpu_ops_as_device(tracing.read_xplane))
    result = harness.run_cell(cell, SEED, 0.3, True, time.perf_counter(),
                              harness.lookup_peaks(V5E))
    assert result["correct"]
    metrics = {name: m["value"] for name, m in result["metrics"].items()}
    assert set(metrics) == set(COPY_METRICS)
    if warm:
        assert metrics[METRIC] == 100.0
        assert metrics["schedule_h2d_gb"] == 0.0
    else:
        assert metrics[METRIC] == 0.0
        assert metrics["schedule_h2d_gb"] > 0.0

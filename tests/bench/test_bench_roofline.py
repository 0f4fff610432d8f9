"""Roofline bytes, the table of peaks, and the benchmark's refusal to run
without a TPU."""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, tracing  # noqa: E402
from bench.metrics import tier_bytes  # noqa: E402

V5E = "TPU v5 lite"


def _tiny_schedule():
    """A 16 x 16 five-point mesh (480 edges) numbered along a Morton curve,
    in 64-id windows: its 2 x 2 blocks of 8 x 8 vertices hold 112 edges
    each; the other 32 edges cross blocks."""
    import jax.numpy as jnp
    import numpy as np

    from bench.generators.rgg import morton
    from bench.loops import schedule_stats
    from repro.graphs import EdgeList, build_window_schedule

    r, c = np.meshgrid(np.arange(16), np.arange(16), indexing="ij")
    ids = morton(r, c).astype(np.int32)
    u = np.concatenate([ids[:, :-1].ravel(), ids[:-1, :].ravel()])
    v = np.concatenate([ids[:, 1:].ravel(), ids[1:, :].ravel()])
    s = build_window_schedule(EdgeList(jnp.asarray(u), jnp.asarray(v), 256),
                              window=64, tile_size=32, reorder="none")
    assert np.count_nonzero(s.boundary_index >= 0) == 32
    return schedule_stats(s)


def test_worked_byte_count():
    s = _tiny_schedule()
    assert s["num_valid"] == 480 and s["num_windowed"] == 448
    assert s["num_rows"] == 4 and s["num_windows"] == 4
    # window tier: 448 edges x 9 B + 4 rows x 64 vertices x 2 B
    assert tier_bytes.window_tier(s) == 448 * 9 + 4 * 64 * 2 == 4544
    # boundary tier: 32 edges x 9 B + 4 windows x 64 vertices x 2 B
    assert tier_bytes.boundary_tier(s) == 32 * 9 + 4 * 64 * 2 == 800


def test_roofline_share_from_a_trace():
    s = _tiny_schedule()
    ev = [tracing.Event(tracing.HOST_PLANE, "python", "window", 0.0, 1e6),
          tracing.Event("/device:TPU:0", tracing.OPS_LINE,
                        "skipper_pipeline_kernel.1", 0.0, 2000.0),
          tracing.Event("/device:TPU:0", tracing.OPS_LINE,
                        "skipper_pipeline_kernel.1", 5000.0, 2000.0)]
    run = harness.Run(setup_s=0.0, calls=[(0, 1), (1, 2)],
                      medges_per_s=0.0, peak_bytes=0, schedule=s,
                      trace=tracing.summarize(ev),
                      peaks=harness.lookup_peaks(V5E))
    least = 2 * 4544 / 819e9                   # two calls at peak bandwidth
    share = harness.read_metric("window_kernel_roofline", run)
    assert share == pytest.approx(100 * least / 4e-6)
    # no boundary-kernel event: the metric has nothing to read
    assert harness.read_metric("boundary_kernel_roofline", run) is None


def test_peaks_lookup():
    assert harness.lookup_peaks(V5E)["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        harness.lookup_peaks("TPU v9 imaginary")


def _run_bench(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "bench", "run.py"), "--workload",
         "g500-s22.warm", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cpu_run_exits_nonzero_and_prints_no_result():
    p = _run_bench(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_bench(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""

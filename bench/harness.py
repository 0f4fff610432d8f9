"""One run of one cell: set-up, the measured window, the metrics, the check.

``BENCHMARK.json`` names the cells; a cell names a configuration (its file
under ``bench/configs/``) and a traffic mix (``bench/traffic/<mix>.json``),
which names the loop that drives the window (``bench/loops/<loop>.py``).
A metric is reported by the cells that its ``workloads`` list, or, without
the list, by every cell (an end-to-end metric) or every cell that reports
the end-to-end metric it ``moves`` (a per-layer one). Its value comes from
``bench/metrics/<name>.py``, whose ``read(run)`` returns a number or None
when the run holds nothing for it to read; a None leaves the metric out.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Optional

import jax
import numpy as np

from bench import loops, reference, tracing

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list      # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def _reported(entries: list, cell: str, moved: Optional[set] = None) -> list:
    out = []
    for m in entries:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif moved is None or m["moves"] in moved:
            out.append(m)
    return out


def load_cell(name: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = _reported(spec["end_to_end"], name)
    return Cell(name, w["chips"], config, traffic, e2e,
                _reported(spec["per_layer"], name, {m["name"] for m in e2e}))


def make_loop(cell: Cell, seed: int, interpret: bool):
    """The loop the cell's traffic names, set to drive the program."""
    return loops.load(cell.traffic["loop"]).Loop(
        cell.config, cell.traffic, seed, interpret)


def read_metric(name: str, run) -> Optional[float]:
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench.metrics._" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    value = mod.read(run)
    return None if value is None else float(value)


@dataclasses.dataclass
class Run:
    """What a metric reader may read of one run."""
    setup_s: float
    calls: list               # (start, end) host clock of the window's calls
    medges_per_s: float       # input edges x calls / calls' summed wall time
    peak_bytes: int           # device peak_bytes_in_use after the window
    schedule: dict            # the program's counts of the newest schedule
    trace: Optional[tracing.TraceSummary]
    peaks: Optional[dict]     # the chip's row of peaks.json


def lookup_peaks(kind: str) -> dict:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(it has {sorted(table)})")
    return table[kind]


def _traced_window(loop, seconds: float, spans):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            loop.window(seconds)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                         "*.xplane.pb"))
        return tracing.summarize(tracing.read_xplane(path, spans))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def check_answers(loop) -> list:
    """The reference's readings of each kept answer; an answer equal to one
    already read for the same graph has the same readings."""
    readings, seen = [], []
    for u, v, mask, state in loop.answers:
        for u2, mask2, state2, r in seen:
            if (u2 is u and np.array_equal(mask2, mask)
                    and np.array_equal(state2, state)):
                break
        else:
            r = reference.check(u, v, loop.num_vertices, mask, state)
            seen.append((u, mask, state, r))
        readings.append(r)
    return readings


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, peaks: Optional[dict]) -> dict:
    """Set up, measure, check; returns the result line's object."""
    dev = jax.devices()[0]
    loop = make_loop(cell, seed, interpret=dev.platform != "tpu")
    loop.setup()
    gc.collect()
    setup_s = time.perf_counter() - t_start

    summary = None
    if trace:
        summary = _traced_window(
            loop, seconds, loops.load(cell.traffic["loop"]).SPANS)
    else:
        loop.window(seconds)
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    took = sorted(b - a for a, b in loop.calls)
    print(f"window: {len(took)} calls, seconds min {took[0]:.4f} median "
          f"{took[len(took) // 2]:.4f} max {took[-1]:.4f}", file=sys.stderr)
    loop.close()
    gc.collect()

    run = Run(setup_s=setup_s, calls=loop.calls,
              medges_per_s=loop.medges_per_s, peak_bytes=peak,
              schedule=loop.stats, trace=summary, peaks=peaks)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = read_metric(m["name"], run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    readings = check_answers(loop)
    worst = reference.worst(readings)
    failed = sum(not reference.passes(r) for r in readings)
    correct = bool(readings) and failed == 0
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(loop.calls),
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops(),
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = {k: {"value": worst[k], "limit": lim}
                        for k, lim in reference.LIMITS.items()}
    result["checks"]["answers_checked"] = {"value": len(readings), "limit": 1}
    return result


def print_result(result: dict) -> None:
    """The compared numbers as the last lines of stderr; the result as the
    last line of stdout."""
    for k, c in result["checks"].items():
        rel = ">=" if k == "answers_checked" else "<="
        print(f"check {k}: {c['value']} {rel} {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)

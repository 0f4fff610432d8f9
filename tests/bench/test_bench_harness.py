"""A whole benchmark run at a tiny size on the CPU (Pallas in interpret
mode), past the harness's look for a chip: the program's answers pass the
reference; the control and each fault planted under the timed path make
``correct`` come out false. Also checks ``BENCHMARK.json`` against the
files the harness finds by name."""
import json
import os
import re
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import control, harness, reference  # noqa: E402

TINY_G500 = {"generator": "kronecker",
             "graph": {"scale": 10, "edgefactor": 16, "a": 0.57, "b": 0.19,
                       "c": 0.19, "graph_seed": 1},
             "schedule": {"window": 256, "tile_size": 128,
                          "reorder": "degree"}}
TINY_RGG = {"generator": "rgg", "graph": {"scale": 10, "graph_seed": 0},
            "schedule": {"window": 256, "tile_size": 128, "reorder": "none"}}
SEED = (1 << 33) + 5


def _cell(cfg, mix, name="tiny"):
    with open(os.path.join(ROOT, "bench", "traffic", mix + ".json")) as f:
        traffic = json.load(f)
    e2e = [{"name": f"medges_per_s.{mix}", "unit": "Medges/s"},
           {"name": "setup_s", "unit": "s"}]
    return harness.Cell(name, 1, cfg, traffic, e2e, [])


def _run(cell, seed=SEED):
    return harness.run_cell(cell, seed, 0.3, False, time.perf_counter(), None)


@pytest.mark.parametrize("cfg,mix", [(TINY_G500, "warm"), (TINY_RGG, "cold")],
                         ids=["g500-warm", "rgg-cold"])
def test_program_run_is_correct(cfg, mix, capsys):
    result = _run(_cell(cfg, mix))
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {f"medges_per_s.{mix}", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "checks"
    assert result["checks"]["uncovered_edges"] == {"value": 0, "limit": 0}
    harness.print_result(result)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == result
    assert err.strip().splitlines()[-4].startswith("check double_matched: 0")


BROKEN = {"control": "double_matched", "no_fallback": "uncovered_edges",
          "unchanged": "uncovered_edges", "half_left_out": "uncovered_edges",
          "answer_altered": "uncovered_edges",
          "state_altered": "state_mismatches"}


@pytest.mark.parametrize("path,broken", sorted(BROKEN.items()),
                         ids=sorted(BROKEN))
def test_fault_under_the_timed_path_is_not_correct(path, broken):
    """A whole run with the path switched on underneath reads false."""
    with control.PATHS[path]():
        result = _run(_cell(TINY_G500, "warm"))
    assert not result["correct"] and result["failed"] >= 1
    assert result["checks"][broken]["value"] > 0


@pytest.mark.parametrize("cfg,mix", [(TINY_G500, "warm"), (TINY_RGG, "cold")],
                         ids=["g500-warm", "rgg-cold"])
@pytest.mark.parametrize("path", ["control", "no_fallback"])
def test_control_is_not_correct(cfg, mix, path):
    """Without the claim check two edges of a tile share a vertex; without
    the exact fallback edges are left undecided."""
    r = control.readings(_cell(cfg, mix), 2, 0.3, [path, "program"])
    assert not r[path]["correct"] and r[path][BROKEN[path]] > 0
    # and the kernels are restored
    assert r["program"]["correct"] and r["program"]["answers"] >= 1


def test_reference_on_a_path():
    u = np.array([0, 1, 2], np.int32)
    v = np.array([1, 2, 3], np.int32)
    state = np.array([2, 2, 2, 2], np.uint8)
    ok = reference.check(u, v, 4, np.array([True, False, True]), state)
    assert ok == {"double_matched": 0, "uncovered_edges": 0,
                  "state_mismatches": 0}
    bad = reference.check(u, v, 4, np.array([True, True, False]), state)
    assert bad == {"double_matched": 1, "uncovered_edges": 0,
                   "state_mismatches": 1}        # vertex 3 is not covered
    short = reference.check(u, v, 4, np.array([True, False, True]), state[:3])
    assert not reference.passes(short)


# -- BENCHMARK.json against the files the harness finds by name -------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_names_files_that_exist(spec):
    for c in spec["configs"]:
        assert NAME.match(c["name"]) and os.path.isfile(
            os.path.join(ROOT, c["file"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["source"] == c["source"]
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        with open(os.path.join(ROOT, "bench", "traffic",
                               w["traffic"] + ".json")) as f:
            loop = json.load(f)["loop"]
        assert os.path.isfile(os.path.join(ROOT, "bench", "loops",
                                           loop + ".py"))
    metrics = spec["end_to_end"] + spec["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in spec["workloads"]}


def test_every_cell_reports_setup_another_and_a_layer(spec):
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        assert all(m["moves"] in e2e for m in cell.per_layer)
        assert 0 < min(m["bound"] for m in cell.end_to_end) <= 0.25

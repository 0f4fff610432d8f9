"""Kernel conformance analyzer (``src/repro/analysis/``, DESIGN.md §14).

Four pins:

1. **Rules discriminate.** Every rule has a minimal passing fixture and a
   minimal violating fixture — a rule that flags the good case or misses
   the bad case is broken in itself, independent of the production tree.
2. **Mutation canaries.** Each seeded mutant of the boundary kernel
   (``analysis/mutations.py``) is caught by the EXPECTED rule — the
   analyzer keeps its teeth against exactly the hazard classes the
   ROADMAP listed as "verify on silicon".
3. **The clean tree is clean.** Source battery over ``src/repro`` plus
   the kernel targets analyze to zero errors (the full 9-target sweep is
   the CI ``static-analysis`` job; here we keep the fast subset so tier-1
   stays quick).
4. **Recompile guard.** Repeated ``skipper_match`` / ``distributed_skipper``
   calls with equal configs hit the lru-cached builders (the PR 3/PR 5
   caching fixes), observed via ``cache_info`` — a regression that
   re-traces per call shows up as zero hits.
"""
import types

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from repro.analysis import Severity, analyze_mutation, analyze_sources
from repro.analysis.mutations import MUTATION_NAMES
from repro.analysis.rules.base import SourceFile, get_rules
from repro.analysis.rules.deprecated_alias import DeprecatedAlias
from repro.analysis.rules.dma_order import DmaHappensBefore, WritebackOrder
from repro.analysis.rules.host_sync import HostSync, LruStaticKey, TracedCallback
from repro.analysis.rules.mosaic_lowering import MosaicGather
from repro.analysis.rules.state_dtype import StateDtype
from repro.analysis.rules.vmem_budget import (
    BlockRace,
    PallasCount,
    TileGeometry,
    VmemBudget,
)
from repro.analysis.targets import get_targets
from repro.analysis.trace import collect_pallas_calls
from repro.graphs import grid_graph


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

def _boundary_artifact():
    (target,) = get_targets(["boundary_kernel"])
    (art,) = collect_pallas_calls(target.trace(1), target.name)
    return target, art


def _mutant_artifact(name):
    from repro.analysis.mutations import trace_kernel_mutation

    (art,) = collect_pallas_calls(trace_kernel_mutation(name), f"m:{name}")
    return art


def _tiny_call(lane, dtype=jnp.uint8, out_map=None, grid=(2, 2)):
    """Minimal synthetic pallas_call for geometry / race fixtures."""
    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    out_map = out_map or (lambda i, j: (i, 0))
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((8, lane), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec((8, lane), out_map),
        out_shape=jax.ShapeDtypeStruct((8 * grid[0], lane), dtype),
        interpret=True,
    )
    x = jax.ShapeDtypeStruct((8 * grid[0], lane), dtype)
    return jax.make_jaxpr(call)(x)


def _src(text, path="src/repro/fake_mod.py"):
    return SourceFile.parse(path, text)


# ---------------------------------------------------------------------------
# 1. rule discrimination: one passing + one violating fixture per rule
# ---------------------------------------------------------------------------

def test_mosaic_gather_rule():
    _, good = _boundary_artifact()
    assert MosaicGather().check_kernel(good) == []
    bad = _mutant_artifact("dynamic_gather")
    hits = MosaicGather().check_kernel(bad)
    assert hits and all(f.severity is Severity.ERROR for f in hits)
    assert "gather" in hits[0].message


def test_dma_happens_before_rule():
    _, good = _boundary_artifact()
    assert DmaHappensBefore().check_kernel(good) == []
    bad = _mutant_artifact("dropped_dma_wait")
    hits = DmaHappensBefore().check_kernel(bad)
    assert [f.severity for f in hits] == [Severity.ERROR]
    assert "unwaited" in hits[0].message


def test_writeback_order_rule():
    _, good = _boundary_artifact()
    assert WritebackOrder().check_kernel(good) == []
    bad = _mutant_artifact("swapped_writeback")
    hits = WritebackOrder().check_kernel(bad)
    assert [f.severity for f in hits] == [Severity.ERROR]
    # the windowed kernels have no aliased ANY state: rule not applicable
    (pt,) = get_targets(["pipeline_kernel"])
    (pa,) = collect_pallas_calls(pt.trace(1), pt.name)
    assert WritebackOrder().check_kernel(pa) == []


def test_tile_geometry_rule():
    ok = collect_pallas_calls(_tiny_call(lane=128), "t")[0]
    assert not [f for f in TileGeometry().check_kernel(ok)
                if f.severity is Severity.ERROR]
    bad = collect_pallas_calls(_tiny_call(lane=64), "t")[0]
    hits = [f for f in TileGeometry().check_kernel(bad)
            if f.severity is Severity.ERROR]
    assert hits and "128" in hits[0].message  # uint8 lane misalignment


def test_block_race_rule():
    rule = BlockRace()
    tgt = types.SimpleNamespace(name="t")
    ok = _tiny_call(lane=128, out_map=lambda i, j: (i, 0))
    arts = collect_pallas_calls(ok, "t")
    assert not [f for f in rule.check_target(tgt, ok, arts)
                if f.severity is Severity.ERROR]
    # block revisited at non-consecutive grid steps: (i,j) -> (j, 0) under
    # row-major iteration visits block 0 at steps 0 and 2
    bad = _tiny_call(lane=128, out_map=lambda i, j: (j, 0))
    arts = collect_pallas_calls(bad, "t")
    hits = [f for f in rule.check_target(tgt, bad, arts)
            if f.severity is Severity.ERROR]
    assert hits and "non-consecutive" in hits[0].message


def test_vmem_budget_rule_detects_v_dependence():
    rule = VmemBudget()

    def build(scale):
        return _tiny_call(lane=128 * scale, grid=(2, 1))

    leaky = types.SimpleNamespace(
        name="leaky", rescalable=True, vmem_claim="", trace=build,
    )
    arts = collect_pallas_calls(build(1), "leaky")
    hits = [f for f in rule.check_target(leaky, build(1), arts)
            if f.severity is Severity.ERROR]
    assert hits and "V-independence claim is broken" in hits[0].message
    # the real boundary target passes (V-independence verified as INFO)
    target, art = _boundary_artifact()
    infos = rule.check_target(target, target.trace(1), [art])
    assert not [f for f in infos if f.severity is Severity.ERROR]
    assert any("V-independence verified" in f.message for f in infos)


def test_pallas_count_rule():
    rule = PallasCount()
    tgt = types.SimpleNamespace(name="t", expect_pallas=1)
    jx = _tiny_call(lane=128)
    arts = collect_pallas_calls(jx, "t")
    assert not [f for f in rule.check_target(tgt, jx, arts)
                if f.severity is Severity.ERROR]
    hits = rule.check_target(
        types.SimpleNamespace(name="t", expect_pallas=2), jx, arts,
    )
    assert [f.severity for f in hits] == [Severity.ERROR]


def test_traced_callback_rule():
    rule = TracedCallback()
    tgt = types.SimpleNamespace(name="t")
    clean = jax.make_jaxpr(lambda x: x * 2)(jnp.ones((4,)))
    assert rule.check_target(tgt, clean, []) == []

    def with_cb(x):
        return jax.pure_callback(
            lambda a: a, jax.ShapeDtypeStruct(x.shape, x.dtype), x,
        )

    dirty = jax.make_jaxpr(with_cb)(jnp.ones((4,)))
    hits = rule.check_target(tgt, dirty, [])
    assert hits and hits[0].severity is Severity.ERROR


def test_state_dtype_rule():
    rule = StateDtype()
    assert rule.check_file(_src(
        "import jax.numpy as jnp\n"
        "def f(spec, n):\n"
        "    state = jnp.zeros((n,), spec.vmem_dtype)\n"
        "    ids = jnp.zeros((n,), jnp.int32)\n"   # not state-ish: fine
        "    return state, ids\n"
    )) == []
    hits = rule.check_file(_src(
        "import jax.numpy as jnp\n"
        "def f(n):\n"
        "    state = jnp.zeros((n,), jnp.int32)\n"
        "    return state\n"
    ))
    assert [f.severity for f in hits] == [Severity.ERROR]
    # waiver silences the same line
    assert rule.check_file(_src(
        "import jax.numpy as jnp\n"
        "def f(n):\n"
        "    state = jnp.zeros((n,), jnp.int32)  # state-dtype: ok\n"
        "    return state\n"
    )) == []


def test_host_sync_rule():
    rule = HostSync()
    assert rule.check_file(_src(
        "import jax\n"
        "def f(x):\n"
        "    return jax.device_get(x)  # host-sync: ok (documented)\n"
    )) == []
    hits = rule.check_file(_src(
        "import jax\n"
        "def f(x):\n"
        "    return jax.device_get(x)\n"
    ))
    assert [f.severity for f in hits] == [Severity.ERROR]
    # out-of-library drivers (benchmarks/) fetch freely
    assert rule.check_file(_src(
        "import jax\ndef f(x):\n    return jax.device_get(x)\n",
        path="benchmarks/bench_thing.py",
    )) == []


def test_lru_static_key_rule():
    rule = LruStaticKey()
    assert rule.check_file(_src(
        "import functools\n"
        "@functools.lru_cache(maxsize=None)\n"
        "def build(n, tile, spec=None):\n"
        "    return n\n"
    )) == []
    hits = rule.check_file(_src(
        "import functools\n"
        "@functools.lru_cache(maxsize=None)\n"
        "def build(n, opts=[]):\n"
        "    return n\n"
    ))
    assert [f.severity for f in hits] == [Severity.ERROR]


def test_deprecated_alias_rule():
    rule = DeprecatedAlias()
    assert rule.check_file(_src(
        "def f(stats):\n    return stats.gathered_bytes\n"
    )) == []
    hits = rule.check_file(_src(
        "def f(stats):\n    return stats.gathered_ints\n"
    ))
    assert [f.severity for f in hits] == [Severity.ERROR]
    # the definition site and tests are exempt
    assert rule.check_file(_src(
        "def f(s):\n    return s.gathered_ints\n",
        path="src/repro/core/distributed.py",
    )) == []
    assert rule.check_file(_src(
        "def f(s):\n    return s.gathered_ints\n",
        path="tests/test_statespec.py",
    )) == []


# ---------------------------------------------------------------------------
# 2. mutation canaries: each mutant caught by the EXPECTED rule
# ---------------------------------------------------------------------------

EXPECTED_RULE = {
    "dropped_dma_wait": "dma-happens-before",
    "swapped_writeback": "writeback-order",
    "dynamic_gather": "mosaic-gather",
    "hardcoded_state_dtype": "state-dtype",
}


@pytest.mark.parametrize("name", sorted(EXPECTED_RULE))
def test_mutation_canary_caught(name):
    report = analyze_mutation(name)
    assert not report.clean, f"mutant {name} analyzed clean: teeth lost"
    assert EXPECTED_RULE[name] in {f.rule for f in report.errors}


def test_mutation_registry_complete():
    assert sorted(MUTATION_NAMES) == sorted(EXPECTED_RULE)
    with pytest.raises(KeyError):
        analyze_mutation("no_such_mutation")


# ---------------------------------------------------------------------------
# 3. the clean tree is clean (fast subset; full sweep runs in CI)
# ---------------------------------------------------------------------------

def test_clean_tree_sources():
    report = analyze_sources(["src/repro", "benchmarks", "examples"])
    assert report.clean, report.render()
    assert report.files_analyzed > 50


def test_clean_kernel_targets():
    from repro.analysis.runner import analyze_targets

    report = analyze_targets(
        ["window_kernel", "pipeline_kernel", "boundary_kernel"]
    )
    assert report.clean, report.render()
    assert len(report.targets_analyzed) == 3
    # the budget measurements land in the JSON next to the roofline numbers
    d = report.to_dict()
    assert d["version"] == 1 and d["clean"]
    budgets = [f for f in report.findings
               if f.rule == "vmem-budget" and f.data
               and "total_bytes" in f.data]
    assert budgets


def test_roofline_vmem_hook():
    from repro.roofline import vmem_step_bytes

    out = vmem_step_bytes("boundary_kernel")
    assert out["skipper_boundary_kernel"]["total_bytes"] > 0


# ---------------------------------------------------------------------------
# 4. recompile guard: equal configs must hit the cached builders
# ---------------------------------------------------------------------------

def test_skipper_match_recompile_guard():
    from repro.kernels.skipper_match import ops, skipper_match

    g = grid_graph(16, 16)
    kw = dict(window=256, tile_size=256)
    skipper_match(g, **kw)
    before = ops._build_pipeline.cache_info()
    skipper_match(g, **kw)
    after = ops._build_pipeline.cache_info()
    assert after.hits > before.hits, (
        f"equal-config skipper_match re-traced: {before} -> {after}"
    )


def test_distributed_skipper_recompile_guard():
    from repro.core import distributed
    from repro.core.distributed import distributed_skipper

    g = grid_graph(16, 16)
    distributed_skipper(g, block_size=256)
    before = distributed._compiled_dispersed.cache_info()
    distributed_skipper(g, block_size=256)
    after = distributed._compiled_dispersed.cache_info()
    assert after.hits > before.hits

    distributed_skipper(g, block_size=256, window=256, reorder="none")
    before = distributed._compiled_sharded.cache_info()
    distributed_skipper(g, block_size=256, window=256, reorder="none")
    after = distributed._compiled_sharded.cache_info()
    assert after.hits > before.hits

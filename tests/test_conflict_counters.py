"""``skipper_match`` gathers the per-edge conflicts back to stream order only
when the caller asks ``with_conflicts=True``; ``Counters`` are summed over
the slot-order decision buffers on every call. These tests pin that both
pipelines give the same ``MatchResult``, that the ``Counters`` equal what
the stream-order conflicts imply (also under the fault sites that zero or
invalidate slots), and that the pipeline without conflicts compiles no
conflicts gather and no per-edge conflicts output."""
import re

import numpy as np
import pytest

from repro.core.faults import FaultPlan, proposal_drop_mask
from repro.core.statespec import StateSpec
from repro.graphs import erdos_renyi_graph
from repro.graphs.windows import build_window_schedule
from repro.kernels.skipper_match import ops, skipper_match

SPECS = {"default": None, "legacy_i32": StateSpec.legacy_i32()}
FAULTS = {
    "none": None,
    "lose_shard": FaultPlan(seed=7, lose_shard=0),
    "drop_proposals": FaultPlan(seed=7, drop_proposals=0.3),
}


def _schedule(reorder):
    g = erdos_renyi_graph(300, 900, seed=3)
    s = build_window_schedule(g, window=32, tile_size=32, reorder=reorder)
    assert s.num_rows > 0 and s.num_boundary_padded > 0  # both tiers run
    return g, s


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("reorder", ["none", "degree"])
@pytest.mark.parametrize("spec", list(SPECS))
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_counters_in_slot_order_equal_the_stream_conflicts(
    backend, spec, reorder, fault
):
    g, s = _schedule(reorder)
    kw = dict(schedule=s, backend=backend, spec=SPECS[spec],
              faults=FAULTS[fault])
    plain = skipper_match(**kw)
    full, conf = skipper_match(with_conflicts=True, **kw)
    conf = np.asarray(conf)
    assert conf.dtype == np.int32 and conf.shape == (g.num_edges,)
    assert conf.sum() > 0

    np.testing.assert_array_equal(plain.match_mask, full.match_mask)
    np.testing.assert_array_equal(plain.state, full.state)
    assert plain.state.dtype == full.state.dtype
    m = g.num_edges
    want = (m, 2 * m + 2 * int(conf.sum()),
            2 * int(np.asarray(full.match_mask).sum()), 1)
    for counters in (plain.counters, full.counters):
        got = tuple(int(x) for x in (
            counters.edge_reads, counters.state_loads,
            counters.state_stores, counters.rounds))
        assert got == want

    if fault == "lose_shard":
        # the lost row's matched bits are zeroed, its conflicts are not:
        # both stay counted
        lost = s.edge_index[FAULTS[fault].lose_shard % s.num_rows]
        assert conf[lost[lost >= 0]].sum() > 0
        assert not np.asarray(full.match_mask)[lost[lost >= 0]].any()
    if fault == "drop_proposals":
        # dropped global-tier slots are invalid: their edges count nothing
        drop = np.asarray(proposal_drop_mask(
            FAULTS[fault], s.num_boundary_padded))
        dropped = s.boundary_index[drop & (s.boundary_index >= 0)]
        assert dropped.size > 0
        assert conf[dropped].sum() == 0


def _entry_outputs(text):
    return re.search(r"entry_computation_layout=\{\(.*?\)->\((.*?)\)\}",
                     text).group(1)


def _scoped_gathers(text, scope):
    return [line for line in text.splitlines()
            if re.search(r"=\s*\S+\s+gather\(", line)
            and f"/{scope}/" in line]


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_pipeline_without_conflicts_compiles_no_conflicts_gather(backend):
    g, s = _schedule("none")
    m = g.num_edges
    texts = {}
    for with_conflicts in (False, True):
        skipper_match(schedule=s, backend=backend,
                      with_conflicts=with_conflicts)
        texts[with_conflicts] = ops._COMPILED[
            next(reversed(ops._COMPILED))].as_text()
        if not with_conflicts:
            _, scopes = ops.op_scopes()
            assert set(scopes.values()) == set(ops.SCOPES)

    assert f"s32[{m}]" not in _entry_outputs(texts[False])
    assert not _scoped_gathers(texts[False], "conflict_gather")
    # the pipeline that returns the conflicts still gathers them
    assert f"s32[{m}]" in _entry_outputs(texts[True])
    assert _scoped_gathers(texts[True], "conflict_gather")

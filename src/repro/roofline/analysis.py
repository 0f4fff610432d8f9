"""Byte budgets of the matcher, for roofline studies.

:func:`state_traffic_bytes` turns a run's state-access counters into HBM
bytes under a state spec; :func:`vmem_step_bytes` reads a traced kernel's
per-grid-step VMEM budget from the conformance analyzer. The benchmark's
kernel rooflines are computed from the device trace in
``bench/metrics/tier_bytes.py``, not here.

Hardware constant: TPU v5e HBM, 819 GB/s.
"""
from __future__ import annotations

from typing import Dict

HBM_BW = 819e9               # bytes/s / chip


def state_traffic_bytes(counters, spec=None) -> Dict[str, float]:
    """State-array traffic of a matcher run in BYTES under a state spec.

    ``core/types.Counters`` counts state *accesses* (the paper's PAPI
    convention — loads + stores of ``state[]``); the roofline wants bytes,
    and the byte-per-access factor is exactly what ``core/statespec``
    decides: the hot loop touches state at the spec's VMEM width, so the
    uint8 default moves 4x fewer state bytes than the legacy int32 graph
    for the same access counts. Edge-topology reads stay int32 (2 endpoint
    ids, 8 B per edge read) at every spec.

    Returns ``{"state_bytes", "edge_bytes", "total_bytes", "memory_s"}``
    where ``memory_s`` is the HBM term these bytes contribute at the
    modeled bandwidth.
    """
    from repro.core.statespec import resolve as resolve_spec

    spec = resolve_spec(spec)
    accesses = int(counters.state_loads) + int(counters.state_stores)
    state_b = float(accesses * spec.vmem_bytes)
    edge_b = float(int(counters.edge_reads) * 8)  # 2 x i32 endpoints
    total = state_b + edge_b
    return {
        "state_bytes": state_b,
        "edge_bytes": edge_b,
        "total_bytes": total,
        "memory_s": total / HBM_BW,
    }


def vmem_step_bytes(target: str = "boundary_kernel") -> Dict[str, Dict]:
    """Per-grid-step VMEM byte budget of a traced kernel target, keyed by
    kernel name — the static companion to :func:`state_traffic_bytes`.

    Delegates to the conformance analyzer (``repro.analysis``): the target
    is traced to a jaxpr on CPU and each pallas kernel's resident bytes
    are decomposed into double-buffered blocks, scratch, and a liveness
    upper bound on intermediates — the same numbers the ``vmem-budget``
    rule gates in CI, surfaced here so roofline studies can quote them.
    Targets: see ``repro.analysis.targets.TARGETS`` (e.g.
    ``boundary_kernel``, ``pipeline_kernel``, ``window_kernel``).
    """
    from repro.analysis.rules.vmem_budget import kernel_step_bytes
    from repro.analysis.targets import get_targets
    from repro.analysis.trace import collect_pallas_calls

    (tgt,) = get_targets([target])
    arts = collect_pallas_calls(tgt.trace(1), tgt.name)
    return {a.name: kernel_step_bytes(a) for a in arts}

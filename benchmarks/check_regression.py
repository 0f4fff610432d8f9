"""Bench-smoke regression gate (CI).

Compares a freshly recorded kernel_bench JSON against the committed baseline
and fails if any gated row (``kernel/windowed_pipeline/*``,
``kernel/distributed_pipeline/*``, ``kernel/boundary_pipeline/*`` or
``kernel/bmatch/*``) regressed beyond the tolerance. Two extra gates ride
along: ``kernel/distributed_pipeline_hooks/*`` (the fault-harness overhead
row, 2% per-prefix tolerance vs the plain pipeline row of the same run) and
a hard zero-check on the recovery fields the fault-free verified bench run
records (nonzero = silently dropped work, a correctness failure).

CI runners and the recording machine differ in absolute speed, so raw
``us_per_call`` comparisons are meaningless across hosts. Each gated row is
therefore NORMALIZED by a same-run sibling (both sides share the engine and
the host, so machine speed cancels): the windowed pipeline by the jnp tiled
matcher of the same graph, the locality-sharded distributed matcher by the
dispersed jnp-local-pass distributed baseline (same 4-device bench
process), and the b-matching router by the same-run
``window_match/tile128`` row (both engine-bound jnp tile passes):

    ratio(run, row) = us(gated_row) / us(norm_row)

and the gate is ``ratio_new <= ratio_baseline * (1 + tolerance)``.

Usage:
    python benchmarks/check_regression.py new.json baseline.json [--tolerance 0.2]
"""
from __future__ import annotations

import argparse
import json
import sys

# gated prefix -> same-run normalization prefix; the _noreorder twin is
# reported but not gated (it exists for the trajectory, and flakes more:
# no reorder => epilogue-dominated timing)
PREFIXES = {
    "kernel/windowed_pipeline/": "kernel/jnp_matcher/",
    "kernel/distributed_pipeline/": "kernel/distributed_jnp_local/",
    # boundary-heavy (no-reorder rmat14, global tier dominant): gates the
    # block-pair epilogue against the same-run jnp tiled matcher
    "kernel/boundary_pipeline/": "kernel/boundary_jnp/",
    # the fault-harness hooks row runs the IDENTICAL compiled work through
    # the harness plumbing (inert FaultPlan + policy epilogue) — normalized
    # by the plain pipeline row of the same run so the gate is exactly
    # "what do the hooks cost", machine speed cancelled
    "kernel/distributed_pipeline_hooks/": "kernel/distributed_pipeline/",
    # state-width A/B: the single-byte default spec normalized by the
    # same-run legacy_i32 twin on the SAME schedule — gates "narrow state
    # must not cost throughput"; the byte-reduction claim itself is the
    # hard STATE_BYTES_FIELDS check below
    "kernel/state_u8/": "kernel/state_legacy_i32/",
}
# per-prefix overrides of the global --tolerance: the hooks row must track
# the plain pipeline row within 2% (DESIGN.md §11 — default-off means free)
PREFIX_TOLERANCE = {
    "kernel/distributed_pipeline_hooks/": 0.02,
}
# recovery fields recorded by the fault-free verified bench run — any
# nonzero value means the matcher silently dropped or corrupted work, which
# is a correctness failure, not a perf regression
RECOVERY_FIELDS = (
    "recovery_attempts", "residual_edges",
    "recovered_matches", "corrupted_cells",
)
# state-width hard gate: the u8 row's recorded state payloads must undercut
# its same-run legacy_i32 twin by at least this factor (DESIGN.md §12 — the
# refactor's memory claim; analytic fields, so no timer noise allowance)
STATE_BYTES_FIELDS = ("vmem_state_bytes", "wire_state_bytes")
STATE_BYTES_MIN_REDUCTION = 3.5
INFO_PREFIXES = {
    "kernel/windowed_pipeline_noreorder/": "kernel/jnp_matcher/",
}
# gated prefix -> one FIXED same-run row (no per-graph suffix): every
# kernel/bmatch/* case normalizes by the single windowed-oracle row
FIXED_NORMS = {
    "kernel/bmatch/": "kernel/window_match/tile128",
}


def _ratios(data: dict, prefixes=PREFIXES, fixed_norms=()) -> dict:
    """Gated-row -> normalized-ratio map. ``prefixes`` pairs a gated prefix
    with a same-suffix normalizer prefix; ``fixed_norms`` pairs a gated
    prefix with ONE fixed normalizer row (pass FIXED_NORMS explicitly on
    gating calls; informational calls leave it empty)."""
    out = {}
    for name, row in data.items():
        for prefix, norm_prefix in prefixes.items():
            if name.startswith(prefix):
                graph = name[len(prefix):]
                norm = data.get(norm_prefix + graph)
                if norm is None:
                    continue
                out[name] = row["us_per_call"] / norm["us_per_call"]
        for prefix, norm_name in dict(fixed_norms).items():
            if name.startswith(prefix):
                norm = data.get(norm_name)
                if norm is None:
                    continue
                out[name] = row["us_per_call"] / norm["us_per_call"]
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("new_json")
    ap.add_argument("baseline_json")
    ap.add_argument("--tolerance", type=float, default=0.2,
                    help="allowed relative slowdown of the jnp-normalized ratio")
    args = ap.parse_args()

    with open(args.new_json) as f:
        new_data = json.load(f)
    with open(args.baseline_json) as f:
        base_data = json.load(f)
    new = _ratios(new_data, fixed_norms=FIXED_NORMS)
    base = _ratios(base_data, fixed_norms=FIXED_NORMS)

    info_base = _ratios(base_data, INFO_PREFIXES)
    for name, r in sorted(_ratios(new_data, INFO_PREFIXES).items()):
        b = info_base.get(name)
        print(f"{name}: ratio {r:.3f} vs baseline "
              f"{'%.3f' % b if b is not None else 'n/a'} (informational)")

    failed = []
    for name, row in sorted(new_data.items()):
        bad = {k: row[k] for k in RECOVERY_FIELDS if row.get(k)}
        if bad:
            print(f"{name}: nonzero recovery fields {bad} FAIL")
            failed.append(f"{name}: fault-free run reported {bad}")
    for name, row in sorted(new_data.items()):
        if not name.startswith("kernel/state_u8/"):
            continue
        twin = new_data.get(
            "kernel/state_legacy_i32/" + name[len("kernel/state_u8/"):])
        if twin is None:
            failed.append(f"{name}: legacy_i32 twin missing from new run")
            continue
        for field in STATE_BYTES_FIELDS:
            u8_b, i32_b = row.get(field), twin.get(field)
            if not u8_b or not i32_b:
                failed.append(f"{name}: missing byte field {field}")
                continue
            reduction = i32_b / u8_b
            verdict = ("ok" if reduction >= STATE_BYTES_MIN_REDUCTION
                       else "FAIL")
            print(f"{name}: {field} reduction {reduction:.2f}x "
                  f"(min {STATE_BYTES_MIN_REDUCTION}x) {verdict}")
            if verdict == "FAIL":
                failed.append(
                    f"{name}: {field} reduced only {reduction:.2f}x")
    for name, r_base in sorted(base.items()):
        r_new = new.get(name)
        if r_new is None:
            failed.append(f"{name}: missing from new run")
            continue
        tol = args.tolerance
        for prefix, p_tol in PREFIX_TOLERANCE.items():
            if name.startswith(prefix):
                tol = p_tol
                # the hooks gate means "hooks add at most tol to the plain
                # row" — a baseline ratio < 1 is timer noise, and taking it
                # literally would shrink the limit below the claim
                r_base = max(r_base, 1.0)
        limit = r_base * (1.0 + tol)
        verdict = "FAIL" if r_new > limit else "ok"
        print(f"{name}: ratio {r_new:.3f} vs baseline {r_base:.3f} "
              f"(limit {limit:.3f}) {verdict}")
        if r_new > limit:
            failed.append(f"{name}: {r_new:.3f} > {limit:.3f}")
    if not base:
        print("no gated pipeline rows in baseline — nothing to check")
    if failed:
        print("\nregressions:\n  " + "\n  ".join(failed))
        return 1
    print("\nno gated pipeline regression beyond tolerance")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Shared test strategies: graph/stream generators, the optional-hypothesis
shim, and the forced-device subprocess runner.

This folds the old ``_hyp.py`` shim in — import ``given`` / ``settings`` /
``st`` from here. When hypothesis is installed they are the real thing;
when it is not (minimal containers), ``given``/``settings`` decorate the
test as skipped and ``st`` is an inert stub (its strategy constructors are
only evaluated at decoration time). Deterministic pins in the same module
keep running either way.

The generators are plain numpy builders shared by the per-file suites
(matching core, boundary pair, statespec, faults, APRAM conformance) so
each file stops growing its own slightly-different ``_graph`` helper:

* :func:`random_edge_list` — uniform endpoints, with optional knobs for
  the stream hazards the protocol must survive (self-loops, duplicate
  slots, invalid ``-1`` padding, canonicalization).
* :func:`adversarial_edge_list` — the contention mix the fuzzer uses
  (hub fan-in + chain runs + duplicates + self-loops + padding).
* :func:`random_candidate_stream` — b-matching candidate streams with
  invalid slots, for the bipartite/MoE suites.
* :func:`run_subprocess` — run a script under
  ``--xla_force_host_platform_device_count=N`` (moved here from
  test_distributed so the faults/statespec/apram suites stop importing a
  test module for it).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised in minimal containers
    HAVE_HYPOTHESIS = False

    def _skip_decorator(*_a, **_k):
        return pytest.mark.skip(reason="hypothesis not installed")

    given = settings = _skip_decorator

    class st:  # noqa: N801 - strategy stubs, evaluated at decoration only
        _inert = staticmethod(lambda *a, **k: None)
        integers = floats = booleans = sampled_from = lists = text = _inert
        tuples = _inert


#: common strategy bundles (inert without hypothesis — decoration-time only)
seeds = st.integers(0, 2**31 - 1)


# ---------------------------------------------------------------------------
# graph / stream builders (plain numpy; no hypothesis dependency)
# ---------------------------------------------------------------------------
def random_edge_list(rng, n, m, *, canonical=False, self_loops=0.0,
                     duplicates=0.0, invalid=0.0):
    """Uniform random ``EdgeList`` with optional stream hazards.

    ``rng`` is a ``numpy.random.Generator`` or an int seed. ``self_loops``
    / ``duplicates`` / ``invalid`` are per-slot probabilities: loops force
    ``v = u``, duplicates copy another stream slot, invalid slots become
    ``(-1, -1)`` padding. ``canonical=True`` returns ``u <= v`` per edge
    (what the window-schedule builders expect)."""
    import jax.numpy as jnp

    from repro.graphs.types import EdgeList

    if not isinstance(rng, np.random.Generator):
        rng = np.random.default_rng(rng)
    u = rng.integers(0, n, m).astype(np.int64)
    v = rng.integers(0, n, m).astype(np.int64)
    if duplicates:
        dup = rng.random(m) < duplicates
        src = rng.integers(0, m, m)
        u = np.where(dup, u[src], u)
        v = np.where(dup, v[src], v)
    if self_loops:
        v = np.where(rng.random(m) < self_loops, u, v)
    if invalid:
        pad = rng.random(m) < invalid
        u = np.where(pad, -1, u)
        v = np.where(pad, -1, v)
    if canonical:
        u, v = np.minimum(u, v), np.maximum(u, v)
    return EdgeList(jnp.asarray(u, jnp.int32), jnp.asarray(v, jnp.int32),
                    int(n))


def adversarial_edge_list(seed, n=64, m=192):
    """The fuzzer's contention mix as an ``EdgeList``: a few hot hubs,
    path-like chain runs, duplicate slots, self-loops and invalid padding
    — the shapes reservation-order bugs are sensitive to."""
    import jax.numpy as jnp

    from repro.graphs.types import EdgeList

    rng = np.random.default_rng(seed)
    hubs = rng.integers(0, max(2, n // 10), m)
    chain = np.arange(m) % (n - 1)
    ru = rng.integers(0, n, m)
    rv = rng.integers(0, n, m)
    pick = rng.integers(0, 4, m)
    u = np.select([pick == 0, pick == 1], [hubs, chain], ru)
    v = np.select([pick == 0, pick == 1], [rv, chain + 1], rv)
    dup = rng.random(m) < 0.10
    src = rng.integers(0, m, m)
    u = np.where(dup, u[src], u)
    v = np.where(dup, v[src], v)
    v = np.where(rng.random(m) < 0.05, u, v)
    pad = rng.random(m) < 0.08
    u = np.where(pad, -1, u)
    v = np.where(pad, -1, v)
    return EdgeList(jnp.asarray(u, jnp.int32), jnp.asarray(v, jnp.int32),
                    int(n))


def random_candidate_stream(rng, num_tokens, num_experts, m, *,
                            invalid=0.05):
    """B-matching candidate stream ``(token_ids, expert_ids)`` as int32
    numpy arrays, with ``invalid`` fraction of ``token_id = -1`` slots."""
    if not isinstance(rng, np.random.Generator):
        rng = np.random.default_rng(rng)
    tok = rng.integers(0, num_tokens, m).astype(np.int32)
    exp = rng.integers(0, num_experts, m).astype(np.int32)
    if invalid:
        tok[rng.random(m) < invalid] = -1
    return tok, exp


# ---------------------------------------------------------------------------
# forced-device subprocess runner (from test_distributed)
# ---------------------------------------------------------------------------
def run_subprocess(script: str, num_devices: int, timeout: int = 900):
    """Run ``script`` in a fresh interpreter with
    ``--xla_force_host_platform_device_count=num_devices`` (the main pytest
    process keeps its single-device jax). The script must print
    ``SUBPROCESS_OK`` on success.

    Forced host devices are CPU devices, so the child is pinned to the CPU
    platform: it never asks for an accelerator, which belongs to one
    process at a time and may be held by this one."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={num_devices}"
    )
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", script],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SUBPROCESS_OK" in proc.stdout, proc.stdout[-2000:]

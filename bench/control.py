"""The control of ``correct``, and the program's readings beside it.

    python bench/control.py --workload g500-s22.warm --path control --seeds 1 2 3

``--path`` takes several paths, and ``--seeds`` several seeds: each path
is read on each seed, in one process, and one set-up on a seed serves
every path.

The configurations state no precision, so the control breaks a guarantee
they state, the way a change that would tempt a later PR breaks it:

* ``control``: the kernels' claim check switched off — no edge of a tile is
  ever blocked by a lower edge that claims one of its endpoints, so two
  edges of one tile that share a free vertex both commit. Validity breaks
  (``double_matched``) on every cell.
* ``no_fallback``: the program's own ``fallback=False`` in both Pallas
  kernel factories — edges that lose a claim in the one vectorised round
  stay undecided. Maximality breaks on the geometric graphs; on the
  Kronecker graph later tiles cover every endpoint it leaves free.
* ``unchanged``, ``half_left_out``, ``answer_altered``, ``state_altered``:
  faults planted where ``skipper_match`` returns its answer — nothing
  matched and the state as it started; the second half of the edge stream
  never decided; one selected edge dropped from the mask; one matched
  vertex's state set back to ACC.
* ``program``: the kernels as they are.

For each seed the script runs the cell's set-up, then for each path a
short window at the cell's own sizes and load, and prints the reference's
worst readings over that window's kept answers as one JSON line. One
process reads every seed, so set-up that the seeds share (compilation) is
paid once.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@contextlib.contextmanager
def _rebuilt():
    """Drop every compiled kernel and pipeline on entry and on exit, so the
    patched kernels are traced anew and the real ones come back after."""
    from repro.kernels.skipper_match import kernel, ops

    caches = (ops._build_pipeline, kernel.build_pipeline_matcher,
              kernel.build_boundary_matcher)

    def clear():
        for c in caches:
            c.cache_clear()

    clear()
    try:
        yield
    finally:
        clear()


@contextlib.contextmanager
def claim_check_off():
    """Every tile's blocked test answers "not blocked"."""
    import jax.numpy as jnp
    from repro.kernels.skipper_match import kernel

    saved = kernel._blocked_on_mxu
    kernel._blocked_on_mxu = lambda conflict: (
        lambda free: jnp.zeros_like(free))
    try:
        with _rebuilt():
            yield
    finally:
        kernel._blocked_on_mxu = saved


@contextlib.contextmanager
def fallback_off():
    """Both Pallas kernel factories of ``skipper_match`` with ``fallback``
    (their sixth argument) False."""
    from repro.kernels.skipper_match import kernel, ops

    saved = kernel.build_pipeline_matcher, ops.build_boundary_matcher

    def off(factory):
        def build(*args):
            if len(args) != 8:
                raise TypeError("kernel factory signature changed: "
                                f"{len(args)} arguments")
            return factory(*args[:5], False, *args[6:])
        build.cache_clear = factory.cache_clear
        return build

    kernel.build_pipeline_matcher = off(saved[0])
    ops.build_boundary_matcher = off(saved[1])
    try:
        with _rebuilt():
            yield
    finally:
        kernel.build_pipeline_matcher, ops.build_boundary_matcher = saved


def _unchanged(mask, state):
    return np.zeros_like(mask), np.zeros_like(state)


def _half_left_out(mask, state):
    mask[mask.shape[0] // 2:] = False
    return mask, state


def _answer_altered(mask, state):
    mask[np.flatnonzero(mask)[0]] = False
    return mask, state


def _state_altered(mask, state):
    state[np.flatnonzero(state == 2)[0]] = 0
    return mask, state


def _planted(alter):
    """``skipper_match`` whose answer passes through ``alter(mask, state)``
    on the host before the caller sees it."""

    @contextlib.contextmanager
    def patched():
        import repro.kernels.skipper_match as sm
        from repro.core.types import MatchResult

        real = sm.skipper_match

        def call(*args, **kwargs):
            res = real(*args, **kwargs)
            mask, state = alter(np.array(res.match_mask), np.array(res.state))
            return MatchResult(match_mask=mask, state=state,
                               counters=res.counters)

        sm.skipper_match = call
        try:
            yield
        finally:
            sm.skipper_match = real

    return patched


PATHS = {"program": contextlib.nullcontext, "control": claim_check_off,
         "no_fallback": fallback_off, "unchanged": _planted(_unchanged),
         "half_left_out": _planted(_half_left_out),
         "answer_altered": _planted(_answer_altered),
         "state_altered": _planted(_state_altered)}


def readings(cell, seed: int, seconds: float, paths) -> dict:
    """For each of ``paths``, the reference's worst readings over one short
    window's answers; one set-up on ``seed`` serves every path."""
    from bench import harness, reference

    import jax

    loop = harness.make_loop(cell, seed, jax.devices()[0].platform != "tpu")
    loop.setup()
    out = {}
    for path in paths:
        loop.calls, loop.answers = [], []
        with PATHS[path]():
            loop.window(seconds)
        r = harness.check_answers(loop)
        out[path] = dict(reference.worst(r), answers=len(r),
                         correct=bool(r) and all(map(reference.passes, r)))
    loop.close()
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--path", choices=sorted(PATHS), nargs="+",
                    required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args()

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    cell = harness.load_cell(args.workload)
    import jax

    jax.config.update(
        "jax_compilation_cache_dir",
        os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or os.path.join(ROOT, ".jax_cache"))
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        sys.exit(2)
    for seed in args.seeds:
        t0 = time.perf_counter()
        for path, r in readings(cell, seed, args.seconds, args.path).items():
            print(json.dumps(dict(workload=cell.name, path=path, seed=seed,
                                  seconds=time.perf_counter() - t0, **r)),
                  flush=True)

if __name__ == "__main__":
    main()

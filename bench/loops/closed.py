"""A closed loop of one caller: each call starts when the one before it
has returned.

The traffic file's ``schedule`` says what a call is:

* ``"setup"``: the schedule of graph 0 is built once, in set-up, and every
  call matches it (warm);
* ``"per_call"``: every call takes a stream order that the process has not
  matched before, wraps it as an ``EdgeList``, builds its schedule and
  matches it (cold). The next stream order is made on the host between
  calls (span ``make_graph``), outside the calls' time, as the caller's
  producer would hand it over.

A call is what a user of the program writes (``README.md``):
``build_window_schedule``, ``skipper_match`` on the Pallas path, and the
fetch of the match mask and the vertex state. Each part runs inside a host
span (``SPANS``) that a traced run reads.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import generators
from bench.loops import schedule_stats
from bench.tracing import WINDOW

SPANS = ("make_graph", "schedule_build", "skipper_match", "fetch_mask")
MODES = ("setup", "per_call")
SAMPLE_ANSWERS = 8    # window answers kept for the reference


class Loop:
    """One caller driving the program with one cell's traffic."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, interpret: bool):
        if traffic["schedule"] not in MODES:
            raise ValueError(f"schedule must be one of {MODES}: {traffic}")
        self.cfg, self.seed, self.interpret = cfg, seed, interpret
        self.per_call = traffic["schedule"] == "per_call"
        self.schedule = None      # the set-up schedule (warm)
        self.stats = None         # schedule_stats of the newest schedule
        self.calls = []           # (start, end) host clock of window calls
        self.answers = []         # kept (u, v, mask, state)
        self._rng = np.random.default_rng(seed)
        self._made = 0            # stream orders made so far

    # -- the program, as its users call it ------------------------------
    def _build(self, u, v):
        from repro.graphs import EdgeList, build_window_schedule

        sc = self.cfg["schedule"]
        with jax.profiler.TraceAnnotation("schedule_build"):
            s = build_window_schedule(
                EdgeList(jnp.asarray(u), jnp.asarray(v), self.num_vertices),
                window=sc["window"], tile_size=sc["tile_size"],
                reorder=sc["reorder"])
        self.stats = schedule_stats(s)
        return s

    def _call(self, u, v):
        s = self._build(u, v) if self.per_call else self.schedule
        import repro.kernels.skipper_match as sm

        with jax.profiler.TraceAnnotation("skipper_match"):
            res = jax.block_until_ready(sm.skipper_match(
                schedule=s, backend="pallas", interpret=self.interpret))
        with jax.profiler.TraceAnnotation("fetch_mask"):
            return jax.device_get((res.match_mask, res.state))

    # -- the phases --------------------------------------------------------
    def _next_graph(self):
        """The stream order the next call takes: a new one per call (cold),
        graph 0 on every call (warm)."""
        if self._made and not self.per_call:
            return self._graph
        with jax.profiler.TraceAnnotation("make_graph"):
            self._graph = self._make(self.seed, self._made)
        self._made += 1
        return self._graph

    def setup(self) -> None:
        """Make graph 0, build the warm schedule and make one warm-up call
        on graph 0, which loads or compiles every program the window
        runs."""
        t0 = time.perf_counter()
        self._make, self.num_vertices = generators.stream_maker(self.cfg)
        u, v = self._next_graph()
        self.num_edges = int(u.shape[0])
        if not self.per_call:
            self.schedule = self._build(u, v)
        t1 = time.perf_counter()
        self._call(u, v)
        print(f"setup: graph and schedule {t1 - t0:.3f} s, warm-up call "
              f"{time.perf_counter() - t1:.3f} s", file=sys.stderr)

    def window(self, seconds: float) -> None:
        """Call back to back; a call that starts before ``seconds`` have
        passed runs to its end, and the window ends with it."""
        end = time.perf_counter() + seconds
        with jax.profiler.TraceAnnotation(WINDOW):
            while not self.calls or time.perf_counter() < end:
                u, v = self._next_graph()
                t0 = time.perf_counter()
                mask, state = self._call(u, v)
                self.calls.append((t0, time.perf_counter()))
                self._keep((u, v, mask, state))

    def _keep(self, answer) -> None:
        """Reservoir sample of the window's answers, drawn from the seed."""
        i = len(self.calls) - 1
        if i < SAMPLE_ANSWERS:
            self.answers.append(answer)
        else:
            j = int(self._rng.integers(0, i + 1))
            if j < SAMPLE_ANSWERS:
                self.answers[j] = answer

    def close(self) -> None:
        """Drop what holds the program's state before the reference runs."""
        self.schedule = None

    @property
    def medges_per_s(self) -> float:
        """Input edges of the window's calls over the calls' summed wall
        time, in millions per second."""
        busy = sum(b - a for a, b in self.calls)
        return len(self.calls) * self.num_edges / busy / 1e6

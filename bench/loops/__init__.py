"""Traffic loops: what drives the program during the measured window.

A traffic mix (``bench/traffic/<mix>.json``) is data: it names its loop
(``"loop"``) and gives the parameters that loop reads. A loop is a module
``bench/loops/<loop>.py``, found by that name, that defines
``Loop(cfg, traffic, seed, interpret)`` and ``SPANS``, the host spans its
window's parts run in. A ``Loop`` has:

* ``setup()``: make the graphs, build what set-up builds, warm up every
  program the window runs;
* ``window(seconds)``: drive the program; afterwards ``calls`` holds the
  (start, end) host clock of each whole call and ``answers`` a sample of
  ``(u, v, mask, state)``, drawn from the seed, for the reference;
* ``close()``: drop what holds the program's state;
* ``num_vertices``, ``num_edges`` (per call), ``stats`` (the program's
  counts of the newest schedule, ``schedule_stats``) and
  ``medges_per_s``.

A new kind of traffic is a new loop module and a traffic file naming it.
"""
from __future__ import annotations

import importlib


def load(name: str):
    """The loop module ``bench/loops/<name>.py``."""
    return importlib.import_module(f"bench.loops.{name}")


def schedule_stats(s) -> dict:
    """The program's exact counts of one ``WindowSchedule``."""
    return {
        "window": s.window,
        "tile_size": s.tile_size,
        "num_windows": s.num_windows,
        "num_rows": s.num_rows,
        "tiles_per_window": s.tiles_per_window,
        "num_boundary_padded": s.num_boundary_padded,
        "num_valid": s.num_valid,
        "num_windowed": s.num_windowed,
    }

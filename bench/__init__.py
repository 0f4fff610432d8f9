"""Chip benchmark of the Skipper matcher.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the chip it is started on.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json`` — the deployment: its source, its sizes,
  the generator that makes its graph and the schedule parameters;
* ``bench/traffic/<mix>.json`` — data: the loop that drives the window
  and the parameters it reads;
* ``bench/loops/<loop>.py`` — a kind of traffic (``closed``: one caller,
  back to back), shared by every mix that names it;
* ``bench/metrics/<metric>.py`` — one reader per metric, end-to-end and
  per-layer alike: ``read(run) -> float | None``.

The rest is the yardstick that no program change may move: the seeded
generators (``bench/generators/``), the plain reference that decides
``correct`` (``bench/reference.py``), the reduction from the profiler's
trace to metrics (``bench/tracing.py``) and the table of chip peaks
(``bench/peaks.json``).
"""

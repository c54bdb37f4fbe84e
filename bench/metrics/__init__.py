"""One reader per metric, found by the metric's name in ``BENCHMARK.json``.

Each module defines ``read(run) -> float | None``.  ``run`` carries the
run's :class:`bench.window.Window` (``run.window``), its set-up seconds
(``run.setup_s``) and, in a ``--trace 1`` run, the reduced profiler trace
(``run.trace``, None elsewhere).  A reader that finds nothing to read
returns None, and the metric is left out of the result line.
"""

"""Per-layer metric readers: ``bench/metrics/<metric>.py`` defines
``read(rec)``, which returns the metric from the traced run's record, or
``None`` where the record holds nothing to read.  ``rec`` has ``trace``
(``bench/trace.py``'s reduction), ``window_ns`` (the part of the window
the trace holds), ``cut`` (whether that is less than the whole window:
the profiler's buffer filled), ``spans`` (the
benchmark's host spans, seconds), ``counters``, ``cfg``, ``traffic``,
``peaks`` and ``workload``."""

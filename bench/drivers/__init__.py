"""Traffic drivers: one module per kind of traffic, found by the
``driver`` name in ``bench/traffic/<mix>.json``.  Each has ``setup``,
``window``, ``release`` and ``check`` (see ``bench/run.py``)."""

"""Spans and counters of the program's own layers (DESIGN.md §15).

Off by default: :func:`span` then returns one shared no-op context and
:func:`count` returns at once, so an instrumented call costs a flag
check.  After :func:`enable`, each span

* enters ``jax.profiler.TraceAnnotation(name, **meta)``, so it lands on
  the profiler's host plane, on the clock the device planes share;
* keeps a record: name, parent (the enclosing span of the same thread),
  ``perf_counter_ns`` start, duration and meta;
* adds to per-name totals: count, seconds and self seconds (the
  duration less the part its child spans cover).

Spans sit at phase boundaries (a packing pass, an epoch call), never per
rating or per slot; names start with ``repro.``.  :func:`snapshot`
returns everything as plain JSON-safe dicts.  ``jax`` is imported only
once tracing is on, so pure-NumPy modules may import this one.
"""
from __future__ import annotations

import contextlib
import threading
import time

#: records kept in memory; later spans still add to the totals
MAX_RECORDS = 10_000

_OFF = contextlib.nullcontext()     # what a disabled span returns
_lock = threading.Lock()
_local = threading.local()
_on = False
_totals: dict = {}      # name -> [count, ns, self ns]
_counters: dict = {}
_records: list = []
_dropped = 0


class _Span:
    __slots__ = ("name", "meta", "parent", "ann", "t0", "child_ns")

    def __init__(self, name, meta):
        self.name, self.meta = name, meta

    def __enter__(self):
        import jax
        stack = _local.__dict__.setdefault("stack", [])
        self.parent = stack[-1] if stack else None
        self.child_ns = 0
        self.ann = jax.profiler.TraceAnnotation(self.name, **self.meta)
        self.ann.__enter__()
        stack.append(self)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        global _dropped
        dur = time.perf_counter_ns() - self.t0
        _local.stack.pop()
        self.ann.__exit__(*exc)
        if self.parent is not None:
            self.parent.child_ns += dur
        with _lock:
            t = _totals.setdefault(self.name, [0, 0, 0])
            t[0] += 1
            t[1] += dur
            t[2] += dur - self.child_ns
            if len(_records) < MAX_RECORDS:
                _records.append({
                    "name": self.name,
                    "parent": None if self.parent is None
                    else self.parent.name,
                    "start_ns": self.t0, "dur_ns": dur, "meta": self.meta})
            else:
                _dropped += 1
        return False


def span(name: str, **meta):
    """Context manager timing one phase; ``meta`` values are str, int
    or float."""
    if not _on:
        return _OFF
    return _Span(name, meta)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name``."""
    if not _on:
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    global _on
    _on = False


def enabled() -> bool:
    return _on


def reset() -> None:
    """Forget every total, counter and record (on or off stays)."""
    global _totals, _counters, _records, _dropped
    with _lock:
        _totals, _counters, _records, _dropped = {}, {}, [], 0


def snapshot() -> dict:
    """``{"spans": {name: {"count", "s", "self_s"}}, "counters": {...},
    "records": [...], "dropped": n}``: copies, JSON-safe.  ``dropped``
    counts the spans past :data:`MAX_RECORDS` that kept no record."""
    with _lock:
        return {
            "spans": {k: {"count": c, "s": ns / 1e9, "self_s": self_ns / 1e9}
                      for k, (c, ns, self_ns) in _totals.items()},
            "counters": dict(_counters),
            "records": [dict(r, meta=dict(r["meta"])) for r in _records],
            "dropped": _dropped}

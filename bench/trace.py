"""Reduce a JAX profiler trace to what the per-layer metrics read.

``reduce_xplane(path)`` reads one ``.xplane.pb`` with
``jax.profiler.ProfileData`` and returns a plain dict (JSON-safe):

* ``devices``: one entry per TPU device plane (``/device:TPU:<i>``), with
  ``modules`` -- the ``[name, start_ns, dur_ns]`` events of the plane's
  "XLA Modules" line (one per program execution) -- and ``ops`` -- for
  each op name of the "XLA Ops" line, ``[count, total_ns]``;
* ``host``: ``[name, start_ns, dur_ns]`` of the host events of the
  benchmark's own spans (names starting with ``bench.``) and of every
  other host event long enough to name an idle gap.

Host and device events of one trace share one clock.  The functions
below work on that dict, so tests can feed them a recorded reduction.
"""
from __future__ import annotations

import re

_TPU = re.compile(r"^/device:TPU:(\d+)$")
#: host events shorter than this do not name idle gaps (keeps the
#: reduction small)
HOST_MIN_NS = 50_000


def reduce_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        m = _TPU.match(plane.name)
        if m:
            mods, ops = [], {}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods.extend([ev.name, float(ev.start_ns),
                                 float(ev.duration_ns)]
                                for ev in line.events)
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        c = ops.setdefault(ev.name, [0, 0.0])
                        c[0] += 1
                        c[1] += float(ev.duration_ns)
            devices[m.group(1)] = {"modules": mods, "ops": ops}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    d = float(ev.duration_ns)
                    if ev.name.startswith("bench.") or d >= HOST_MIN_NS:
                        host.append([ev.name, float(ev.start_ns), d])
    return {"devices": devices, "host": host}


def window(red: dict, name: str = "bench.window"):
    """``(start_ns, end_ns)`` of the benchmark's window span."""
    spans = [h for h in red["host"] if h[0] == name]
    if not spans:
        raise ValueError(f"no {name!r} span in the trace")
    _, s, d = max(spans, key=lambda h: h[2])
    return s, s + d


def _union(intervals, lo, hi):
    """Merged ``[start, end]`` intervals clipped to ``[lo, hi]``."""
    out = []
    for s, e in sorted((max(s, lo), min(s + d, hi))
                       for s, d in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_intervals(dev: dict, lo: float, hi: float):
    """Union of the device's program executions inside the window."""
    return _union([(s, d) for _, s, d in dev["modules"]], lo, hi)


def busy_ns(dev: dict, lo: float, hi: float) -> float:
    return sum(e - s for s, e in busy_intervals(dev, lo, hi))


def module_ns(dev: dict, lo: float, hi: float, pattern: str) -> float:
    """Device time inside the window of the programs whose name matches
    ``pattern`` (a regular expression searched in the module name)."""
    rx = re.compile(pattern)
    return sum(e - s for s, e in _union(
        [(s, d) for n, s, d in dev["modules"] if rx.search(n)], lo, hi))


def module_count(dev: dict, lo: float, hi: float, pattern: str) -> int:
    rx = re.compile(pattern)
    return sum(1 for n, s, d in dev["modules"]
               if rx.search(n) and lo <= s + d / 2 <= hi)


#: ops that contain other ops (their time is their body's)
_CONTAINER = re.compile(r"\b(while|conditional|call)\(")
#: an op's name in a breakdown: its HLO text up to this many characters
NAME_CHARS = 160


def top_ops(red: dict, count: int = 10):
    """The device ops that took the most time, averaged over devices:
    ``[[name, seconds], ...]`` (loops and calls left out: their time is
    their body's).  Falls back to whole programs where the trace holds
    no op line."""
    tot = {}
    devs = list(red["devices"].values())
    for dev in devs:
        if dev["ops"]:
            for name, (_, ns) in dev["ops"].items():
                if _CONTAINER.search(name):
                    continue
                name = name[:NAME_CHARS]
                tot[name] = tot.get(name, 0.0) + ns
        else:
            for name, _, ns in dev["modules"]:
                tot[name] = tot.get(name, 0.0) + ns
    n = max(len(devs), 1)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:count]
    return [[name, ns / n / 1e9] for name, ns in top]


def idle_gaps(red: dict, lo: float, hi: float, count: int = 10):
    """The longest gaps inside the window in which the first device ran
    nothing, each named by the shortest host event that covers its
    middle: ``[[name, seconds], ...]``."""
    devs = sorted(red["devices"].items(), key=lambda kv: int(kv[0]))
    if not devs:
        return []
    busy = busy_intervals(devs[0][1], lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:count]:
        mid = (s + e) / 2
        cover = [h for h in red["host"]
                 if h[1] <= mid <= h[1] + h[2] and h[0] != "bench.window"]
        name = min(cover, key=lambda h: h[2])[0] if cover else "no host span"
        out.append([name, (e - s) / 1e9])
    return out

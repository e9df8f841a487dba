"""Share of the window in which the device ran no program (1 - busy /
window, from the profiler trace, averaged over the devices), in the
serving cells."""
from bench import trace as tr


def read(rec):
    lo, hi = rec["window_ns"]
    devs = list(rec["trace"]["devices"].values())
    if not devs or hi <= lo:
        return None
    busy = sum(tr.busy_ns(d, lo, hi) for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / (hi - lo))

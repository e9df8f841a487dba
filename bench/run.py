#!/usr/bin/env python3
"""Chip benchmark of the NOMAD trainer and the top-k server.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the TPU chips
the cell asks for.  Everything is found by name from ``BENCHMARK.json``:
the cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``), the traffic names its
driver (``bench/drivers/<driver>.py``) and each per-layer metric has a
reader ``bench/metrics/<metric>.py``.

A run checks the device first (a TPU, as many chips as the cell asks
for, a ``device_kind`` listed in ``bench/peaks.json``) and exits
non-zero with no result otherwise.  Then the driver sets up (counted in
``setup_s``), the window runs for ``--seconds`` (under the profiler with
``--trace 1``), the peak device memory is read, the program's state is
freed and the plain reference judges what the window's path produced.
The last lines of standard error give each compared number beside its
limit; the last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``breakdown`` with ``--trace 1``) and, last, ``checks``.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    if not path.is_file():
        raise SystemExit(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """Everything a driver gets: the cell's entries and files, the run's
    arguments, the devices, and where to leave spans and counters."""
    name: str
    cfg: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    peaks: dict
    spans: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)

    def span(self, name: str):
        """A host span of the benchmark's own: its seconds go to
        ``spans[name]`` and, under the profiler, into the trace."""
        return _Span(self, name)


class _Span:
    def __init__(self, cell, name):
        self.cell, self.name = cell, name

    def __enter__(self):
        import jax
        self._ann = jax.profiler.TraceAnnotation(f"bench.{self.name}")
        self._ann.__enter__()
        self._t = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t
        self._ann.__exit__(*exc)
        self.cell.spans[self.name] = self.cell.spans.get(self.name, 0) + dt


def cell_spec(bench: dict, workload: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; cells: "
                         f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    return w, configs[w["config"]]


def device_check(chips: int, peaks: dict):
    """The cell's chips, or exit: no TPU, too few chips, or a device kind
    with no peaks listed."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's devices are {d.platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} TPU chips, found "
                         f"{len(devs)}")
    if d.device_kind not in peaks:
        raise SystemExit(f"device kind {d.device_kind!r} is not in "
                         f"bench/peaks.json")
    return devs[:chips], peaks[d.device_kind]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (``JAX_COMPILATION_CACHE_DIR`` where it is set), every
    program kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def selected(entries: list, workload: str, reported=None) -> list:
    """Metric entries this cell reports: those that list it, or, with no
    list, every cell (per-layer metrics: every cell that reports the
    end-to-end metric they move)."""
    out = []
    for e in entries:
        if "workloads" in e:
            if workload in e["workloads"]:
                out.append(e)
        elif reported is None or e.get("moves") in reported:
            out.append(e)
    return out


def run_window(cell: Cell, driver, state) -> dict:
    """The driver's window, under the profiler with ``--trace 1``;
    counts the compilations inside it.  Returns the driver's result,
    with the trace's reduction under ``"reduced"`` when traced."""
    import jax
    compiles = []

    def on_event(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    trace_dir = ROOT / "bench_out" / f"trace-{os.getpid()}"
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        if cell.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        try:
            with cell.span("window"):
                res = driver.window(cell, state)
        finally:
            if cell.trace:
                t = time.perf_counter()
                jax.profiler.stop_trace()
                log(f"trace: written in {time.perf_counter() - t:.3f} s")
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    log(f"window: {res['window_s']:.3f} s, {res['attempted']} attempted, "
        f"{res['failed']} failed, {len(compiles)} compiles inside it")
    if cell.trace:
        from bench import trace as tr
        t = time.perf_counter()
        files = sorted(trace_dir.rglob("*.xplane.pb"))
        if not files:
            raise SystemExit("the profiler wrote no trace")
        res["reduced"] = tr.reduce_xplane(str(files[-1]))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: reduced in {time.perf_counter() - t:.3f} s")
        log_programs(res["reduced"])
    return res


def log_programs(reduced: dict) -> None:
    """One line per device: its programs by device time."""
    for dev_id, dev in sorted(reduced["devices"].items()):
        names = {}
        for name, _, ns in dev["modules"]:
            c = names.setdefault(name, [0, 0.0])
            c[0] += 1
            c[1] += ns / 1e9
        top = sorted(names.items(), key=lambda kv: -kv[1][1])[:8]
        log(f"trace: device {dev_id}: {len(dev['modules'])} program runs, "
            f"{len(dev['ops'])} op names; programs by time "
            + "; ".join(f"{n} x{c} {s:.4f} s" for n, (c, s) in top))


#: how long before the window's end the last program may end in a
#: whole trace: the host's sync and the driver's tally, with room
TAIL_NS = 1e9


def held_end(reduced: dict, lo: float, hi: float, programs: dict) -> float:
    """Where the trace stops holding the window: ``hi`` for a whole
    trace, else the earliest end of a cut device's programs.

    The profiler drops every event once its buffer is full.  A whole
    trace holds, on every device, as many runs of each program as the
    driver counted (``programs``: a regular expression of the module
    name -> runs), and its last program ends within ``TAIL_NS`` of the
    window's end, since each window ends once the host holds the results
    of its last program.  A trace with fewer runs, or whose programs stop
    early, was cut: it holds the window up to its last program's end and
    nothing after.  More runs than the driver counted is an error."""
    from bench import trace as tr
    end = hi
    for dev_id, dev in sorted(reduced["devices"].items()):
        last = max((s + d for _, s, d in dev["modules"] if s < hi),
                   default=lo)
        cut = last < hi - TAIL_NS
        for pattern, want in programs.items():
            got = tr.module_count(dev, lo, hi, pattern)
            if got > want:
                raise SystemExit(
                    f"device {dev_id} holds {got} runs of {pattern!r} in "
                    f"the window, the driver ran {want}")
            cut = cut or got < want
        if cut:
            end = min(end, last)
    if end <= lo:
        raise SystemExit("the trace holds no program run in the window")
    return end


def traced_metrics(cell: Cell, bench: dict, reported: set, reduced: dict,
                   programs: dict, device: dict):
    """Per-layer metrics, ``busy_s``/``window_s`` (into ``device``) and
    the breakdown, from the part of the window the trace holds."""
    from bench import trace as tr
    lo, hi = tr.window(reduced)
    devs = list(reduced["devices"].values())
    if not devs:
        raise SystemExit("the trace holds no TPU device plane")
    end = held_end(reduced, lo, hi, programs)
    if end < hi:
        log(f"trace: cut, it holds {(end - lo) / 1e9:.3f} s of the "
            f"{(hi - lo) / 1e9:.3f} s window; metrics read that part")
    device["busy_s"] = sum(tr.busy_ns(d, lo, end) for d in devs) / len(
        devs) / 1e9
    device["window_s"] = (end - lo) / 1e9
    rec = {"trace": reduced, "window_ns": (lo, end), "cut": end < hi,
           "spans": cell.spans, "counters": cell.counters, "cfg": cell.cfg,
           "traffic": cell.traffic, "peaks": cell.peaks,
           "workload": cell.name}
    metrics = {}
    for e in selected(bench["per_layer"], cell.name, reported):
        mod = load_module(BENCH / "metrics" / f"{e['name']}.py",
                          f"bench_metric_{e['name']}")
        v = mod.read(rec)
        if v is not None:
            metrics[e["name"]] = {"value": float(v), "unit": e["unit"]}
    breakdown = {"device_ops": tr.top_ops(reduced),
                 "idle_gaps": tr.idle_gaps(reduced, lo, end)}
    return metrics, breakdown


def run_cell(cell: Cell, driver, bench: dict) -> dict:
    """Set up, run the window, read the device, judge.  Returns the
    result object (``device`` without ``platform`` and ``kind``)."""
    state = driver.setup(cell)
    # what set-up left on the heap (JAX, the program, the data) moves to
    # the permanent generation: the window's collections then traverse
    # only what the window allocates, instead of pausing the load
    # generator for tens of milliseconds
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T0
    log(f"setup: {setup_s:.3f} s; spans "
        + ", ".join(f"{k} {v:.3f} s" for k, v in cell.spans.items()))
    res = run_window(cell, driver, state)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in cell.devices)
    driver.release(cell, state)
    checks = driver.check(cell, state)
    correct = (bool(checks) and all(v <= lim for _, v, lim in checks)
               and res["failed"] == 0)

    device = {"count": len(cell.devices), "memory_peak_bytes": peak}
    e2e = selected(bench["end_to_end"], cell.name)
    if cell.trace:
        metrics, breakdown = traced_metrics(
            cell, bench, {e["name"] for e in e2e}, res["reduced"],
            res["programs"], device)
    else:
        values = dict(res["metrics"], setup_s=setup_s)
        metrics = {e["name"]: {"value": float(values[e["name"]]),
                               "unit": e["unit"]} for e in e2e}
        breakdown = None
    out = {"correct": correct, "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {name: {"value": float(v), "limit": float(lim)}
                     for name, v, lim in checks}
    for name, v, lim in checks:
        log(f"check {name}: {v!r} (limit {lim!r}) "
            f"{'ok' if v <= lim else 'FAIL'}")
    return out


def prepare(workload: str):
    """``(bench, cell entry, config entry, config, traffic, driver)`` read
    from the checkout."""
    bench = load_json(ROOT / "BENCHMARK.json")
    w, c = cell_spec(bench, workload)
    cfg = load_json(ROOT / c["file"])
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py",
                         f"bench_driver_{traffic['driver']}")
    return bench, w, c, cfg, traffic, driver


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, w, _, cfg, traffic, driver = prepare(args.workload)
    devices, peaks = device_check(int(w["chips"]),
                                  load_json(BENCH / "peaks.json"))
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    log(f"compile cache: {enable_compile_cache()}")
    cell = Cell(name=args.workload, cfg=cfg, traffic=traffic,
                seed=args.seed, seconds=args.seconds,
                trace=bool(args.trace), devices=devices, peaks=peaks)
    out = run_cell(cell, driver, bench)
    d = devices[0]
    # same key, same place: "checks" stays last
    out["device"] = {"platform": d.platform, "kind": d.device_kind,
                     **out["device"]}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

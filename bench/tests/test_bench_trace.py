"""Trace reduction: on a small trace recorded on one TPU v5e
(``data/small.xplane.pb``: three ``bench.epoch`` spans inside a
``bench.window`` span, each running a 2,000-step loop program and a
256x256 matmul, 10 ms apart) and on hand-made reductions."""
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return tr.reduce_xplane(str(DATA))


def test_recorded_trace_has_the_device_and_the_spans(red):
    assert list(red["devices"]) == ["0"]
    dev = red["devices"]["0"]
    names = [n for n, _, _ in dev["modules"]]
    assert sum("loop" in n for n in names) == 3
    assert sum(n.startswith("jit__lambda") for n in names) == 3
    assert sum(h[0] == "bench.epoch" for h in red["host"]) == 3


def test_recorded_busy_and_idle(red):
    lo, hi = tr.window(red)
    dev = red["devices"]["0"]
    busy = tr.busy_ns(dev, lo, hi)
    assert 0 < busy < hi - lo
    # the three 10 ms sleeps are idle time
    assert hi - lo - busy > 25e6
    gaps = tr.idle_gaps(red, lo, hi)
    assert gaps[0][1] >= gaps[-1][1] and gaps[0][1] > 0.009
    assert tr.module_count(dev, lo, hi, "loop") == 3
    ops = tr.top_ops(red)
    assert ops and all(s > 0 for _, s in ops)


def test_union_and_clipping():
    dev = {"modules": [["a", 0, 10], ["b", 5, 10], ["a", 30, 10],
                       ["c", 95, 20]], "ops": {}}
    assert tr.busy_intervals(dev, 0, 100) == [[0, 15], [30, 40], [95, 100]]
    assert tr.busy_ns(dev, 0, 100) == 30
    assert tr.module_ns(dev, 0, 100, "^a$") == 20
    assert tr.module_count(dev, 0, 100, "a|b") == 3


def test_idle_gaps_named_by_host_span():
    red = {"devices": {"0": {"modules": [["p", 10, 10], ["p", 50, 10]],
                             "ops": {}}},
           "host": [["bench.window", 0, 100], ["bench.epoch", 0, 40],
                    ["host work", 22, 25]]}
    gaps = tr.idle_gaps(red, *tr.window(red))
    assert gaps[0] == ["no host span", 40e-9]
    assert gaps[1] == ["host work", 30e-9]
    assert gaps[2] == ["bench.epoch", 10e-9]


@pytest.mark.parametrize("runs", [3, 4, 2])
def test_cut_trace_is_read_up_to_its_cut(red, runs):
    """A trace that holds every run the driver counted is whole; one that
    holds fewer was cut; one that holds more is an error."""
    from bench import run
    lo, hi = tr.window(red)
    last = max(s + d for _, s, d in red["devices"]["0"]["modules"])
    if runs == 3:
        assert run.held_end(red, lo, hi, {"loop": runs}) == hi
    elif runs == 4:
        assert run.held_end(red, lo, hi, {"loop": runs}) == last
    else:
        with pytest.raises(SystemExit, match="the driver ran 2"):
            run.held_end(red, lo, hi, {"loop": runs})


def test_trace_that_stops_early_is_cut():
    """The first traced epoch on the chip: one epoch program whose events
    stop 5.58 s into a 9.55 s window, when the profiler's buffer filled.
    The metrics read the 5.58 s the trace holds."""
    from bench import run
    red = {"devices": {"0": {"modules": [["jit_train(1)", 0, 5.58e9]],
                             "ops": {}}},
           "host": [["bench.window", 0, 9.55e9]]}
    assert run.held_end(red, *tr.window(red), {r"jit_train\b": 1}) == 5.58e9

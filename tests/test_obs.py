"""repro.obs (DESIGN.md §15): spans and counters are free when off, keep
parent and self time when on, land in a jax.profiler trace by name, and
instrument the trainer from packing down to the slot body, whose named
scopes change only the compiled program's metadata."""
import contextlib
import json
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import strategies
from repro import api, obs
from repro.core import nomad
from repro.core import partition as part
from repro.core.stepsize import PowerSchedule


@pytest.fixture(autouse=True)
def _clean():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def test_disabled_span_is_the_shared_noop_and_records_nothing():
    a, b = obs.span("repro.x"), obs.span("repro.y", k=1)
    assert a is b
    with a:
        obs.count("n", 3)
    snap = obs.snapshot()
    assert snap == {"spans": {}, "counters": {}, "records": [], "dropped": 0}


def test_nesting_gives_parent_and_self_time():
    obs.enable()
    with obs.span("repro.outer", epochs=2):
        with obs.span("repro.inner"):
            pass
        with obs.span("repro.inner"):
            pass
    snap = obs.snapshot()
    outer, inner = snap["spans"]["repro.outer"], snap["spans"]["repro.inner"]
    assert outer["count"] == 1 and inner["count"] == 2
    assert inner["self_s"] == inner["s"]
    assert outer["self_s"] == pytest.approx(outer["s"] - inner["s"],
                                            abs=1e-9)
    assert 0 <= outer["self_s"] <= outer["s"]
    recs = {(r["name"], r["parent"]) for r in snap["records"]}
    assert recs == {("repro.outer", None), ("repro.inner", "repro.outer")}
    (o,) = [r for r in snap["records"] if r["name"] == "repro.outer"]
    assert o["meta"] == {"epochs": 2}
    for r in snap["records"]:
        if r["parent"]:
            assert o["start_ns"] <= r["start_ns"]
            assert r["start_ns"] + r["dur_ns"] <= o["start_ns"] + o["dur_ns"]


def test_counters_add():
    obs.enable()
    obs.count("train.calls")
    obs.count("train.calls")
    obs.count("stream.slots", 7)
    assert obs.snapshot()["counters"] == {"train.calls": 2,
                                          "stream.slots": 7}


def test_snapshot_is_a_json_safe_copy_and_reset_clears():
    obs.enable()
    with obs.span("repro.a", p=4):
        obs.count("c", 2)
    snap = obs.snapshot()
    assert json.loads(json.dumps(snap)) == snap
    snap["records"][0]["meta"]["p"] = 5
    snap["counters"]["c"] = 9
    assert obs.snapshot()["records"][0]["meta"] == {"p": 4}
    assert obs.snapshot()["counters"] == {"c": 2}
    obs.reset()
    assert obs.enabled()
    assert obs.snapshot() == {"spans": {}, "counters": {}, "records": [],
                              "dropped": 0}


def test_records_are_bounded_totals_are_not(monkeypatch):
    monkeypatch.setattr(obs, "MAX_RECORDS", 2)
    obs.enable()
    for _ in range(5):
        with obs.span("repro.s"):
            pass
    snap = obs.snapshot()
    assert len(snap["records"]) == 2 and snap["dropped"] == 3
    assert snap["spans"]["repro.s"]["count"] == 5


def test_enabled_span_lands_in_a_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    obs.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("repro.probe", epochs=1):
            jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = {ev.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert "repro.probe" in names


# --------------------------------------------------------------------- #
# the instrumented trainer                                               #
# --------------------------------------------------------------------- #

def _problem(seed=0, m=40, n=24, nnz=300, n_test=40):
    rows, cols, vals = strategies.coo_problem(seed, m, n, nnz)
    rng = np.random.default_rng((seed, 0x0B5))
    test = (rng.integers(0, m, n_test), rng.integers(0, n, n_test),
            rng.normal(size=n_test))
    return api.MCProblem(rows=rows, cols=cols, vals=vals, m=m, n=n,
                         test=test)


def _cfg(**kw):
    base = dict(k=4, lam=0.01, epochs=1, p=4, seed=0,
                stepsize=PowerSchedule(alpha=0.05, beta=0.02))
    base.update(kw)
    return api.NomadConfig(**base)


def test_pack_records_its_phases():
    problem = _problem()
    obs.enable()
    problem.packed(4, waves=True)
    snap = obs.snapshot()
    phases = ("repro.pack.assign", "repro.pack.sort", "repro.pack.order",
              "repro.pack.fill")
    assert set(snap["spans"]) == {"repro.pack", *phases}
    parents = {r["name"]: r["parent"] for r in snap["records"]}
    assert all(parents[ph] == "repro.pack" for ph in phases)
    pack = snap["spans"]["repro.pack"]
    covered = sum(snap["spans"][ph]["s"] for ph in phases)
    assert pack["self_s"] == pytest.approx(pack["s"] - covered, abs=1e-9)
    assert snap["counters"] == {"pack.ratings": problem.nnz}


def test_traced_fused_train_records_spans_and_counts():
    problem = _problem()
    eng, _ = api._nomad_cold_start(problem, _cfg(), None, None)
    assert eng.stream_counts is None
    obs.enable()
    eng.train(1, problem.test, dispatch="fused")
    snap = obs.snapshot()
    parents = {r["name"]: r["parent"] for r in snap["records"]}
    for name in ("repro.train.stage", "repro.stream.build",
                 "repro.stream.upload", "repro.train.dispatch",
                 "repro.train.sync"):
        assert parents[name] == "repro.train", name
    assert parents["repro.train"] is None
    slots, updates = eng.stream_counts
    assert updates == problem.nnz
    assert slots == len(part.epoch_stream(eng.br)[0])
    assert snap["counters"] == {"train.calls": 1, "train.epochs": 1,
                                "stream.slots": slots,
                                "stream.updates": updates}
    (tr,) = [r for r in snap["records"] if r["name"] == "repro.train"]
    assert tr["meta"] == {"epochs": 1}

    # the stream is built once: a second call stages, dispatches, syncs
    obs.reset()
    eng.train(2, problem.test, dispatch="fused", fuse_epochs=1)
    spans = obs.snapshot()["spans"]
    assert "repro.stream.build" not in spans
    assert spans["repro.train.dispatch"]["count"] == 2
    assert spans["repro.train.sync"]["count"] == 2
    assert obs.snapshot()["counters"] == {"train.calls": 1,
                                          "train.epochs": 2}


def test_traced_loop_train_records_dispatch_and_sync_per_epoch():
    problem = _problem()
    eng, _ = api._nomad_cold_start(problem, _cfg(), None, None)
    obs.enable()
    eng.train(3, problem.test, dispatch="loop")
    spans = obs.snapshot()["spans"]
    assert spans["repro.train"]["count"] == 1
    assert spans["repro.train.dispatch"]["count"] == 3
    # one RMSE sync per epoch, and the end-of-call divergence check
    assert spans["repro.train.sync"]["count"] == 4


@pytest.mark.parametrize("dispatch", ["fused", "loop"])
def test_tracing_changes_no_result(dispatch):
    problem = _problem(seed=3)
    cfg = _cfg(epochs=2, dispatch=dispatch)
    off = api.solve(problem, cfg)
    obs.enable()
    on = api.solve(problem, cfg)
    assert np.array_equal(off.W, on.W) and np.array_equal(off.H, on.H)
    assert off.trace == on.trace
    assert obs.snapshot()["spans"]["repro.cold_start"]["count"] == 1


# --------------------------------------------------------------------- #
# named scopes on the slot body                                          #
# --------------------------------------------------------------------- #

_TABLES = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)$"
                     r"|^\d+ ")
_METADATA = re.compile(r",? metadata=\{[^}]*\}")
_SLOT = re.compile(r'op_name="[^"]*/(slot\.[a-z_]+)/')


def _stream_epoch_text(kernel):
    """The fused stream driver compiled at a tiny shape (a fresh jit, so
    nothing is read from an earlier trace)."""
    eng, _ = api._nomad_cold_start(_problem(), _cfg(kernel=kernel), None,
                                   None)
    stream = tuple(jnp.asarray(a.reshape(-1))
                   for a in part.epoch_stream(eng.br))
    z = jnp.zeros(0, jnp.int32)
    train = nomad._fused_driver(nomad._stream_epoch_body)
    return train.lower(
        eng.Ws, eng.Hs, stream, jnp.zeros(1, jnp.float32),
        jnp.full(1, -1, jnp.int32), eng.lam, z, z,
        jnp.zeros(0, jnp.float32), policy=eng.policy, entry=None,
        n_rec=0).compile().as_text()


def _strip(text):
    return "\n".join(_METADATA.sub("", line) for line in text.splitlines()
                     if not _TABLES.match(line))


@pytest.mark.parametrize("kernel", ["xla", "wave"])
def test_slot_scopes_tag_gather_update_and_scatter(kernel):
    text = _stream_epoch_text(kernel)
    scopes = {}
    for line in text.splitlines():
        m = _SLOT.search(line)
        if m:
            op = line.split("=", 1)[1].split("(", 1)[0].split()[-1]
            scopes.setdefault(m.group(1), set()).add(op)
    assert set(scopes) == {"slot.index", "slot.gather", "slot.sgd",
                           "slot.scatter"}
    assert "dynamic-slice" in scopes["slot.index"]
    assert "gather" in scopes["slot.gather"]
    assert {"multiply", "subtract"} <= scopes["slot.sgd"]
    assert "scatter" in scopes["slot.scatter"] or "fusion" in scopes[
        "slot.scatter"]
    # a fusion takes its root instruction's op_name: the slot's one
    # scatter into the factor table is the one fusion rooted in a
    # scatter under its scope (the index select and the row concat
    # beside it are fusions of their own)
    tagged = [line for line in text.splitlines()
              if " fusion(" in line and '/slot.scatter/scatter"' in line]
    assert len(tagged) == 1


@pytest.mark.parametrize("kernel", ["xla", "wave"])
def test_slot_scopes_change_only_metadata(kernel, monkeypatch):
    scoped = _stream_epoch_text(kernel)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _stream_epoch_text(kernel)
    assert "/slot." in scoped and "/slot." not in plain
    assert _strip(scoped) == _strip(plain)

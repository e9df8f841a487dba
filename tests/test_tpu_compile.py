"""Compile rehearsal for one TPU v5e, without the chip.

The TPU compiler is installed even where no chip is attached: it
compiles for a *described* ``v5e:2x2`` topology and refuses what the
chip's compiler would refuse — a Mosaic kernel it cannot lower, blocks
beyond VMEM, a program beyond HBM.  These tests compile the main path's
programs at Netflix widths (k=100, the 17,770-item catalog) and the
Pallas SGD kernels at the cell shapes they can hold, and read the
stream epoch's compiled slot loop.  Nothing runs, so they say nothing
about results or times.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and the test
workers all import this file.  The persistent compilation cache is off
around the compiles (a compile for a described chip cannot be read back
without one).
"""
import os
import re

import pytest

V5E_HBM = 16 << 30                  # bytes of HBM on one v5e chip
NETFLIX_M, NETFLIX_N, K, P = 2_649_429, 17_770, 100, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_fits(lowered, *, kernel: bool):
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert total <= V5E_HBM, f"program needs {total / 2**30:.2f} GiB"
    assert ("tpu_custom_call" in compiled.as_text()) == kernel
    return compiled


def _lower_stream_driver(sharding, impl):
    import jax.numpy as jnp

    from repro.core import nomad
    from repro.kernels.policy import KernelPolicy
    s = lambda shape, dt: _spec(sharding, shape, dt)    # noqa: E731
    m_local, n_local = -(-NETFLIX_M // P), -(-NETFLIX_N // P)
    slots, n_test = 1 << 16, 4096
    data = (s((slots * P,), jnp.int32), s((slots * P,), jnp.int32),
            s((slots * P,), jnp.float32), s((slots * P,), jnp.bool_))
    return nomad._local_train_stream.lower(
        s((P, m_local, K), jnp.float32), s((P, n_local, K), jnp.float32),
        data, s((2,), jnp.float32), s((2,), jnp.int32), 0.05,
        s((n_test,), jnp.int32), s((n_test,), jnp.int32),
        s((n_test,), jnp.float32),
        policy=KernelPolicy(impl=impl), entry=None, n_rec=2)


@pytest.fixture(scope="module")
def stream_driver_text(one_chip):
    """The fused stream driver's compiled text at Netflix widths, one
    compile per impl for the whole module."""
    texts = {}

    def get(impl):
        if impl not in texts:
            compiled = _compile_fits(_lower_stream_driver(one_chip, impl),
                                     kernel=False)
            texts[impl] = compiled.as_text()
        return texts[impl]
    return get


def test_stream_driver_compiles_at_netflix_widths(stream_driver_text):
    """The fused stream driver (``api.solve``'s default path) at p=8,
    k=100 over one worker's Netflix row shard, with a short stream."""
    assert stream_driver_text("xla")


_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[\w.\-]+) .*\{$")
_WHILE_BODY = re.compile(r" while\(.*, body=(%[\w.\-]+)")
_CALLS = re.compile(r"calls=(%[\w.\-]+)")
_SCOPED = re.compile(r'"used_scoped_memory_configs":\[([^\]]*)\]')


def _computations(text):
    """Optimized HLO text -> {computation name: its instruction lines}."""
    out, name = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            name = m.group(1)
            out[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            out[name].append(line)
    return out


def _scatter_fusions(comps, body):
    """The fusions of ``body`` that hold a scatter, at any depth of
    nested fusion."""
    def holds_scatter(line):
        m = _CALLS.search(line)
        return m is not None and any(
            " scatter(" in x or (" fusion(" in x and holds_scatter(x))
            for x in comps.get(m.group(1), []))
    return [x for x in comps[body]
            if " fusion(" in x and holds_scatter(x)]


@pytest.mark.parametrize("impl", ["xla", "wave"])
def test_stream_slot_body_scatters_rows_in_place(stream_driver_text, impl):
    """The slot loop writes its rows back with one scatter into the
    factor table, in HBM and in place.  Kept apart, the 7.1 MB H of the
    Netflix catalog lives in VMEM and its scatter staged a copy of all
    of H in scoped memory on every slot (10 MB at the benchmark's
    shapes), the costliest op of the epoch."""
    text = stream_driver_text(impl)
    comps = _computations(text)
    bodies = {m.group(1) for line in text.splitlines()
              for m in [_WHILE_BODY.search(line)] if m}
    slot_bodies = [b for b in bodies if _scatter_fusions(comps, b)]
    assert len(slot_bodies) == 1
    (body,) = slot_bodies
    assert len(_scatter_fusions(comps, body)) == 1
    for line in comps[body]:
        if " fusion(" not in line:
            continue
        m = _SCOPED.search(line)
        sizes = re.findall(r'"size":"(\d+)"', m.group(1)) if m else []
        assert max(map(int, sizes), default=0) < 1 << 20, line[:120]


@pytest.mark.parametrize("users", [1, 64])
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_topk_scorer_compiles_over_netflix_catalog(one_chip, impl, users):
    import jax.numpy as jnp

    from repro.serve import topk
    W_u = _spec(one_chip, (users, K), jnp.float32)
    H = _spec(one_chip, (NETFLIX_N, K), jnp.float32)
    if impl == "pallas":
        lowered = topk._topk_pallas.lower(W_u, H, None, k_top=10,
                                          item_tile=4096, interpret=False)
    else:
        lowered = topk._topk_xla.lower(W_u, H, None, k_top=10,
                                       item_tile=4096)
    _compile_fits(lowered, kernel=impl == "pallas")


@pytest.mark.parametrize("kernel", ["block", "waves_block", "waves_grid"])
def test_pallas_sgd_kernel_compiles_at_supported_cell(one_chip, kernel):
    """Each Pallas SGD kernel at a cell its resident tiles can hold
    (``nomad_sgd.fits_vmem``); larger cells are refused before lowering
    (tests/test_kernels.py)."""
    import jax.numpy as jnp

    from repro.kernels import nomad_sgd
    s = lambda shape, dt: _spec(one_chip, shape, dt)    # noqa: E731
    m_t, n_t, p = 8192, 2048, 4
    assert nomad_sgd.fits_vmem(m_t, n_t, K, grid=kernel == "waves_grid")
    if kernel == "block":
        fn, lead, ratings = nomad_sgd.nomad_sgd_block, (), (8192,)
    elif kernel == "waves_block":
        fn, lead, ratings = nomad_sgd.nomad_sgd_waves_block, (), (256, 8)
    else:
        fn, lead, ratings = nomad_sgd.nomad_sgd_waves_grid, (p,), (256, 8)
    lowered = fn.lower(
        s(lead + (m_t, K), jnp.float32), s(lead + (n_t, K), jnp.float32),
        s(lead + ratings, jnp.int32), s(lead + ratings, jnp.int32),
        s(lead + ratings, jnp.float32), s(lead + ratings, jnp.bool_),
        0.01, 0.05, interpret=False)
    _compile_fits(lowered, kernel=True)

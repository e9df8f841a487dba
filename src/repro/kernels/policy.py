"""Kernel execution policy for the NOMAD block-SGD update.

``KernelPolicy`` is the single, validated description of *how* a block of
ratings is executed: which kernel implementation, its tiling knobs, the
sub-block pipelining factor, and the factor precision policy.  It
replaces the string-``impl`` branching that used to be re-validated ad
hoc in ``kernels.ops``, ``NomadRingEngine.__post_init__`` and every
launcher: invalid combinations now fail (or downgrade, with a warning)
at *construction* time, once, with one message.

The object is a frozen (hashable) dataclass, so it can be passed through
``jax.jit`` as a static argument and used as a memoization key for packed
layouts (``MCProblem.packed``).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Tuple, Union

IMPLS: Tuple[str, ...] = ("auto", "xla", "pallas", "wave", "wave_pallas")

#: impls that consume the conflict-free ``(n_waves, wave_width)`` layout
WAVE_IMPLS: Tuple[str, ...] = ("wave", "wave_pallas")

#: factor storage precisions (DESIGN.md §13).  Anything below fp32
#: stores W/H low-precision and accumulates the SGD update in fp32.
DTYPE_POLICIES: Tuple[str, ...] = ("fp32", "bf16", "fp16")

#: the sequential fallback each wave impl downgrades to when the
#: pipelined sub-block layout is requested (the wave layout is colored
#: over whole cells; slicing an H block into sub-blocks would split
#: waves across permute steps and break the serializability proof)
_WAVE_DOWNGRADE = {"wave": "xla", "wave_pallas": "pallas"}


@dataclasses.dataclass(frozen=True)
class KernelPolicy:
    """How one block-SGD update executes.

    impl         -- 'auto' | 'xla' | 'pallas' | 'wave' | 'wave_pallas'
                    (sequential rating list vs. conflict-free wave layout,
                    XLA vs. Pallas lowering; see DESIGN.md §3)
    chunk        -- rating chunk for the sequential Pallas kernel
    wave_chunk   -- wave chunk for the wave Pallas kernel (also the
                    inner grid extent of the occupancy grid kernel)
    sub_blocks   -- item sub-blocks per H block for the pipelined SPMD
                    permute overlap (DESIGN.md §2); 1 = whole-block
    dtype_policy -- 'fp32' | 'bf16' | 'fp16': factor *storage* precision.
                    Below fp32 the SGD update gathers rows, upcasts,
                    accumulates in fp32 and downcasts on scatter
                    (DESIGN.md §13); fp32 keeps every path bitwise equal
                    to the historical kernels.
    block_rows   -- occupancy-grid selector for the wave Pallas kernel:
                    0 = auto (grid over (cell, wave-chunk) on TPU when
                    the cell's factor tiles fit VMEM, single-program
                    scan otherwise), -1 = never use the grid kernel,
                    > 0 = use the grid kernel whenever
                    max(m_local, n_local) <= block_rows.
    """
    impl: str = "auto"
    chunk: int = 1024
    wave_chunk: int = 8
    sub_blocks: int = 1
    dtype_policy: str = "fp32"
    block_rows: int = 0

    def __post_init__(self):
        if self.impl not in IMPLS:
            raise ValueError(
                f"impl={self.impl!r} not in {IMPLS}")
        if self.chunk < 1 or self.wave_chunk < 1:
            raise ValueError("chunk and wave_chunk must be >= 1")
        if self.sub_blocks < 1:
            raise ValueError(f"sub_blocks must be >= 1, got {self.sub_blocks}")
        if self.dtype_policy not in DTYPE_POLICIES:
            raise ValueError(
                f"dtype_policy={self.dtype_policy!r} not in {DTYPE_POLICIES}")
        if self.block_rows < -1:
            raise ValueError(
                f"block_rows must be -1 (never), 0 (auto) or a positive "
                f"row bound, got {self.block_rows}")
        if self.wave and self.sub_blocks > 1:
            # The wave coloring spans whole cells; the pipelined layout
            # slices each H block into sub_blocks permute stages, which
            # would split waves across stages and void the conflict-free
            # guarantee.  Downgrade to the sequential lowering of the
            # same family instead of hard-failing (the historical
            # ValueError made a *valid* user config unconstructible).
            repl = _WAVE_DOWNGRADE[self.impl]
            warnings.warn(
                f"impl={self.impl!r} does not support sub_blocks > 1 "
                f"(the wave layout is colored over whole cells); "
                f"downgrading to impl={repl!r} for the pipelined SPMD "
                "path", UserWarning, stacklevel=2)
            object.__setattr__(self, "impl", repl)

    # ------------------------------------------------------------------ #
    @property
    def wave(self) -> bool:
        """True if this policy consumes the wave layout."""
        return self.impl in WAVE_IMPLS

    @property
    def mixed(self) -> bool:
        """True if factors are stored below fp32 (bounded-error tier)."""
        return self.dtype_policy != "fp32"

    @property
    def storage_dtype(self):
        """jnp dtype the factor shards are stored in."""
        import jax.numpy as jnp
        return {"fp32": jnp.float32, "bf16": jnp.bfloat16,
                "fp16": jnp.float16}[self.dtype_policy]

    @property
    def compute_dtype(self):
        """Accumulation dtype for the SGD update, or ``None`` when
        storage is already fp32 (the literal, bitwise-historical path —
        no cast is ever inserted)."""
        if not self.mixed:
            return None
        import jax.numpy as jnp
        return jnp.float32

    def wants_grid(self, m_local: int, n_local: int, k: int) -> bool:
        """Whether the wave Pallas dispatch should use the occupancy
        grid kernel for cells of this shape (``block_rows`` semantics
        above).  Only meaningful for ``impl='wave_pallas'``."""
        if self.block_rows == -1:
            return False
        if self.block_rows > 0:
            return max(m_local, n_local) <= self.block_rows
        from .nomad_sgd import fits_vmem
        from .ops import on_tpu
        return on_tpu() and fits_vmem(m_local, n_local, k, grid=True)

    @property
    def serve_impl(self) -> str:
        """Which serving top-k scorer this policy selects
        (``repro.serve.topk``): the Pallas tile kernel for the Pallas
        train impls, the XLA scan otherwise; ``'auto'`` is the Pallas
        scorer on TPU (where it compiles at catalog widths) and the XLA
        scan elsewhere.  The wave/sequential split is a training concern
        — for serving only the lowering matters."""
        if self.impl == "auto":
            from .ops import on_tpu
            return "pallas" if on_tpu() else "xla"
        return "pallas" if self.impl in ("pallas", "wave_pallas") \
            else "xla"

    @classmethod
    def coerce(cls, value: Union[str, "KernelPolicy", None], *,
               sub_blocks: int = 1,
               dtype_policy: str = "fp32") -> "KernelPolicy":
        """Build a policy from a legacy ``impl`` string (or pass one
        through).  ``sub_blocks`` / ``dtype_policy`` merge in when the
        value is a string or when the given policy still has the
        default; a *conflicting* explicit pair fails here rather than
        silently preferring one."""
        if value is None:
            value = "auto"
        if isinstance(value, str):
            return cls(impl=value, sub_blocks=sub_blocks,
                       dtype_policy=dtype_policy)
        if isinstance(value, KernelPolicy):
            out = value
            if sub_blocks != 1 and sub_blocks != out.sub_blocks:
                if out.sub_blocks != 1:
                    raise ValueError(
                        f"conflicting sub_blocks: policy says "
                        f"{out.sub_blocks}, caller says {sub_blocks}")
                out = dataclasses.replace(out, sub_blocks=sub_blocks)
            if dtype_policy != "fp32" and dtype_policy != out.dtype_policy:
                if out.dtype_policy != "fp32":
                    raise ValueError(
                        f"conflicting dtype_policy: policy says "
                        f"{out.dtype_policy!r}, caller says "
                        f"{dtype_policy!r}")
                out = dataclasses.replace(out, dtype_policy=dtype_policy)
            return out
        raise TypeError(f"cannot coerce {type(value).__name__} to "
                        "KernelPolicy")

    # ------------------------------------------------------------------ #
    def check_packed(self, br, *, pipelined: bool = True) -> None:
        """Validate that a ``BlockedRatings`` carries the layouts this
        policy executes (wave layout present, sub-block pre-partition
        matching).  Raises ``ValueError`` with an actionable message."""
        if self.wave and br.wave_rows is None:
            raise ValueError(
                f"impl={self.impl!r} needs the wave layout; call "
                "partition.pack(..., waves=True) or "
                "MCProblem.packed(..., waves=True)")
        if (pipelined and self.sub_blocks > 1
                and br.sub_blocks != self.sub_blocks):
            raise ValueError(
                f"policy sub_blocks={self.sub_blocks} but ratings were "
                f"packed with sub_blocks={br.sub_blocks}; call "
                "partition.pack(..., sub_blocks=...) to match")

    def cell_arrays(self, br, *, pipelined: bool, step_major: bool = False):
        """Select the rating arrays this policy consumes from a packed
        ``BlockedRatings``: the pre-partitioned per-sub-block lists when
        the pipelined SPMD path is active, the wave layout for wave
        impls, the flat sequential lists otherwise (sub-block pipelining
        only exists on the SPMD path; the local emulator runs whole
        cells, matching seed behaviour).

        ``step_major=True`` returns contiguous ``[step, worker, ...]``
        transposes (``partition.step_major_cells``) — the layout the
        local executor's scan consumes, paid once here instead of a
        ``jnp.swapaxes`` copy inside every epoch dispatch."""
        self.check_packed(br, pipelined=pipelined)
        if pipelined and self.sub_blocks > 1:
            arrays = br.sub_rows, br.sub_cols, br.sub_vals, br.sub_mask
        elif self.wave:
            arrays = (br.wave_rows, br.wave_cols, br.wave_vals,
                      br.wave_mask)
        else:
            arrays = br.rows, br.cols, br.vals, br.mask
        if step_major:
            from ..core.partition import step_major_cells
            arrays = step_major_cells(arrays)
        return arrays

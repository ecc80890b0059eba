"""Pallas-lowered batched design-point pricing (candidate-axis tiling).

The DSE price phase (:mod:`repro.core.pricing`) is pure elementwise
arithmetic over stacked float64 plan columns — exactly the shape Pallas
tiles well: every column is blocked along the batch (candidate) axis and
one grid step prices one tile of candidates entirely on-core. The kernel
body *is* the shared pricing formula (``pricing._price`` — or any other
elementwise column formula, e.g. ``pricing._roofline``), so the operation
order that makes the batched backends bit-identical to the scalar
reference is preserved by construction.

Bit-exactness story
-------------------
The kernel runs in **interpret mode on the host CPU device under
``jax.enable_x64(True)``** — every op is the IEEE-double XLA op the
certified ``jax`` backend uses. It is pinned to the CPU device even where
an accelerator is the default, so it never runs at a demoted precision. Two
compiled-path hazards remain, each pinned off separately:

* LLVM contracts ``a*b + c`` into an FMA inside a fused computation (the
  documented last-ulp drift of the ``jit=True`` pricing path; an
  ``optimization_barrier`` alone does *not* stop it). The call is
  AOT-compiled with ``xla_backend_optimization_level=0`` — a
  *per-computation* compiler option, no process-global ``XLA_FLAGS``.
* XLA's HLO algebraic simplifier re-rounds multi-op patterns, e.g.
  ``div(div(a, b), c) → div(a, b·c)`` in the derate term. Inside the
  kernel every value is a ``_StrictArray`` whose op results each pass
  through an ``optimization_barrier``, so no cross-op pattern is visible
  to the simplifier.

With both in place the kernel is bit-identical to numpy and hence to
``price_plan_scalar``. ``ops.certify()`` proves this row by row, and
``tools/check_pricing_backend.py`` (``DFMODEL_PRICING_BACKEND=pallas``)
enforces it end-to-end against the serial sweep in CI.

Numerics contract (the compiled f32 lowering)
---------------------------------------------
The compiled path (``run_columns_f32`` / the ``pallas-compiled`` backend)
deliberately leaves the certified envelope: float32 tiles of
(8, 128) — the flat candidate axis reshaped into sublane × lane blocks —
with the ragged tail masked to zero through a shipped validity column
instead of neutral-row padding, and NO opt-level-0 / barrier pinning (the
whole point is letting the compiler fuse). Its outputs carry bounded
relative drift vs the f64 envelope instead of bit-identity, and every
consumer must route *decisions* through the drift-budget contract in
:mod:`repro.kernels.pricing.drift`: winners are selected by exactly
re-pricing (f64, numpy-reference arithmetic) every candidate whose f32
iter-time lands within the declared band of the f32 argmin — plus every
feasibility-ambiguous candidate at the capacity boundary — so compiled
winners are provably identical to the scalar reference, and any observed
drift beyond the declared band raises. ``drift.py`` holds the band
(``DFMODEL_DRIFT_BAND``, default ``1e-5``), the banded selection, and the
certification helpers; ``ops.certify_f32`` proves the drift bound on
seeded random vectors. On CPU (no compiled pallas lowering in this jax
version) the kernel runs as an interpret-mode f32 twin — same tiling,
same masking, same dtype — so the numerics are testable anywhere;
``interpret="auto"`` switches to real compilation on an accelerator.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

#: Candidates per grid step. Large enough to amortize interpret-mode
#: dispatch, small enough that a tile of ~26 float64 columns stays resident.
DEFAULT_TILE = 512

#: The compiled f32 tile: 8 sublanes × 128 lanes — the native float32
#: vreg tiling — so one grid step prices 1024 candidates.
F32_SUBLANES = 8
F32_LANES = 128
F32_BLOCK = F32_SUBLANES * F32_LANES


def padded_length(n: int, tile: int = DEFAULT_TILE) -> int:
    """Pad ``n`` to a tile multiple, then bucket to a power-of-two tile
    count, so a sweep of ragged batch sizes shares O(log) cached
    executables instead of minting one per distinct padded length.
    Every batch ≤ ``tile`` lands in one tile; beyond that the pad never
    exceeds 2× the batch."""
    tiles = max(1, math.ceil(n / tile))
    return tile * (1 << (tiles - 1).bit_length())


def _unwrap(x):
    return x.a if isinstance(x, _StrictArray) else x


def _wrap(x):
    return _StrictArray(jax.lax.optimization_barrier(x))


class _StrictArray:
    """An array whose every op result passes through an optimization
    barrier, so XLA's algebraic simplifier cannot pattern-match across ops
    (e.g. the div(div(a, b), c) → div(a, b·c) rewrite that would re-round
    the derate term). Together with the level-0 backend compile this pins
    the kernel to the exact per-op IEEE sequence of the numpy reference."""

    __slots__ = ("a",)

    def __init__(self, a):
        self.a = a

    def astype(self, dtype):
        return _StrictArray(self.a.astype(dtype))


def _defop(name):
    def op(self, other):
        return _wrap(getattr(self.a, name)(_unwrap(other)))
    return op


for _name in ("__add__", "__radd__", "__sub__", "__rsub__", "__mul__",
              "__rmul__", "__truediv__", "__rtruediv__", "__pow__",
              "__lt__", "__le__", "__gt__", "__ge__", "__eq__", "__ne__",
              "__and__", "__rand__", "__or__", "__ror__"):
    setattr(_StrictArray, _name, _defop(_name))


class _StrictNamespace:
    """The ``xp`` shim handed to the formula inside the kernel: jnp ops on
    unwrapped values, every result barrier-wrapped."""

    @staticmethod
    def maximum(a, b):
        return _wrap(jnp.maximum(_unwrap(a), _unwrap(b)))

    @staticmethod
    def minimum(a, b):
        return _wrap(jnp.minimum(_unwrap(a), _unwrap(b)))

    @staticmethod
    def where(cond, x, y):
        return _wrap(jnp.where(_unwrap(cond), _unwrap(x), _unwrap(y)))


def _columns_kernel(*refs, formula, in_names, out_names):
    """One grid step: price a tile of candidates with the shared formula."""
    cols = {name: _StrictArray(ref[...])
            for name, ref in zip(in_names, refs)}
    out = formula(_StrictNamespace, cols)
    for name, ref in zip(out_names, refs[len(in_names):]):
        # bool outputs (the capacity check) travel as 0.0/1.0 float64; the
        # ops wrapper restores the dtype outside the kernel
        ref[...] = _unwrap(out[name]).astype(ref.dtype)


@functools.lru_cache(maxsize=64)
def _compiled_call(formula, in_names: tuple[str, ...],
                   out_names: tuple[str, ...], padded: int, tile: int,
                   interpret: bool):
    """AOT-compile the tiled pallas call at optimization level 0 (see the
    module docstring — this is what pins FMA contraction off). Cached per
    (formula, column layout, padded length) so warm sweeps reuse the
    executable."""
    kernel = functools.partial(_columns_kernel, formula=formula,
                               in_names=in_names, out_names=out_names)
    spec = pl.BlockSpec((tile,), lambda i: (i,))
    call = jax.jit(pl.pallas_call(
        kernel,
        grid=(padded // tile,),
        in_specs=[spec] * len(in_names),
        out_specs=[spec] * len(out_names),
        out_shape=[jax.ShapeDtypeStruct((padded,), jnp.float64)
                   for _ in out_names],
        interpret=interpret,
    ))
    args = [jax.ShapeDtypeStruct((padded,), jnp.float64) for _ in in_names]
    return call.lower(*args).compile(
        compiler_options={"xla_backend_optimization_level": "0"})


def run_columns(formula, cols, out_names, tile: int = DEFAULT_TILE,
                interpret: bool = True) -> dict[str, np.ndarray]:
    """Run an elementwise column formula as a Pallas kernel.

    ``formula(xp, cols) -> dict`` must be pure elementwise arithmetic over
    the batch axis (the :mod:`repro.core.pricing` contract). Columns are
    padded to a tile multiple with neutral 1.0 rows (every pricing
    denominator stays non-zero) and the pad is sliced off the outputs.
    The tile is *not* shrunk to the batch, and padded lengths are
    bucketed to powers of two above the tile (:func:`padded_length`), so
    a sweep of ragged batch sizes shares O(log) cached executables
    instead of triggering a per-length recompile.
    """
    in_names = tuple(cols)
    n = len(next(iter(cols.values())))
    padded = padded_length(n, tile)
    from repro.core.pricing import host_cpu_device

    with jax.enable_x64(True), jax.default_device(host_cpu_device()):
        compiled = _compiled_call(formula, in_names, tuple(out_names),
                                  padded, tile, interpret)
        ins = [jnp.asarray(np.pad(np.asarray(cols[name], dtype=np.float64),
                                  (0, padded - n), constant_values=1.0))
               for name in in_names]
        outs = compiled(*ins)
        return {name: np.asarray(out)[:n]
                for name, out in zip(out_names, outs)}


# --- the compiled f32 lowering (see "Numerics contract" above) ---------------
def _columns_kernel_f32(*refs, formula, in_names, out_names):
    """One grid step: price an (8, 128) candidate tile in float32.

    ``refs[0]`` is the validity tile (1.0 on real candidate rows, 0.0 on
    the ragged tail) — masking through a shipped column instead of a
    baked-in batch length keeps the executable cacheable across every
    batch that buckets to the same padded length."""
    valid = refs[0][...] != 0.0
    cols = {name: ref[...] for name, ref in zip(in_names, refs[1:])}
    out = formula(jnp, cols)
    for name, ref in zip(out_names, refs[1 + len(in_names):]):
        # bool outputs (the capacity check) travel as 0.0/1.0 float32
        ref[...] = jnp.where(valid, out[name].astype(jnp.float32),
                             jnp.float32(0.0))


@functools.lru_cache(maxsize=64)
def _compiled_call_f32(formula, in_names: tuple[str, ...],
                       out_names: tuple[str, ...], padded: int,
                       interpret: bool):
    """The jitted 2D-tiled pallas call. No opt-level-0 pin, no barriers —
    the compiled path trades bit-identity for speed and settles its
    numerics through the drift-budget contract instead. Cached per
    (formula, column layout, bucketed padded length)."""
    kernel = functools.partial(_columns_kernel_f32, formula=formula,
                               in_names=in_names, out_names=out_names)
    rows = padded // F32_LANES
    spec = pl.BlockSpec((F32_SUBLANES, F32_LANES), lambda i: (i, 0))
    return jax.jit(pl.pallas_call(
        kernel,
        grid=(rows // F32_SUBLANES,),
        in_specs=[spec] * (1 + len(in_names)),
        out_specs=[spec] * len(out_names),
        out_shape=[jax.ShapeDtypeStruct((rows, F32_LANES), jnp.float32)
                   for _ in out_names],
        interpret=interpret,
    ))


def resolve_interpret(interpret: bool | str) -> bool:
    """``"auto"`` resolves to the interpret-mode twin on the CPU backend
    and to the real (Mosaic) lowering on an accelerator."""
    if interpret == "auto":
        return jax.default_backend() == "cpu"
    return bool(interpret)


def f32_call(formula, in_names, out_names, n: int,
             interpret: bool | str = "auto"):
    """The jitted f32 kernel that prices ``n`` rows of the ``in_names``
    columns, and the (sublane-rows, 128) float32 shape of each of its
    ``1 + len(in_names)`` arguments: the validity column, then one block
    per input column. :func:`run_columns_f32` runs this callable and
    :func:`.ops.lower_f32` lowers it, so both see one program."""
    padded = padded_length(n, F32_BLOCK)
    call = _compiled_call_f32(formula, tuple(in_names), tuple(out_names),
                              padded, resolve_interpret(interpret))
    return call, (padded // F32_LANES, F32_LANES)


def run_columns_f32(formula, cols, out_names,
                    interpret: bool | str = "auto"
                    ) -> dict[str, np.ndarray]:
    """Run an elementwise column formula as the compiled f32 kernel.

    The flat candidate axis is padded to a power-of-two multiple of
    :data:`F32_BLOCK` (:func:`padded_length`) and reshaped into
    (sublane-rows, 128) blocks; one grid step prices an (8, 128) tile.
    The ragged tail is masked to zero inside the kernel via a shipped
    validity column — no neutral-row padding, so pad rows cost nothing
    and garbage in them can never leak into real outputs.

    ``interpret="auto"`` runs the real (non-interpret) lowering on an
    accelerator backend and the interpret-mode f32 twin on CPU — same
    tiling, masking and dtype, so the drift contract is testable without
    hardware. Outputs are float32 (:mod:`.drift` re-prices decisions
    exactly; see the module docstring's numerics contract).
    """
    n = len(next(iter(cols.values())))
    call, shape = f32_call(formula, tuple(cols), out_names, n, interpret)
    padded = shape[0] * shape[1]

    def block(col: np.ndarray) -> jnp.ndarray:
        flat = np.pad(np.asarray(col, dtype=np.float32), (0, padded - n))
        return jnp.asarray(flat.reshape(shape))

    valid = np.zeros(padded, dtype=np.float32)
    valid[:n] = 1.0
    ins = [jnp.asarray(valid.reshape(shape))]
    ins += [block(cols[name]) for name in cols]
    outs = call(*ins)
    return {name: np.asarray(out).reshape(-1)[:n]
            for name, out in zip(out_names, outs)}

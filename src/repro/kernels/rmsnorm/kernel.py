"""Fused residual-add + RMSNorm Pallas TPU kernel.

The unfused sequence (add → square-mean → rsqrt-scale) is three HBM
round-trips of the (T, d) activation; fusing keeps the row tile in VMEM and
writes both the normed output and the updated residual once — the
row-granularity analogue of the paper's intra-chip tensor pinning.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rms_kernel(x_ref, w_ref, r_ref, y_ref, rout_ref, *, eps: float,
                has_residual: bool):
    x = x_ref[...].astype(jnp.float32)
    if has_residual:
        x = x + r_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * w_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    rout_ref[...] = x.astype(rout_ref.dtype)


def fused_rmsnorm_fwd(x: jax.Array, w: jax.Array,
                      residual: jax.Array | None = None,
                      eps: float = 1e-6, block_rows: int = 256,
                      interpret: bool = False):
    """x: (T, d) -> (normed (T, d), new_residual (T, d))."""
    t, d = x.shape
    block_rows = min(block_rows, t)
    assert t % block_rows == 0
    has_res = residual is not None
    res = residual if has_res else x   # dummy operand when unused

    kernel = functools.partial(_rms_kernel, eps=eps, has_residual=has_res)
    y, rout = pl.pallas_call(
        kernel,
        grid=(t // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, d), x.dtype),
            jax.ShapeDtypeStruct((t, d), x.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, w, res)
    return y, rout

"""Mamba2 SSD intra-chunk Pallas TPU kernel.

The SSD dual form makes the intra-chunk work three MXU matmuls
(C Bᵀ, scores·X, C·h_in) plus elementwise decay — a natural fused dataflow
partition: scores, L, and the chunk state live in VMEM only.

Grid = (B·H, n_chunks); the chunk dimension is sequential ("arbitrary") and
carries the running inter-chunk state h in VMEM scratch, so the *entire*
recurrence runs inside one kernel launch: HBM sees x/dt/B/C tiles in and
y tiles out — no materialized (Q,Q) scores, no per-chunk state round-trips.

TPU adaptation notes: chunk size Q and state N are 128-multiples (MXU edge);
dt/dA are precomputed outside (cheap, elementwise) to keep the kernel purely
matmul+exp.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, b_ref, c_ref, da_ref, y_ref, hout_ref,
                h_ref, *, num_chunks: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)           # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)         # (Q,)
    B = b_ref[0].astype(jnp.float32)           # (Q, N)
    C = c_ref[0].astype(jnp.float32)           # (Q, N)
    dA = da_ref[0].astype(jnp.float32)         # (Q,)

    qn = x.shape[0]
    csum = jnp.cumsum(dA)                      # (Q,)
    diff = csum[:, None] - csum[None, :]
    row = jax.lax.broadcasted_iota(jnp.int32, (qn, qn), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (qn, qn), 1)
    L = jnp.where(row >= col, jnp.exp(diff), 0.0)

    xdt = x * dt[:, None]
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * L
    y = jax.lax.dot_general(scores, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    h = h_ref[...]                             # (N, P)
    y = y + jax.lax.dot_general(C, h, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) \
        * jnp.exp(csum)[:, None]

    decay_out = jnp.exp(csum[-1] - csum)[:, None]
    h_new = h * jnp.exp(csum[-1]) + jax.lax.dot_general(
        B, xdt * decay_out, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    h_ref[...] = h_new
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ic == num_chunks - 1)
    def _final():
        hout_ref[0] = h_new.astype(hout_ref.dtype)


def ssd_chunk_fwd(x: jax.Array, dt: jax.Array, B: jax.Array, C: jax.Array,
                  dA: jax.Array, chunk: int = 128,
                  interpret: bool = False):
    """x: (BH, S, P); dt/dA: (BH, S); B/C: (BH, S, N).

    Returns (y (BH, S, P), h_final (BH, N, P)). The inter-chunk recurrence is
    carried *inside* the kernel across the sequential chunk grid dimension.
    """
    bh, s, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0
    nc = s // chunk

    kernel = functools.partial(_ssd_kernel, num_chunks=nc)
    y, h_final = pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda ih, ic: (ih, ic, 0)),
            pl.BlockSpec((1, chunk), lambda ih, ic: (ih, ic)),
            pl.BlockSpec((1, chunk, n), lambda ih, ic: (ih, ic, 0)),
            pl.BlockSpec((1, chunk, n), lambda ih, ic: (ih, ic, 0)),
            pl.BlockSpec((1, chunk), lambda ih, ic: (ih, ic)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda ih, ic: (ih, ic, 0)),
            pl.BlockSpec((1, n, p), lambda ih, ic: (ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, p), x.dtype),
            jax.ShapeDtypeStruct((bh, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, B, C, dA)
    return y, h_final

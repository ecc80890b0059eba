"""FlashAttention forward as a Pallas TPU kernel.

This is the executable form of the paper's fused intra-chip partition
{MHA1, Softmax, MHA2} (Fig 2C, §VII.B): scores and probabilities never leave
VMEM; only Q/K/V tiles stream from HBM and only O tiles stream back — exactly
the DRAM-traffic reduction DFModel's dataflow mode models.

TPU mapping notes (vs the CUDA original):
  · grid = (B·H, Sq/bq, Sk/bk); the innermost kv dimension is sequential
    ("arbitrary") and carries running (m, l, acc) in VMEM scratch — the MXU
    analogue of the SM-local accumulator.
  · block shapes are (bq, hd)/(bk, hd) with bq=bk=128·k to keep both matmuls
    MXU-aligned (hd is 64 or 128 for all assigned archs).
  · GQA is handled in the K/V index_map (query head → kv head), avoiding the
    materialized head-repeat a naive port would do.
  · causal masking skips fully-masked kv blocks via pl.when on the block
    index — the tile-level equivalent of FlashAttention's early exit.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref,
               acc_ref, m_ref, l_ref, *, causal: bool, scale: float,
               block_q: int, block_k: int, num_k: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _body():
        q = q_ref[0].astype(jnp.float32)            # (bq, hd)
        k = k_ref[0].astype(jnp.float32)            # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    if causal:
        # skip kv blocks strictly above the diagonal (fully masked)
        pl.when(ik * block_k <= iq * block_q + block_q - 1)(_body)
    else:
        _body()

    @pl.when(ik == num_k - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)             # fully-masked rows → 0
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True, block_q: int = 128,
                        block_k: int = 128,
                        interpret: bool = False) -> jax.Array:
    """q: (B, H, Sq, hd); k, v: (B, Hkv, Sk, hd) -> (B, H, Sq, hd)."""
    b, h, sq, hd = q.shape
    _, hkv, sk, _ = k.shape
    assert h % hkv == 0, "GQA requires H % Hkv == 0"
    n_rep = h // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    num_q, num_k = sq // block_q, sk // block_k
    scale = 1.0 / math.sqrt(hd)

    qr = q.reshape(b * h, sq, hd)
    kr = k.reshape(b * hkv, sk, hd)
    vr = v.reshape(b * hkv, sk, hd)

    def q_map(ih, iq, ik):
        return (ih, iq, 0)

    def kv_map(ih, iq, ik):
        # query head ih = ib*h + ihq → kv row ib*hkv + ihq // n_rep
        ib = ih // h
        ihq = ih % h
        return (ib * hkv + ihq // n_rep, ik, 0)

    kernel = functools.partial(_fa_kernel, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k, num_k=num_k)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, num_q, num_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, sq, hd)

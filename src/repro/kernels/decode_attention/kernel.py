"""Split-KV decode attention as a Pallas TPU kernel (FlashDecoding-style).

One query token attends over a long KV cache. The KV sequence is the
streaming dimension: grid = (B·Hkv, S/bk), running (m, l, acc) in VMEM.
All q heads in a GQA group are processed together as the matmul M dimension
(n_rep × hd GEMM rows) so the MXU sees a real matrix even at batch 1.

Exports the log-sum-exp alongside O so the context-parallel combine
(``repro.parallel.context``) can merge per-shard partial attentions across
chips — the distributed half of the paper's fused-decode partition.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _dec_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale: float, block_k: int,
                num_k: int, n_rep: int):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    kv_len = len_ref[0]

    @pl.when(ik * block_k < kv_len)
    def _body():
        q = q_ref[0].astype(jnp.float32)            # (n_rep, hd)
        k = k_ref[0].astype(jnp.float32)            # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (n_rep, block_k), 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)
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

    @pl.when(ik == num_k - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[...] + jnp.log(safe)).astype(lse_ref.dtype)


def decode_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array,
                         kv_len, block_k: int = 256,
                         interpret: bool = False):
    """q: (B, H, hd); k/v: (B, Hkv, S, hd). Returns (o (B,H,hd), lse (B,H))."""
    b, h, hd = q.shape
    _, hkv, s, _ = k.shape
    n_rep = h // hkv
    block_k = min(block_k, s)
    assert s % block_k == 0
    num_k = s // block_k
    scale = 1.0 / math.sqrt(hd)

    qr = q.reshape(b * hkv, n_rep, hd)
    kr = k.reshape(b * hkv, s, hd)
    vr = v.reshape(b * hkv, s, hd)
    len_arr = jnp.full((1,), kv_len, jnp.int32) if not hasattr(kv_len, "shape") \
        else kv_len.reshape(1).astype(jnp.int32)

    kernel = functools.partial(_dec_kernel, scale=scale, block_k=block_k,
                               num_k=num_k, n_rep=n_rep)
    o, lse = pl.pallas_call(
        kernel,
        grid=(b * hkv, num_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM),
            pl.BlockSpec((1, n_rep, hd), lambda ih, ik: (ih, 0, 0)),
            pl.BlockSpec((1, block_k, hd), lambda ih, ik: (ih, ik, 0)),
            pl.BlockSpec((1, block_k, hd), lambda ih, ik: (ih, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, n_rep, hd), lambda ih, ik: (ih, 0, 0)),
            pl.BlockSpec((1, n_rep), lambda ih, ik: (ih, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, n_rep, hd), q.dtype),
            jax.ShapeDtypeStruct((b * hkv, n_rep), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_rep, hd), jnp.float32),
            pltpu.VMEM((n_rep,), jnp.float32),
            pltpu.VMEM((n_rep,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(len_arr, qr, kr, vr)
    return o.reshape(b, h, hd), lse.reshape(b, h)

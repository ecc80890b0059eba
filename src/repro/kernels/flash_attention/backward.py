"""FlashAttention backward as Pallas TPU kernels (FlashAttention-2 §3.2).

The forward-with-LSE variant exports the per-row log-sum-exp so the backward
never rematerializes the (Sq, Sk) probability matrix in HBM: each tile
recomputes P = exp(QKᵀ·scale − LSE) in VMEM and contracts it immediately.

Two kernels, mirroring the FA-2 work partition:
  · dKV kernel — grid (B·H, kv-blocks, q-blocks): the q dimension is
    sequential and carries (dk, dv) accumulators in VMEM; one pass over Q/dO
    per kv tile. GQA reduction over the query heads of a kv head happens
    outside (a cheap reshape-sum).
  · dQ kernel — grid (B·H, q-blocks, kv-blocks): kv sequential, carries the
    dq accumulator.

D = rowsum(dO ∘ O) is precomputed outside (one elementwise pass).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


# ------------------------- forward with LSE export ---------------------------
def _fa_fwd_lse_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                       acc_ref, m_ref, l_ref, *, causal, scale,
                       block_q, block_k, num_k):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
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
        pl.when(ik * block_k <= iq * block_q + block_q - 1)(_body)
    else:
        _body()

    @pl.when(ik == num_k - 1)
    def _finalize():
        l = l_ref[...]
        lsafe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / lsafe[:, None]).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(lsafe)


def flash_attention_fwd_lse(q, k, v, causal=True, block_q=128, block_k=128,
                            interpret=False):
    """Forward returning (o, lse) — the training-path variant."""
    b, h, sq, hd = q.shape
    _, hkv, sk, _ = k.shape
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

    def lse_map(ih, iq, ik):
        return (ih, iq)

    def kv_map(ih, iq, ik):
        ib, ihq = ih // h, ih % h
        return (ib * hkv + ihq // n_rep, ik, 0)

    kernel = functools.partial(_fa_fwd_lse_kernel, causal=causal,
                               scale=scale, block_q=block_q,
                               block_k=block_k, num_k=num_k)
    o, lse = pl.pallas_call(
        kernel,
        grid=(b * h, num_q, num_k),
        in_specs=[pl.BlockSpec((1, block_q, hd), q_map),
                  pl.BlockSpec((1, block_k, hd), kv_map),
                  pl.BlockSpec((1, block_k, hd), kv_map)],
        out_specs=[pl.BlockSpec((1, block_q, hd), q_map),
                   pl.BlockSpec((1, block_q), lse_map)],
        out_shape=[jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
                   jax.ShapeDtypeStruct((b * h, sq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32),
                        pltpu.VMEM((block_q,), jnp.float32),
                        pltpu.VMEM((block_q,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return o.reshape(b, h, sq, hd), lse.reshape(b, h, sq)


# ----------------------------- tile recompute --------------------------------
def _tile_p(q, k, lse, scale, causal, iq, ik, block_q, block_k):
    """P = exp(QKᵀ·scale − LSE) for one (q, k) tile, fp32."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)
    return jnp.exp(s - lse[:, None])


# ------------------------------- dKV kernel ----------------------------------
def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                       dk_ref, dv_ref, dk_acc, dv_acc, *, causal, scale,
                       block_q, block_k, num_q):
    ik = pl.program_id(1)   # kv block (parallel)
    iq = pl.program_id(2)   # q block (sequential, carries accumulators)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        dd = dd_ref[0]
        p = _tile_p(q, k, lse, scale, causal, iq, ik, block_q, block_k)
        # dV += Pᵀ dO
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dP = dO Vᵀ ; dS = P ∘ (dP − D)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dd[:, None])
        # dK += dSᵀ Q · scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        # q blocks strictly above the diagonal see no kv of this tile
        pl.when(iq * block_q + block_q - 1 >= ik * block_k)(_body)
    else:
        _body()

    @pl.when(iq == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# -------------------------------- dQ kernel ----------------------------------
def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                      dq_ref, dq_acc, *, causal, scale, block_q, block_k,
                      num_k):
    iq = pl.program_id(1)   # q block (parallel)
    ik = pl.program_id(2)   # kv block (sequential, carries dq)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        dd = dd_ref[0]
        p = _tile_p(q, k, lse, scale, causal, iq, ik, block_q, block_k)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dd[:, None])
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        pl.when(ik * block_k <= iq * block_q + block_q - 1)(_body)
    else:
        _body()

    @pl.when(ik == num_k - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


# ------------------------------ host wrapper ---------------------------------
def flash_attention_bwd(q, k, v, o, lse, do, causal=True,
                        block_q=128, block_k=128, interpret=False):
    """Returns (dq, dk, dv). q/o/do: (B,H,Sq,hd); k,v: (B,Hkv,Sk,hd);
    lse: (B,H,Sq). GQA: per-query-head dk/dv are reduced over the group."""
    b, h, sq, hd = q.shape
    _, hkv, sk, _ = k.shape
    n_rep = h // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    num_q, num_k = sq // block_q, sk // block_k
    scale = 1.0 / math.sqrt(hd)

    # D = rowsum(dO ∘ O) — one cheap elementwise pass
    dd = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    qr = q.reshape(b * h, sq, hd)
    kr = k.reshape(b * hkv, sk, hd)
    vr = v.reshape(b * hkv, sk, hd)
    dor = do.reshape(b * h, sq, hd)
    lser = lse.reshape(b * h, sq)
    ddr = dd.reshape(b * h, sq)

    def kv_of(ih):
        ib, ihq = ih // h, ih % h
        return ib * hkv + ihq // n_rep

    # ---- dk / dv (per query head; reduce over the GQA group afterwards) ----
    dkv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k, num_q=num_q),
        grid=(b * h, num_k, num_q),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda ih, ik, iq: (ih, iq, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda ih, ik, iq: (kv_of(ih), ik, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda ih, ik, iq: (kv_of(ih), ik, 0)),
            pl.BlockSpec((1, block_q, hd), lambda ih, ik, iq: (ih, iq, 0)),
            pl.BlockSpec((1, block_q), lambda ih, ik, iq: (ih, iq)),
            pl.BlockSpec((1, block_q), lambda ih, ik, iq: (ih, iq)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, hd), lambda ih, ik, iq: (ih, ik, 0)),
            pl.BlockSpec((1, block_k, hd), lambda ih, ik, iq: (ih, ik, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b * h, sk, hd), jnp.float32),
                   jax.ShapeDtypeStruct((b * h, sk, hd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_k, hd), jnp.float32),
                        pltpu.VMEM((block_k, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr, dor, lser, ddr)
    dk_h, dv_h = dkv
    dk = dk_h.reshape(b, hkv, n_rep, sk, hd).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(b, hkv, n_rep, sk, hd).sum(axis=2).astype(v.dtype)

    # ---- dq --------------------------------------------------------------
    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k, num_k=num_k),
        grid=(b * h, num_q, num_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda ih, iq, ik: (ih, iq, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda ih, iq, ik: (kv_of(ih), ik, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda ih, iq, ik: (kv_of(ih), ik, 0)),
            pl.BlockSpec((1, block_q, hd), lambda ih, iq, ik: (ih, iq, 0)),
            pl.BlockSpec((1, block_q), lambda ih, iq, ik: (ih, iq)),
            pl.BlockSpec((1, block_q), lambda ih, iq, ik: (ih, iq)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd),
                               lambda ih, iq, ik: (ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr, dor, lser, ddr)
    return dq.reshape(b, h, sq, hd), dk, dv

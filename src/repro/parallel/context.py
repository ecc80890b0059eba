"""Context-parallel decode attention: KV cache sharded along the sequence
dimension across the 'model' axis, combined with a distributed log-sum-exp.

This is the hand-fused alternative to letting GSPMD auto-partition the decode
softmax (which all-gathers score rows). Each chip runs the split-KV Pallas
kernel (or its jnp twin) over its local KV shard, exporting (o_local, lse);
the exact global attention is

    w_i = exp(lse_i - max_j lse_j);   o = Σ_i w_i·o_i / Σ_i w_i

— two tiny psums of (B, H) + (B, H, hd) instead of a (B, H, S) all-gather.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..kernels.decode_attention.ref import decode_attention_ref


def _local_decode(q, k, v, kv_len, use_kernel: bool):
    if use_kernel:
        from ..kernels.decode_attention.ops import decode_attention
        return decode_attention(q, k, v, kv_len, return_lse=True)
    return decode_attention_ref(q, k, v, kv_len, return_lse=True)


def lse_combine(o: jax.Array, lse: jax.Array, axis: str):
    """Merge per-shard partial attentions along ``axis``.

    o: (B, H, hd) local numerator/denominator-normalized output;
    lse: (B, H) local log-sum-exp. Exact for disjoint KV shards."""
    m = jax.lax.pmax(lse, axis)
    w = jnp.exp(lse - m)
    num = jax.lax.psum(o.astype(jnp.float32) * w[..., None], axis)
    den = jax.lax.psum(w, axis)
    return (num / den[..., None]).astype(o.dtype)


def decode_attention_cache_layout(mesh: Mesh, q, cache_k, cache_v, kv_len,
                                  batch_axes=("data",), axis: str = "model"):
    """Context-parallel decode over the model's cache layout.

    q: (B, H, hd) — replicated over ``axis`` inside the map (tiny);
    cache_{k,v}: (B, Smax, Hkv, hd) with Smax sharded on ``axis`` and B on
    the data axes; kv_len: global valid length (pos + 1).

    Collective: one psum of (B, H, hd) + (B, H) instead of GSPMD's
    all-gather of the KV cache — O(B·H·hd) vs O(Smax·Hkv·hd) per step.
    """
    ba = batch_axes if isinstance(batch_axes, (tuple, list)) else (batch_axes,)
    ba = tuple(a for a in ba if a in mesh.axis_names)
    bspec = ba if len(ba) > 1 else (ba[0] if ba else None)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(bspec, None, None),
                       P(bspec, axis, None, None),
                       P(bspec, axis, None, None), P()),
             out_specs=P(bspec, None, None), check_vma=False)
    def fn(q_l, k_shard, v_shard, kv_len):
        idx = jax.lax.axis_index(axis)
        s_local = k_shard.shape[1]
        local_start = idx * s_local
        local_len = jnp.clip(kv_len - local_start, 0, s_local)
        # (B, S, Hkv, hd) -> (B, Hkv, S, hd) for the split-KV layout
        ks = k_shard.transpose(0, 2, 1, 3)
        vs = v_shard.transpose(0, 2, 1, 3)
        o, lse = decode_attention_ref(q_l, ks, vs, local_len,
                                      return_lse=True)
        lse = jnp.where(local_len > 0, lse, -jnp.inf)
        o = jnp.where(local_len > 0, o, 0.0)
        return lse_combine(o, lse, axis)

    return fn(q, cache_k, cache_v, kv_len)


def context_parallel_decode(mesh: Mesh, axis: str = "model",
                            use_kernel: bool = False):
    """Returns fn(q (B,H,hd), k/v (B,Hkv,S,hd) seq-sharded, kv_len) -> o.

    ``kv_len`` is the *global* valid length; each shard masks its local
    window using its axis index.
    """
    n_shards = mesh.shape[axis]

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(), P(None, None, axis, None),
                       P(None, None, axis, None), P()),
             out_specs=P(), check_vma=False)
    def fn(q, k_shard, v_shard, kv_len):
        idx = jax.lax.axis_index(axis)
        s_local = k_shard.shape[2]
        local_start = idx * s_local
        local_len = jnp.clip(kv_len - local_start, 0, s_local)
        o, lse = _local_decode(q, k_shard, v_shard, local_len, use_kernel)
        # shards past the valid prefix contribute nothing
        lse = jnp.where(local_len > 0, lse, -jnp.inf)
        o = jnp.where(local_len > 0, o, 0.0)
        return lse_combine(o, lse, axis)

    return fn

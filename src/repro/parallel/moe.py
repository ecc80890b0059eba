"""Hand-scheduled expert-parallel MoE dispatch (beyond-paper optimization).

Under pure GSPMD, the capacity-buffer scatter in ``models.layers.moe`` —
``buf.at[expert, slot].add(token)`` into an expert-sharded (E, cap, d)
buffer — partitions poorly: the compiler materializes and all-reduces the
*full* capacity buffer (E·cap·d bytes per MoE layer), which makes MoE
training collective-bound (see EXPERIMENTS.md §Perf, olmoe baseline).

This module replaces it with an explicit shard_map schedule:

  · tokens are replicated across the 'model' axis (they already are after
    the attention block's output all-reduce);
  · every shard runs the identical router math, then builds ONLY its local
    experts' capacity buffer (a local scatter, no communication);
  · local experts compute their FFN;
  · each shard gathers its experts' outputs back to token order and the
    partial token outputs are combined with one psum of (T, d) — the only
    collective in the layer.

Collective payload per MoE layer drops from O(E·cap·d) to O(T·d).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


def moe_shard_map(p: dict, x: jax.Array, cfg, mesh: Mesh,
                  capacity_factor: float | None = None) -> jax.Array:
    """Drop-in replacement for layers.moe under an active mesh.

    x: (B, S, d) with B sharded over the data axes and replicated over
    'model'; expert weights (E, d, f) sharded over 'model' on dim 0.
    """
    e = cfg.moe_experts
    k = cfg.moe_top_k
    m_size = mesh.shape["model"]
    assert e % m_size == 0
    e_local = e // m_size
    cf = cfg.moe_capacity_factor if capacity_factor is None else capacity_factor
    ba = _batch_axes(mesh)
    has_gate = "wg" in p

    wspec = P("model", None, None)
    in_specs = [P(ba, None, None), P(None, None), wspec, wspec]
    if has_gate:
        in_specs.insert(3, wspec)

    @partial(jax.shard_map, mesh=mesh, in_specs=tuple(in_specs),
             out_specs=P(ba, None, None), check_vma=False)
    def fn(x_l, router, wi, *rest):
        if has_gate:
            wg, wo = rest
        else:
            (wo,) = rest
        b, s, d = x_l.shape
        t = b * s
        xt = x_l.reshape(t, d)
        # --- routing: identical on every 'model' shard (replicated) --------
        logits = (xt @ router.astype(xt.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, k)                     # (T, k)
        gates = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)
        cap = int(max(1, math.ceil(t * k / e * cf)))
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)         # (T, k, E)
        flat = onehot.reshape(t * k, e)
        rank = jnp.cumsum(flat, axis=0) - 1
        rank = (rank * flat).sum(-1).reshape(t, k)               # (T, k)
        keep = rank < cap
        # --- local dispatch: only this shard's experts ---------------------
        lo = jax.lax.axis_index("model") * e_local
        local = keep & (idx >= lo) & (idx < lo + e_local)
        ei = jnp.where(local, idx - lo, 0).reshape(-1)
        ri = jnp.where(local, rank, 0).reshape(-1)
        w_keep = (gates * local).reshape(-1)                     # (T·k,)
        tok = jnp.broadcast_to(xt[:, None, :], (t, k, d)).reshape(t * k, d)
        buf = jnp.zeros((e_local, cap, d), x_l.dtype)
        buf = buf.at[ei, ri].add(tok * (w_keep > 0)[:, None].astype(x_l.dtype))
        # --- local expert FFN ----------------------------------------------
        h = jnp.einsum("ecd,edf->ecf", buf, wi.astype(x_l.dtype))
        if has_gate:
            g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(x_l.dtype))
            h = jax.nn.silu(g) * h
        else:
            h = jax.nn.gelu(h)
        out = jnp.einsum("ecf,efd->ecd", h, wo.astype(x_l.dtype))
        # --- combine: gather local contributions, one psum over 'model' ----
        y = out[ei, ri].reshape(t, k, d)
        y = (y * w_keep.reshape(t, k, 1).astype(x_l.dtype)).sum(axis=1)
        y = jax.lax.psum(y, "model")
        return y.reshape(b, s, d)

    args = [x, p["router"], p["wi"]]
    if has_gate:
        args.append(p["wg"])
    args.append(p["wo"])
    return fn(*args)

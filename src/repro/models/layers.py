"""Model layers — pure-functional JAX, mesh-agnostic.

Sharding is expressed through logical-axis annotations (``repro.parallel.
logical.shard``) which are no-ops until the launcher installs axis rules, so
the same code runs single-device tests and the 512-chip dry-run.

The attention and SSD implementations here are the *reference* paths (also
serving as the structural twins of the Pallas kernels in ``repro.kernels``);
``use_kernels=True`` in the call context swaps in the fused kernels on TPU.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from ..parallel.logical import shard
from .config import ModelConfig

Pytree = object


def _mm(x: jax.Array, w: jax.Array, cfg: "ModelConfig | None" = None):
    """Projection matmul. With cfg.matmul_out == 'bf16' the dot itself emits
    bf16 (instead of JAX's default f32-accumulate + convert), so GSPMD's
    row-parallel partial-sum all-reduces move bf16 — half the link bytes
    (§Perf knob; numerically the standard Megatron practice)."""
    w = w.astype(x.dtype)
    if (cfg is not None and cfg.matmul_out == "bf16"
            and x.dtype == jnp.bfloat16):
        return jax.lax.dot_general(
            x, w, (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.bfloat16)
    return x @ w


# =============================== initializers ================================
def _dense_init(key, fan_in: int, shape) -> jax.Array:
    std = 1.0 / math.sqrt(fan_in)
    return jax.random.normal(key, shape, dtype=jnp.float32) * std


# ================================ norms ======================================
def rmsnorm(x: jax.Array, w: jax.Array | None, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    if w is not None:
        y = y * w
    return y.astype(dt)


def layernorm(x: jax.Array, w: jax.Array | None, b: jax.Array | None,
              eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    if w is not None:
        y = y * w
    if b is not None:
        y = y + b
    return y.astype(dt)


def make_norm(cfg: ModelConfig):
    """Returns (init_fn, apply_fn) for the config's norm flavor.

    OLMo's non-parametric LayerNorm carries no weights at all."""
    if cfg.norm == "nonparam_ln":
        return (lambda key, d: {},
                lambda p, x: layernorm(x, None, None))
    if cfg.norm == "layernorm":
        return (lambda key, d: {"w": jnp.ones((d,), jnp.float32),
                                "b": jnp.zeros((d,), jnp.float32)},
                lambda p, x: layernorm(x, p["w"], p["b"]))
    return (lambda key, d: {"w": jnp.ones((d,), jnp.float32)},
            lambda p, x: rmsnorm(x, p["w"], cfg.rms_eps))


# ================================ RoPE =======================================
def rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); pos: (S,) or scalar broadcastable positions."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    ang = pos[..., None].astype(jnp.float32) * freqs    # (S, hd/2)
    cos = jnp.cos(ang)[..., None, :]                    # (S, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ============================ attention (ref) ================================
def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, Hkv, hd) -> (B, S, Hkv*n_rep, hd) for GQA."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)
                            ).reshape(b, s, h * n_rep, d)


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = True, q_offset: int = 0,
                  scale: float | None = None) -> jax.Array:
    """Dense reference attention. q: (B,Sq,H,hd); k,v: (B,Sk,Hkv,hd);
    softmax scale ``scale`` (None: 1/sqrt(hd))."""
    b, sq, h, hd = q.shape
    n_rep = h // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        qi = jnp.arange(sq)[:, None] + q_offset
        ki = jnp.arange(k.shape[1])[None, :]
        logits = jnp.where(ki <= qi, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


def attention_chunked(q: jax.Array, k: jax.Array, v: jax.Array,
                      causal: bool = True, block_q: int = 1024,
                      block_k: int = 1024,
                      scale: float | None = None) -> jax.Array:
    """Memory-efficient online-softmax attention (FlashAttention schedule in
    pure jnp — the structural twin of kernels/flash_attention). O(S) memory.

    Shapes as in attention_ref, Sq == Sk required when causal.
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    n_rep = h // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    nq = sq // block_q
    nk = sk // block_k
    qb = q.reshape(b, nq, block_q, h, hd)

    def q_block(carry, qi):
        qblk = qb[:, qi]                                   # (B, bq, H, hd)
        acc0 = jnp.zeros((b, block_q, h, hd), jnp.float32)
        m0 = jnp.full((b, h, block_q), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, block_q), jnp.float32)

        def kv_block(state, ki):
            acc, m, l = state
            kblk = jax.lax.dynamic_slice_in_dim(k, ki * block_k, block_k, 1)
            vblk = jax.lax.dynamic_slice_in_dim(v, ki * block_k, block_k, 1)
            s = jnp.einsum("bqhd,bkhd->bhqk", qblk.astype(jnp.float32),
                           kblk.astype(jnp.float32)) * scale
            if causal:
                qpos = qi * block_q + jnp.arange(block_q)[:, None]
                kpos = ki * block_k + jnp.arange(block_k)[None, :]
                s = jnp.where(kpos <= qpos, s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = (acc * alpha.transpose(0, 2, 1)[..., None]
                       + jnp.einsum("bhqk,bkhd->bqhd", p,
                                    vblk.astype(jnp.float32)))
            return (acc_new, m_new, l_new), None

        if causal:
            # only lower-triangular kv blocks contribute; still scan all for
            # static shape, masked blocks are numerically no-ops
            pass
        (acc, m, l), _ = jax.lax.scan(kv_block, (acc0, m0, l0),
                                      jnp.arange(nk))
        out = acc / l.transpose(0, 2, 1)[..., None]
        return carry, out.astype(q.dtype)

    _, blocks = jax.lax.scan(q_block, None, jnp.arange(nq))
    # blocks: (nq, B, bq, H, hd) -> (B, S, H, hd)
    return blocks.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, hd)


def attention(q, k, v, causal=True, q_offset: int = 0,
              chunked_threshold: int = 8192, scale: float | None = None):
    """Dispatch dense vs chunked by sequence length."""
    sk = k.shape[1]
    sq = q.shape[1]
    if sq * sk > chunked_threshold * chunked_threshold // 16 and sq > 1 \
            and sq % 1024 == 0 and sk % 1024 == 0 and q_offset == 0:
        return attention_chunked(q, k, v, causal, scale=scale)
    return attention_ref(q, k, v, causal, q_offset, scale=scale)


# ============================ GQA attention layer ============================
def init_attention(key, cfg: ModelConfig, cross: bool = False) -> dict:
    d, hd = cfg.d_model, cfg.hd
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": _dense_init(kq, d, (d, cfg.n_heads * hd)),
        "wk": _dense_init(kk, d, (d, cfg.n_kv_heads * hd)),
        "wv": _dense_init(kv, d, (d, cfg.n_kv_heads * hd)),
        "wo": _dense_init(ko, cfg.n_heads * hd, (cfg.n_heads * hd, d)),
    }


def self_attention(p: dict, x: jax.Array, cfg: ModelConfig,
                   positions: jax.Array, causal: bool = True) -> jax.Array:
    b, s, d = x.shape
    hd = cfg.hd
    q = _mm(x, p["wq"], cfg).reshape(b, s, cfg.n_heads, hd)
    k = _mm(x, p["wk"], cfg).reshape(b, s, cfg.n_kv_heads, hd)
    v = _mm(x, p["wv"], cfg).reshape(b, s, cfg.n_kv_heads, hd)
    if cfg.pos_emb == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)
    o = attention(q, k, v, causal=causal, scale=cfg.attn_scale)
    o = o.reshape(b, s, cfg.n_heads * hd)
    return shard(_mm(o, p["wo"], cfg), "batch", "seq", None)


def cross_attention(p: dict, x: jax.Array, memory: jax.Array,
                    cfg: ModelConfig) -> jax.Array:
    """x: (B,S,d) queries; memory: (B,M,d) (image/audio/encoder states)."""
    b, s, d = x.shape
    hd = cfg.hd
    q = _mm(x, p["wq"], cfg).reshape(b, s, cfg.n_heads, hd)
    k = _mm(memory, p["wk"], cfg).reshape(
        b, memory.shape[1], cfg.n_kv_heads, hd)
    v = _mm(memory, p["wv"], cfg).reshape(
        b, memory.shape[1], cfg.n_kv_heads, hd)
    q = shard(q, "batch", "seq", "heads", None)
    o = attention(q, k, v, causal=False)
    o = o.reshape(b, s, cfg.n_heads * hd)
    return shard(_mm(o, p["wo"], cfg), "batch", "seq", None)


def _at(buf: jax.Array, at: tuple) -> jax.Array:
    """The entry of ``buf`` at the leading indices ``at`` (ints or traced
    scalars): a dynamic slice, so a consumer can read it in place."""
    starts = (*at,) + (0,) * (buf.ndim - len(at))
    sizes = (1,) * len(at) + buf.shape[len(at):]
    return jax.lax.dynamic_slice(buf, starts, sizes).reshape(
        buf.shape[len(at):])


def _write_at(buf: jax.Array, at: tuple, new: jax.Array,
              offset: tuple = ()) -> jax.Array:
    """``buf`` with ``new`` written into its entry at ``at``, starting at
    ``offset`` within the entry: one ``dynamic_update_slice``."""
    starts = (*at, *offset) + (0,) * (new.ndim - len(offset))
    return jax.lax.dynamic_update_slice(
        buf, new.astype(buf.dtype).reshape((1,) * len(at) + new.shape),
        starts)


def decode_self_attention(p: dict, x: jax.Array, cache_k: jax.Array,
                          cache_v: jax.Array, at: tuple, pos: jax.Array,
                          cfg: ModelConfig):
    """One-token decode. x: (B,1,d); pos scalar; cache_{k,v}: the stacked
    buffers whose entry at the leading indices ``at`` is this layer's
    (B,Smax,Hkv,hd) cache.

    The new K/V row is written into the buffers (one dynamic_update_slice
    each, in place when the caller carries them) and attention reads the
    layer's entry of the updated buffers. Returns (out, cache_k, cache_v).

    Under the production mesh the cache sequence dim is sharded on 'model'
    (context parallelism): GSPMD turns the softmax/O reductions into
    collectives; the hand-fused path is kernels/decode_attention.
    """
    b, _, d = x.shape
    hd = cfg.hd
    with jax.named_scope("attention"):
        q = _mm(x, p["wq"], cfg).reshape(b, 1, cfg.n_heads, hd)
        k = _mm(x, p["wk"], cfg).reshape(b, 1, cfg.n_kv_heads, hd)
        v = _mm(x, p["wv"], cfg).reshape(b, 1, cfg.n_kv_heads, hd)
        if cfg.pos_emb == "rope":
            q = apply_rope(q, pos[None], cfg.rope_theta)
            k = apply_rope(k, pos[None], cfg.rope_theta)
    with jax.named_scope("kv_cache"):
        cache_k = _write_at(cache_k, at, k, (0, pos))
        cache_v = _write_at(cache_v, at, v, (0, pos))
        ck, cv = _at(cache_k, at), _at(cache_v, at)
    if cfg.decode_attn == "context_parallel":
        from ..parallel.logical import current_mesh
        mesh = current_mesh()
        if (mesh is not None and "model" in mesh.axis_names
                and ck.shape[1] % mesh.shape["model"] == 0):
            from ..parallel.context import decode_attention_cache_layout
            ba = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
            qf = q[:, 0].astype(jnp.float32)
            if cfg.attn_scale is not None:   # the kernel scales by 1/sqrt(hd)
                qf = qf * (cfg.attn_scale * math.sqrt(hd))
            o = decode_attention_cache_layout(mesh, qf, ck, cv, pos + 1, ba)
            o = o.astype(x.dtype).reshape(b, 1, cfg.n_heads * hd)
            return _mm(o, p["wo"], cfg), cache_k, cache_v
    smax = ck.shape[1]
    n_rep = cfg.n_heads // cfg.n_kv_heads
    with jax.named_scope("kv_cache"):      # the cache, cast for attention
        kk = _repeat_kv(ck, n_rep).astype(jnp.float32)
        vv = _repeat_kv(cv, n_rep).astype(jnp.float32)
    with jax.named_scope("attention"):
        logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kk)
        logits = (logits / math.sqrt(hd) if cfg.attn_scale is None
                  else logits * cfg.attn_scale)
        mask = jnp.arange(smax)[None, None, None, :] <= pos
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        # keep the PV contraction in f32: downcasting probs to the cache
        # dtype costs ~3 decimal digits for nothing and makes greedy decode
        # disagree with the context-parallel path (which reduces in f32) on
        # near-ties
        o = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
        o = o.astype(x.dtype).reshape(b, 1, cfg.n_heads * hd)
        return o @ p["wo"].astype(x.dtype), cache_k, cache_v


# ================================= MLP =======================================
def init_mlp(key, cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ki, kg, ko = jax.random.split(key, 3)
    p = {"wi": _dense_init(ki, d, (d, f)),
         "wo": _dense_init(ko, f, (f, d))}
    if cfg.gated:
        p["wg"] = _dense_init(kg, d, (d, f))
    return p


def mlp(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    h = _mm(x, p["wi"], cfg)
    h = shard(h, "batch", "seq", "ff")
    if "wg" in p:
        h = jax.nn.silu(_mm(x, p["wg"], cfg)) * h
    else:
        h = jax.nn.gelu(h)
    return shard(_mm(h, p["wo"], cfg), "batch", "seq", None)


# ================================= MoE =======================================
def init_moe(key, cfg: ModelConfig) -> dict:
    """The router over all ``cfg.moe_experts`` and the weights of the
    experts this chip holds (``cfg.experts_held``)."""
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe_experts
    held = cfg.experts_held[1]
    kr, ki, kg, ko = jax.random.split(key, 4)
    p = {"router": _dense_init(kr, d, (d, e)),
         "wi": _dense_init(ki, d, (held, d, f)),
         "wo": _dense_init(ko, f, (held, f, d))}
    if cfg.gated:
        p["wg"] = _dense_init(kg, d, (held, d, f))
    return p


def moe(p: dict, x: jax.Array, cfg: ModelConfig,
        capacity_factor: float | None = None) -> jax.Array:
    """Top-k token-choice MoE with capacity-bounded scatter dispatch
    (Switch/GShard style). Experts are sharded on the 'model' axis (EP);
    under GSPMD the dispatch/combine scatters lower to all-to-alls.

    With ``cfg.moe_dispatch == 'shard_map'`` and an active mesh, the
    hand-scheduled expert-parallel dispatch (parallel/moe.py) replaces the
    GSPMD auto-partitioned scatter — O(T·d) collective instead of
    O(E·cap·d). See EXPERIMENTS.md §Perf.
    """
    if cfg.moe_dispatch == "shard_map":
        from ..parallel.logical import current_mesh
        mesh = current_mesh()
        if (mesh is not None and "model" in mesh.axis_names
                and cfg.moe_experts % mesh.shape["model"] == 0):
            from ..parallel.moe import moe_shard_map
            return moe_shard_map(p, x, cfg, mesh, capacity_factor)
    b, s, d = x.shape
    e, k = cfg.moe_experts, cfg.moe_top_k
    t = b * s
    xt = x.reshape(t, d)
    logits = (xt @ p["router"].astype(x.dtype)).astype(jnp.float32)  # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                             # (T,k)
    gates = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)

    cf = cfg.moe_capacity_factor if capacity_factor is None else capacity_factor
    cap = int(max(1, math.ceil(t * k / e * cf)))
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)                 # (T,k,E)
    flat = onehot.reshape(t * k, e)
    # position of each (token, slot) within its expert's buffer
    rank = jnp.cumsum(flat, axis=0) - 1                              # (T*k,E)
    rank = (rank * flat).sum(-1).reshape(t, k)
    eidx = idx                                                       # (T,k)
    keep = rank < cap
    # scatter tokens into (E, cap, d)
    buf = jnp.zeros((e, cap, d), x.dtype)
    tok_rep = jnp.broadcast_to(xt[:, None, :], (t, k, d)).reshape(t * k, d)
    ei = jnp.where(keep, eidx, 0).reshape(-1)
    ri = jnp.where(keep, rank, 0).reshape(-1)
    w_keep = (gates * keep).reshape(-1)
    buf = buf.at[ei, ri].add(tok_rep * (w_keep > 0)[:, None].astype(x.dtype))
    buf = shard(buf, "experts", None, None)
    # expert computation (E, cap, d) x (E, d, f)
    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"].astype(x.dtype))
    if "wg" in p:
        g = jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(x.dtype))
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    out = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(x.dtype))
    out = shard(out, "experts", None, None)
    # combine: gather each (token, slot)'s result and weight by gate
    y = out[ei, ri].reshape(t, k, d)
    y = (y * (w_keep.reshape(t, k, 1)).astype(x.dtype)).sum(axis=1)
    return y.reshape(b, s, d)


def moe_dropless(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Dropless top-k MoE over the experts this chip holds.

    The router scores all ``cfg.moe_experts`` (from f32 operands, so that
    rounding does not reorder near-tied experts); each token takes its
    top-k logits and a softmax over those k (softmax, then renormalised
    over the k). The result is each held expert among a token's k times
    its gate, summed: on a chip that holds every expert, the whole layer;
    under expert parallelism, this chip's part of it. No token is dropped.

    Under a mesh with a 'model' axis that divides the held experts, each
    shard computes the part of its own experts for its batch rows and one
    psum over 'model' adds the parts (the shard_map schedule of
    ``parallel/moe.py``); the experts' weights never move.
    """
    first, held = cfg.experts_held
    from ..parallel.logical import current_mesh
    mesh = current_mesh()
    if (mesh is None or "model" not in mesh.axis_names
            or held % mesh.shape["model"]):
        return _moe_held_part(p, x, cfg, first, held).astype(x.dtype)
    from jax.sharding import PartitionSpec as P
    m = mesh.shape["model"]
    ba = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    if x.shape[0] % math.prod(mesh.shape[a] for a in ba):
        ba = None                         # too few rows to split: replicate
    names = ["router", "wi", "wo"] + (["wg"] if "wg" in p else [])
    specs = {n: P(None, None) if n == "router" else P("model", None, None)
             for n in names}

    @partial(jax.shard_map, mesh=mesh, check_vma=False,
             in_specs=(specs, P(ba, None, None)), out_specs=P(ba, None, None))
    def shard_part(pl, xl):
        lo = first + jax.lax.axis_index("model") * (held // m)
        return jax.lax.psum(_moe_held_part(pl, xl, cfg, lo, held // m),
                            "model").astype(xl.dtype)

    return shard_part({n: p[n] for n in names}, x)


def _moe_held_part(p: dict, x: jax.Array, cfg: ModelConfig, first,
                   held: int) -> jax.Array:
    """The dropless layer's sum over experts ``first .. first+held-1``, whose
    weights ``p`` holds, in float32.

    The (token, choice) pairs routed to these experts are sorted by expert
    and run through ``jax.lax.ragged_dot`` in one pass of the most pairs
    they can get, T · min(k, held) rows; the rows past the routed ones are
    masked out of the combine. The pass is fixed: one sized for the
    average load, with a branch to this one for a batch that overflows it,
    did a seventh of the work at Granite's share but made run times vary
    with each batch's routing (PERF.md §6).
    """
    b, s, d = x.shape
    k = cfg.moe_top_k
    t = b * s
    rows = t * min(k, held)
    xt = x.reshape(t, d)
    logits = jnp.dot(xt.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)          # (T,E)
    top, idx = jax.lax.top_k(logits, k)                            # (T,k)
    gates = jax.nn.softmax(top, axis=-1).reshape(-1)               # (T·k,)
    local = idx.reshape(-1) - first
    # groups 0..held-1 are the held experts; group held, those elsewhere
    group = jnp.where((local >= 0) & (local < held), local, held)
    pair = jnp.argsort(group, stable=True)[:rows]                  # by expert
    sizes = jnp.bincount(group, length=held + 1)[:held]
    tok = pair // k
    xs = xt[tok]                                                   # (rows,d)
    h = jax.lax.ragged_dot(xs, p["wi"].astype(x.dtype), sizes)
    if "wg" in p:
        g = jax.lax.ragged_dot(xs, p["wg"].astype(x.dtype), sizes)
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    y = jax.lax.ragged_dot(h, p["wo"].astype(x.dtype), sizes)      # (rows,d)
    routed = (jnp.arange(rows) < sizes.sum())[:, None]
    y = jnp.where(routed, y.astype(jnp.float32) * gates[pair][:, None], 0.0)
    return jnp.zeros((t, d), jnp.float32).at[tok].add(y).reshape(b, s, d)


def moe_aux_loss(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Load-balancing auxiliary loss (Switch §2.2)."""
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    logits = (xt @ p["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    _, idx = jax.lax.top_k(probs, cfg.moe_top_k)
    frac = jax.nn.one_hot(idx, cfg.moe_experts).mean(axis=(0, 1))
    imp = probs.mean(0)
    return cfg.moe_experts * jnp.sum(frac * imp)


# =========================== Mamba2 / SSD layer ==============================
def init_ssm(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    d_in = cfg.ssm_expand * d
    n = cfg.ssm_state
    h = d_in // cfg.ssm_head_dim
    conv_ch = d_in + 2 * n
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "in_proj": _dense_init(k1, d, (d, 2 * d_in + 2 * n + h)),
        "conv_w": _dense_init(k2, cfg.ssm_conv, (cfg.ssm_conv, conv_ch)),
        "A_log": jnp.zeros((h,), jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "norm_w": jnp.ones((d_in,), jnp.float32),
        "out_proj": _dense_init(k3, d_in, (d_in, d)),
    }
    if cfg.ssm_conv_bias:
        p["conv_b"] = _dense_init(jax.random.fold_in(key, 3), cfg.ssm_conv,
                                  (conv_ch,))
    return p


def _ssd_chunk_scan(xs, dt, Bm, Cm, A_log, chunk: int = 128):
    """SSD chunked algorithm (Mamba2 [arXiv:2405.21060] listing 1, jnp ref).

    xs: (B,S,H,P)  dt: (B,S,H)  Bm/Cm: (B,S,N)  A_log: (H,)
    Returns y: (B,S,H,P) and final state (B,H,P,N).
    """
    b, s, h, p = xs.shape
    n = Bm.shape[-1]
    nc = s // chunk
    A = -jnp.exp(A_log)                                   # (H,)
    dA = dt * A                                           # (B,S,H)

    xs = xs.reshape(b, nc, chunk, h, p)
    dt_c = dt.reshape(b, nc, chunk, h)
    dA_c = dA.reshape(b, nc, chunk, h)
    Bc = Bm.reshape(b, nc, chunk, n)
    Cc = Cm.reshape(b, nc, chunk, n)

    # cumulative decay within chunk
    csum = jnp.cumsum(dA_c, axis=2)                       # (B,nc,Q,H)
    # intra-chunk: L[i,j] = exp(csum_i - csum_j) for i >= j
    diff = csum[:, :, :, None, :] - csum[:, :, None, :, :]   # (B,nc,Q,Q,H)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    L = jnp.where(mask[None, None, :, :, None], jnp.exp(diff), 0.0)
    cb = jnp.einsum("bcqn,bckn->bcqk", Cc, Bc)            # (B,nc,Q,Q)
    xdt = xs * dt_c[..., None]                            # (B,nc,Q,H,P)
    y_intra = jnp.einsum("bcqk,bcqkh,bckhp->bcqhp",
                         cb, L.transpose(0, 1, 2, 3, 4), xdt)

    # chunk states: S_c = sum_j exp(csum_last - csum_j) B_j x_j dt_j
    decay_out = jnp.exp(csum[:, :, -1:, :] - csum)        # (B,nc,Q,H)
    states = jnp.einsum("bckn,bckh,bckhp->bchnp",
                        Bc, decay_out, xdt)               # (B,nc,H,N,P)

    # inter-chunk recurrence
    chunk_decay = jnp.exp(csum[:, :, -1, :])              # (B,nc,H)

    def step(hstate, inp):
        st, dec = inp                                     # (B,H,N,P), (B,H)
        out = hstate
        hstate = hstate * dec[..., None, None] + st
        return hstate, out

    h0 = jnp.zeros((b, h, n, p), xs.dtype)
    hfinal, h_prev = jax.lax.scan(
        step, h0, (states.transpose(1, 0, 2, 3, 4),
                   chunk_decay.transpose(1, 0, 2)))
    h_prev = h_prev.transpose(1, 0, 2, 3, 4)              # (B,nc,H,N,P)

    decay_in = jnp.exp(csum)                              # (B,nc,Q,H)
    y_inter = jnp.einsum("bcqn,bcqh,bchnp->bcqhp", Cc, decay_in, h_prev)
    y = (y_intra + y_inter).reshape(b, s, h, p)
    return y, hfinal.transpose(0, 1, 3, 2)                # state (B,H,P,N)


def ssm_layer(p: dict, x: jax.Array, cfg: ModelConfig,
              chunk: int = 128, with_state: bool = False):
    """Mamba2 block forward (training/prefill). With ``with_state`` it
    returns ``(y, final SSM state (B,H,P,N), conv tail (B,K-1,C))``: what
    a decode step continues from."""
    b, s, d = x.shape
    d_in = cfg.ssm_expand * d
    n = cfg.ssm_state
    h = d_in // cfg.ssm_head_dim
    zxbcdt = _mm(x, p["in_proj"], cfg)
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * n], axis=-1)
    conv_tail = xbc[:, -(cfg.ssm_conv - 1):, :]
    # causal depthwise conv over [x;B;C]
    xbc = _causal_conv(xbc, p["conv_w"].astype(x.dtype), p.get("conv_b"))
    xbc = jax.nn.silu(xbc)
    xs, Bm, Cm = jnp.split(xbc, [d_in, d_in + n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    xs = xs.reshape(b, s, h, cfg.ssm_head_dim)
    xs = shard(xs, "batch", "seq", "heads", None)
    y, state = _ssd_chunk_scan(xs.astype(jnp.float32), dt,
                               Bm.astype(jnp.float32), Cm.astype(jnp.float32),
                               p["A_log"].astype(jnp.float32),
                               chunk=min(chunk, s))
    y = y + p["D"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(b, s, d_in).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z), p["norm_w"], cfg.rms_eps)  # gated norm
    y = shard(_mm(y, p["out_proj"], cfg), "batch", "seq", None)
    return (y, state, conv_tail) if with_state else y


def _causal_conv(x: jax.Array, w: jax.Array,
                 bias: jax.Array | None = None) -> jax.Array:
    """Depthwise causal 1-D conv. x: (B,S,C); w: (K,C); bias: (C,)."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(k):
        out = out + xp[:, i:i + x.shape[1], :] * w[i]
    if bias is not None:
        out = out + bias.astype(x.dtype)
    return out


def ssm_decode_step(p: dict, x: jax.Array, state: jax.Array,
                    conv_cache: jax.Array, cfg: ModelConfig):
    """One-token SSD recurrence. x: (B,1,d); state: (B,H,P,N);
    conv_cache: (B, K-1, conv_ch). Returns (y, state, conv_cache)."""
    b, _, d = x.shape
    d_in = cfg.ssm_expand * d
    n = cfg.ssm_state
    h = d_in // cfg.ssm_head_dim
    P = cfg.ssm_head_dim
    zxbcdt = _mm(x, p["in_proj"], cfg)
    z, xbc, dt = jnp.split(zxbcdt[:, 0], [d_in, 2 * d_in + 2 * n], axis=-1)
    w = p["conv_w"].astype(x.dtype)                       # (K, C)
    window = jnp.concatenate([conv_cache, xbc[:, None, :]], axis=1)  # (B,K,C)
    xbc_c = jnp.einsum("bkc,kc->bc", window, w)
    if "conv_b" in p:
        xbc_c = xbc_c + p["conv_b"].astype(x.dtype)
    conv_cache = window[:, 1:]
    xbc_c = jax.nn.silu(xbc_c)
    xs, Bm, Cm = jnp.split(xbc_c, [d_in, d_in + n], axis=-1)
    dtf = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # (B,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))          # (H,)
    dA = jnp.exp(dtf * A)                                 # (B,H)
    xs = xs.reshape(b, h, P).astype(jnp.float32)
    state = (state * dA[..., None, None]
             + jnp.einsum("bhp,bn,bh->bhpn", xs, Bm.astype(jnp.float32), dtf))
    y = jnp.einsum("bhpn,bn->bhp", state, Cm.astype(jnp.float32))
    y = y + p["D"][None, :, None] * xs
    y = y.reshape(b, d_in).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z), p["norm_w"], cfg.rms_eps)
    return (y @ p["out_proj"].astype(x.dtype))[:, None, :], state, conv_cache

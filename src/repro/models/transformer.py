"""Model assembly: embedding → scanned heterogeneous blocks → head.

Layers are grouped into repeating *blocks* (cfg.block_size) so heterogeneous
patterns (Jamba 7:1 mamba:attn, Llama-Vision cross-attn every 5th) scan as
stacked identical pytrees — one block body in the HLO regardless of depth,
which keeps 94-layer × 512-device dry-run compiles tractable.

Public entry points (all pure):
  init_params(cfg, key)
  forward(cfg, params, tokens, ...)                  -> logits
  loss_fn(cfg, params, batch)                        -> scalar loss
  prefill(cfg, params, tokens, ...)                  -> logits, Cache
  decode_step(cfg, params, cache, token, pos, ...)   -> logits, Cache
  init_cache(cfg, batch, max_len)
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from ..parallel.logical import shard
from . import layers as L
from .config import ModelConfig

# remat policies for the block scan (cfg.remat selects; §Perf hillclimb):
#   full — save nothing, recompute the whole block in backward (min memory)
#   dots — save matmul outputs, recompute only cheap elementwise/norm work
#   none — no rematerialization (max memory, no recompute)
REMAT_POLICIES = {
    "full": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
}


# ================================ init =======================================
def _init_layer(key, cfg: ModelConfig, idx: int, cross_ok: bool) -> dict:
    norm_init, _ = L.make_norm(cfg)
    keys = jax.random.split(key, 8)
    p: dict = {"ln1": norm_init(keys[0], cfg.d_model)}
    if cfg.layer_kind(idx) == "attn":
        p["attn"] = L.init_attention(keys[1], cfg)
        if cross_ok and cfg.layer_is_cross(idx):
            p["lnx"] = norm_init(keys[2], cfg.d_model)
            p["xattn"] = L.init_attention(keys[3], cfg, cross=True)
    else:
        p["ssm"] = L.init_ssm(keys[1], cfg)
    if cfg.d_ff:
        p["ln2"] = norm_init(keys[4], cfg.d_model)
        if cfg.layer_is_moe(idx):
            p["moe"] = L.init_moe(keys[5], cfg)
            if cfg.moe_shared_ff:
                p["shared"] = L.init_mlp(
                    keys[6], dataclasses.replace(cfg, d_ff=cfg.moe_shared_ff))
        else:
            p["mlp"] = L.init_mlp(keys[5], cfg)
    return p


def _init_block(key, cfg: ModelConfig, cross_ok: bool = True) -> dict:
    keys = jax.random.split(key, cfg.block_size)
    return {f"l{i}": _init_layer(keys[i], cfg, i, cross_ok)
            for i in range(cfg.block_size)}


def _init_encoder_layer(key, cfg: ModelConfig) -> dict:
    norm_init, _ = L.make_norm(cfg)
    keys = jax.random.split(key, 4)
    return {"ln1": norm_init(keys[0], cfg.d_model),
            "attn": L.init_attention(keys[1], cfg),
            "ln2": norm_init(keys[2], cfg.d_model),
            "mlp": L.init_mlp(keys[3], cfg)}


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    k_embed, k_blocks, k_enc, k_head, k_fn = jax.random.split(key, 5)
    norm_init, _ = L.make_norm(cfg)
    params: dict = {
        "embed": L._dense_init(k_embed, cfg.d_model,
                               (cfg.vocab, cfg.d_model)),
        "final_norm": norm_init(k_fn, cfg.d_model),
        "stack": jax.vmap(lambda k: _init_block(k, cfg))(
            jax.random.split(k_blocks, cfg.n_blocks)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L._dense_init(k_head, cfg.d_model,
                                          (cfg.d_model, cfg.vocab))
    if cfg.is_enc_dec:
        params["enc_stack"] = jax.vmap(
            lambda k: _init_encoder_layer(k, cfg))(
                jax.random.split(k_enc, cfg.encoder_layers))
        params["enc_final_norm"] = norm_init(k_fn, cfg.d_model)
    if cfg.param_dtype == "bfloat16":
        # mixed precision: live params in bf16, fp32 master in the optimizer
        params = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if x.dtype == jnp.float32 else x, params)
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ============================== block bodies =================================
def _residual(cfg: ModelConfig, x: jax.Array, y: jax.Array) -> jax.Array:
    """``x`` plus the branch ``y`` times ``cfg.residual_mult``."""
    return x + (y if cfg.residual_mult == 1.0 else y * cfg.residual_mult)


def _ffn(cfg: ModelConfig, lp: dict, x: jax.Array, dropless: bool):
    """A layer's pre-norm feed-forward and its residual: the MLP, or the
    routed experts (the dropless layer when ``dropless``, else the
    capacity-bounded one) plus any shared expert."""
    _, norm = L.make_norm(cfg)
    if "mlp" in lp:
        with jax.named_scope("mlp"):
            return _residual(cfg, x, L.mlp(lp["mlp"], norm(lp["ln2"], x), cfg))
    with jax.named_scope("moe"):
        h = norm(lp["ln2"], x)
        y = (L.moe_dropless(lp["moe"], h, cfg) if dropless
             else L.moe(lp["moe"], h, cfg))
    if "shared" in lp:
        with jax.named_scope("moe_shared"):
            y = y + L.mlp(lp["shared"], h, cfg)
    return _residual(cfg, x, y)


def _block_fwd(cfg: ModelConfig, bp: dict, x: jax.Array,
               positions: jax.Array, memory: jax.Array | None,
               dropless: bool = False) -> jax.Array:
    _, norm = L.make_norm(cfg)
    for i in range(cfg.block_size):
        lp = bp[f"l{i}"]
        if cfg.layer_kind(i) == "attn":
            with jax.named_scope("attention"):
                x = _residual(cfg, x, L.self_attention(
                    lp["attn"], norm(lp["ln1"], x), cfg, positions))
            if cfg.layer_is_cross(i) and memory is not None:
                x = x + L.cross_attention(lp["xattn"], norm(lp["lnx"], x),
                                          memory, cfg)
        else:
            with jax.named_scope("ssm"):
                x = _residual(cfg, x, L.ssm_layer(lp["ssm"],
                                                  norm(lp["ln1"], x), cfg))
        if cfg.d_ff:
            x = _ffn(cfg, lp, x, dropless)
        x = shard(x, "batch", "seq", None)
    return x


def _encoder_fwd(cfg: ModelConfig, ep: dict, x: jax.Array,
                 positions: jax.Array) -> jax.Array:
    _, norm = L.make_norm(cfg)
    x = x + L.self_attention(ep["attn"], norm(ep["ln1"], x), cfg, positions,
                             causal=False)
    x = x + L.mlp(ep["mlp"], norm(ep["ln2"], x), cfg)
    return shard(x, "batch", "seq", None)


def _scan_stack(body, x: jax.Array, stack, remat: bool = True,
                policy: str = "full"):
    if remat and policy != "none":
        fn = jax.checkpoint(body, policy=REMAT_POLICIES[policy])
    else:
        fn = body

    def step(carry, bp):
        return fn(bp, carry), None

    out, _ = jax.lax.scan(step, x, stack)
    return out


# ================================ forward ====================================
def encode(cfg: ModelConfig, params: dict, frames: jax.Array) -> jax.Array:
    """Run the encoder over (precomputed) frontend embeddings (B, T, d)."""
    pos = jnp.arange(frames.shape[1])
    x = shard(frames, "batch", "seq", None)
    x = _scan_stack(lambda ep, h: _encoder_fwd(cfg, ep, h, pos),
                    x, params["enc_stack"], policy=cfg.remat)
    _, norm = L.make_norm(cfg)
    return norm(params["enc_final_norm"], x)


def _embed(cfg: ModelConfig, params: dict, tokens: jax.Array) -> jax.Array:
    compute_dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    x = params["embed"][tokens].astype(compute_dtype)
    return x if cfg.embed_mult == 1.0 else x * cfg.embed_mult


def _lm_head(cfg: ModelConfig, params: dict, dtype) -> jax.Array:
    return (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(dtype)


def _scale_logits(cfg: ModelConfig, logits: jax.Array) -> jax.Array:
    return logits if cfg.logits_div == 1.0 else logits / cfg.logits_div


def forward(cfg: ModelConfig, params: dict, tokens: jax.Array,
            memory: jax.Array | None = None,
            remat: bool = True, dropless: bool = False) -> jax.Array:
    """Decoder forward. tokens: (B, S) int32; memory: (B, M, d) for
    VLM image embeddings or encoder output. Returns logits (B, S, V).
    ``dropless`` runs expert layers through the dropless layer (serving)
    and not the capacity-bounded one (training)."""
    compute_dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    x = shard(_embed(cfg, params, tokens), "batch", "seq", None)
    pos = jnp.arange(tokens.shape[1])
    if memory is not None:
        memory = memory.astype(compute_dtype)
    x = _scan_stack(
        lambda bp, h: _block_fwd(cfg, bp, h, pos, memory, dropless),
        x, params["stack"], remat=remat, policy=cfg.remat)
    _, norm = L.make_norm(cfg)
    with jax.named_scope("lm_head"):
        x = norm(params["final_norm"], x)
        head = _lm_head(cfg, params, compute_dtype)
        logits = shard(_scale_logits(cfg, x @ head), "batch", "seq", "vocab")
    return logits


def loss_fn(cfg: ModelConfig, params: dict, batch: dict) -> jax.Array:
    """Mean next-token cross-entropy (+ router aux loss hooks in trainer)."""
    memory = _memory_from_batch(cfg, params, batch)
    logits = forward(cfg, params, batch["tokens"], memory=memory)
    labels = batch["labels"]
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("mask", jnp.ones_like(labels, jnp.float32))
    nll = (logz - gold) * mask
    return nll.sum() / jnp.clip(mask.sum(), 1.0)


def _memory_from_batch(cfg: ModelConfig, params: dict, batch: dict):
    if cfg.family == "vlm":
        return batch["image_embeds"]
    if cfg.is_enc_dec:
        return encode(cfg, params, batch["audio_frames"])
    return None


# ============================= KV / state cache ==============================
@dataclasses.dataclass
class CacheSpec:
    n_attn: int          # attention layers per block
    n_ssm: int           # ssm layers per block
    attn_slots: list     # layer idx within block -> cache slot (or -1)
    ssm_slots: list


def cache_spec(cfg: ModelConfig) -> CacheSpec:
    a, s, aslot, sslot = 0, 0, [], []
    for i in range(cfg.block_size):
        if cfg.layer_kind(i) == "attn":
            aslot.append(a); sslot.append(-1); a += 1
        else:
            aslot.append(-1); sslot.append(s); s += 1
    return CacheSpec(a, s, aslot, sslot)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None) -> dict:
    """Zeroed decode state: K/V per attention layer and the conv window per
    SSM layer in ``dtype`` (default: the compute dtype), SSM states in
    float32."""
    if dtype is None:
        dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    spec = cache_spec(cfg)
    nb = cfg.n_blocks
    cache: dict = {}
    if spec.n_attn:
        cache["k"] = jnp.zeros((nb, spec.n_attn, batch, max_len,
                                cfg.n_kv_heads, cfg.hd), dtype)
        cache["v"] = jnp.zeros_like(cache["k"])
    if spec.n_ssm:
        d_in = cfg.ssm_expand * cfg.d_model
        h = d_in // cfg.ssm_head_dim
        conv_ch = d_in + 2 * cfg.ssm_state
        cache["ssm"] = jnp.zeros((nb, spec.n_ssm, batch, h,
                                  cfg.ssm_head_dim, cfg.ssm_state),
                                 jnp.float32)
        cache["conv"] = jnp.zeros((nb, spec.n_ssm, batch, cfg.ssm_conv - 1,
                                   conv_ch), dtype)
    return cache


def _block_decode(cfg: ModelConfig, bp: dict, cache: dict, blk: jax.Array,
                  x: jax.Array, pos: jax.Array, memory: jax.Array | None):
    """One block of a decode step on the whole stacked ``cache``: each
    layer writes its new state into its ``[blk, slot]`` entry in place."""
    _, norm = L.make_norm(cfg)
    spec = cache_spec(cfg)
    cache = dict(cache)
    for i in range(cfg.block_size):
        lp = bp[f"l{i}"]
        if cfg.layer_kind(i) == "attn":
            with jax.named_scope("attention"):
                h, cache["k"], cache["v"] = L.decode_self_attention(
                    lp["attn"], norm(lp["ln1"], x), cache["k"], cache["v"],
                    (blk, spec.attn_slots[i]), pos, cfg)
                x = _residual(cfg, x, h)
            if cfg.layer_is_cross(i) and memory is not None:
                x = x + L.cross_attention(lp["xattn"], norm(lp["lnx"], x),
                                          memory, cfg)
        else:
            at = (blk, spec.ssm_slots[i])
            with jax.named_scope("ssm_state"):
                st, cc = L._at(cache["ssm"], at), L._at(cache["conv"], at)
            with jax.named_scope("ssm"):
                h, st, cc = L.ssm_decode_step(lp["ssm"], norm(lp["ln1"], x),
                                              st, cc, cfg)
                x = _residual(cfg, x, h)
            with jax.named_scope("ssm_state"):
                cache["ssm"] = L._write_at(cache["ssm"], at, st)
                cache["conv"] = L._write_at(cache["conv"], at, cc)
        if cfg.d_ff:
            x = _ffn(cfg, lp, x, dropless=True)
    return x, cache


def decode_step(cfg: ModelConfig, params: dict, cache: dict,
                token: jax.Array, pos: jax.Array,
                memory: jax.Array | None = None):
    """One autoregressive step. token: (B,) int32; pos: scalar int32.

    The layer scan carries the whole cache: each layer writes its new K/V
    row (or SSM state) into it in place and attention reads its entry
    there, so nothing copies a layer's cache out or stacks one back. A
    caller that donates ``cache`` gets the update without a copy.

    Returns (logits (B, V), updated cache)."""
    compute_dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    x = params["embed"][token][:, None, :].astype(compute_dtype)  # (B,1,d)
    if cfg.embed_mult != 1.0:
        x = x * cfg.embed_mult
    if memory is not None:
        memory = memory.astype(compute_dtype)

    def step(carry, bp):
        y, c, blk = carry
        y, c = _block_decode(cfg, bp, c, blk, y, pos, memory)
        return (y, c, blk + 1), None

    # Weights stored wider than the compute dtype (f32 params, bf16
    # compute) unroll the layer loop, so that each matmul reads its layer's
    # slice and casts it inside its own fusion: in a loop, XLA hoists the
    # cast of each whole weight stack out of it, and every step writes and
    # reads back a compute-dtype copy of the weights.
    wide = any(w.dtype.itemsize > jnp.dtype(compute_dtype).itemsize
               for w in jax.tree.leaves(params["stack"]))
    # the scan's own ops (each layer's weights sliced from the stack) are
    # named layers; the cache row writes and reads inside are kv_cache (and
    # ssm_state)
    with jax.named_scope("layers"):
        (x, new_cache, _), _ = jax.lax.scan(
            step, (x, cache, jnp.int32(0)), params["stack"], unroll=wide)
    _, norm = L.make_norm(cfg)
    with jax.named_scope("lm_head"):
        x = norm(params["final_norm"], x)
        head = _lm_head(cfg, params, compute_dtype)
        logits = shard(_scale_logits(cfg, x[:, 0, :] @ head), "batch",
                       "vocab")
    return logits, new_cache


def prefill(cfg: ModelConfig, params: dict, tokens: jax.Array,
            memory: jax.Array | None = None):
    """Prefill pass: logits for the prompt + a cache filled up to S.

    The cache is produced by replaying K/V projections per block — traffic-
    equivalent to fused prefill for the dry-run's purposes, and exactly
    correct w.r.t. decode_step (tested).
    """
    logits = forward(cfg, params, tokens, memory=memory, dropless=True)
    with jax.named_scope("fill_cache"):
        cache = init_cache(cfg, tokens.shape[0], tokens.shape[1])
        cache = _fill_cache(cfg, params, tokens, cache, memory)
    return logits, cache


def _fill_cache(cfg: ModelConfig, params: dict, tokens: jax.Array,
                cache: dict, memory: jax.Array | None):
    """Recompute per-layer inputs and write K/V + SSM states into the cache."""
    compute_dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    x = _embed(cfg, params, tokens)
    if memory is not None:
        memory = memory.astype(compute_dtype)
    pos = jnp.arange(tokens.shape[1])
    _, norm = L.make_norm(cfg)
    spec = cache_spec(cfg)

    def step(carry, inp):
        h = carry
        bp, bc = inp
        nc = dict(bc)
        for i in range(cfg.block_size):
            lp = bp[f"l{i}"]
            if cfg.layer_kind(i) == "attn":
                slot = spec.attn_slots[i]
                xin = norm(lp["ln1"], h)
                b, s, _ = xin.shape
                k = (xin @ lp["attn"]["wk"].astype(xin.dtype)).reshape(
                    b, s, cfg.n_kv_heads, cfg.hd)
                v = (xin @ lp["attn"]["wv"].astype(xin.dtype)).reshape(
                    b, s, cfg.n_kv_heads, cfg.hd)
                if cfg.pos_emb == "rope":
                    k = L.apply_rope(k, pos, cfg.rope_theta)
                nc["k"] = nc["k"].at[slot, :, :s].set(k.astype(nc["k"].dtype))
                nc["v"] = nc["v"].at[slot, :, :s].set(v.astype(nc["v"].dtype))
                h = _residual(cfg, h, L.self_attention(lp["attn"], xin, cfg,
                                                       pos))
                if cfg.layer_is_cross(i) and memory is not None:
                    h = h + L.cross_attention(lp["xattn"], norm(lp["lnx"], h),
                                              memory, cfg)
            else:
                slot = spec.ssm_slots[i]
                with jax.named_scope("ssm"):
                    y, st, conv_tail = L.ssm_layer(
                        lp["ssm"], norm(lp["ln1"], h), cfg, with_state=True)
                    h = _residual(cfg, h, y)
                nc["ssm"] = nc["ssm"].at[slot].set(st)
                nc["conv"] = nc["conv"].at[slot].set(
                    conv_tail.astype(nc["conv"].dtype))
            if cfg.d_ff:
                h = _ffn(cfg, lp, h, dropless=True)
        return h, nc

    _, new_cache = jax.lax.scan(step, x, (params["stack"], cache))
    return new_cache

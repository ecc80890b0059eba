"""Plain float32 reference of the Granite-4.0-H block, on the program's
parameter pytree (``init_params``), for the CPU tests.

The block, as the published ``granitemoehybrid`` model writes it::

    x = embed[t] * embed_mult
    per layer:
      h = x + residual_mult * mixer(rmsnorm(x))
      x = h + residual_mult * (routed(rmsnorm(h)) + shared(rmsnorm(h)))
    logits = (rmsnorm(x) @ embed.T) / logits_div

``mixer`` is Mamba-2 (conv with bias, the selective scan as the plain
per-token recurrence, gated RMSNorm over d_in) or causal GQA attention
with no position embedding and softmax scale ``attn_scale``.
``routed`` takes the top-k router logits over every expert and a softmax
over those k, and sums each held expert the token chose times its gate, as
a dense loop over the held experts. No cache, no batching tricks, every
matmul at ``precision="highest"``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _swiglu(p, x):
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])) @ p["wo"]


def attention(cfg: ModelConfig, p: dict, x):
    assert cfg.pos_emb == "nope", "the reference has no rotary embedding"
    b, s, _ = x.shape
    hd, h, kvh = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    q = (x @ p["wq"]).reshape(b, s, h, hd)
    k = (x @ p["wk"]).reshape(b, s, kvh, hd)
    v = (x @ p["wv"]).reshape(b, s, kvh, hd)
    k = jnp.repeat(k, h // kvh, axis=2)
    v = jnp.repeat(v, h // kvh, axis=2)
    scale = cfg.attn_scale or 1.0 / math.sqrt(hd)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
    return o.reshape(b, s, h * hd) @ p["wo"]


def mamba(cfg: ModelConfig, p: dict, x):
    b, s, d = x.shape
    d_in, n, hp = cfg.ssm_expand * d, cfg.ssm_state, cfg.ssm_head_dim
    nh, kw = d_in // hp, cfg.ssm_conv
    z, xbc, dt = jnp.split(x @ p["in_proj"], [d_in, 2 * d_in + 2 * n], -1)
    pad = jnp.pad(xbc, ((0, 0), (kw - 1, 0), (0, 0)))
    xbc = sum(pad[:, j:j + s] * p["conv_w"][j] for j in range(kw))
    xbc = jax.nn.silu(xbc + p.get("conv_b", 0.0))
    xs, bm, cm = jnp.split(xbc, [d_in, d_in + n], -1)
    xs = xs.reshape(b, s, nh, hp)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    state = jnp.zeros((b, nh, hp, n))
    ys = []
    for t in range(s):
        state = (state * jnp.exp(dt[:, t] * a)[..., None, None]
                 + (dt[:, t, :, None] * xs[:, t])[..., None]
                 * bm[:, t, None, None])
        ys.append(jnp.einsum("bhpn,bn->bhp", state, cm[:, t]))
    y = jnp.stack(ys, 1) + p["D"][:, None] * xs
    y = y.reshape(b, s, d_in) * jax.nn.silu(z)
    return _rms(y, p["norm_w"], cfg.rms_eps) @ p["out_proj"]


def routed(cfg: ModelConfig, p: dict, x):
    """The held experts' part of the expert layer: a dense loop."""
    first, held = cfg.experts_held
    top, idx = jax.lax.top_k(x @ p["router"], cfg.moe_top_k)
    gates = jax.nn.softmax(top, -1)
    y = jnp.zeros_like(x)
    for j in range(held):
        gate = (gates * (idx == first + j)).sum(-1)
        y = y + gate[..., None] * _swiglu(
            {n: p[n][j] for n in ("wi", "wg", "wo")}, x)
    return y


def forward(cfg: ModelConfig, params: dict, tokens) -> jax.Array:
    """Logits (B, S, V) of ``tokens`` (B, S), in float32."""
    with jax.default_matmul_precision("highest"):
        f32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        x = f32["embed"][tokens] * cfg.embed_mult
        r = cfg.residual_mult
        for blk in range(cfg.n_blocks):
            for i in range(cfg.block_size):
                lp = jax.tree.map(lambda a: a[blk], f32["stack"][f"l{i}"])
                h = _rms(x, lp["ln1"]["w"], cfg.rms_eps)
                mix = (attention(cfg, lp["attn"], h)
                       if cfg.layer_kind(i) == "attn"
                       else mamba(cfg, lp["ssm"], h))
                x = x + r * mix
                h = _rms(x, lp["ln2"]["w"], cfg.rms_eps)
                x = x + r * (routed(cfg, lp["moe"], h)
                             + _swiglu(lp["shared"], h))
        x = _rms(x, f32["final_norm"]["w"], cfg.rms_eps)
        return (x @ f32["embed"].T) / cfg.logits_div

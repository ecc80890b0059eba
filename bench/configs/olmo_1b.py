"""olmo_1b: weights from the seed, the plain reference, and the counts of
operations and bytes that each step needs.

Everything here is written from the configuration in ``olmo_1b.json`` and
imports nothing of the program. The weights are made in the benchmark's own
layout (stacked per layer); :func:`program_params` only re-labels the same
arrays into the pytree that the program's ``ServeEngine`` takes.

The reference is the decoder in straightforward ``jax.numpy``: float32,
every matmul at ``precision="highest"``, one layer at a time so that it
fits beside nothing else on one chip. ``quant="fp8"`` computes the same
forward with every matmul operand rounded to float8 (e4m3, one scale per
tensor): the control that a sound comparison has to fail.
"""
from __future__ import annotations

import json
import math
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

SPEC = json.loads((Path(__file__).with_suffix(".json")).read_text())

#: Weight names in the benchmark's layout, their shapes, and fan-in.
WEIGHTS = ("embed", "wq", "wk", "wv", "wo", "wi", "wg", "wf")


def shapes(spec: dict = SPEC) -> dict:
    L, d, f, v = spec["n_layers"], spec["d_model"], spec["d_ff"], spec["vocab"]
    hd = spec["n_heads"] * spec["head_dim"]
    kvd = spec["n_kv_heads"] * spec["head_dim"]
    return {"embed": ((v, d), d), "wq": ((L, d, hd), d),
            "wk": ((L, d, kvd), d), "wv": ((L, d, kvd), d),
            "wo": ((L, hd, d), hd), "wi": ((L, d, f), d),
            "wg": ((L, d, f), d), "wf": ((L, f, d), f)}


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number up to 2**63: both 32-bit halves count."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def make_weights(seed: int, spec: dict = SPEC) -> dict:
    """Every weight from ``seed`` in one jitted call on the device, in
    float32 (the configuration's ``param_dtype``): N(0, 1/fan_in)."""
    shp = shapes(spec)

    def build(key):
        keys = jax.random.split(key, len(WEIGHTS))
        return {n: jax.random.normal(k, shp[n][0], jnp.float32)
                * (1.0 / math.sqrt(shp[n][1]))
                for n, k in zip(WEIGHTS, keys)}

    return jax.jit(build)(seed_key(seed))


def program_params(w: dict) -> dict:
    """The same arrays in the program's pytree (one layer per scanned
    block, non-parametric norms hold nothing, tied head)."""
    return {"embed": w["embed"], "final_norm": {},
            "stack": {"l0": {"ln1": {}, "ln2": {},
                             "attn": {"wq": w["wq"], "wk": w["wk"],
                                      "wv": w["wv"], "wo": w["wo"]},
                             "mlp": {"wi": w["wi"], "wg": w["wg"],
                                     "wo": w["wf"]}}}}


def param_count(spec: dict = SPEC) -> int:
    return sum(int(np.prod(s)) for s, _ in shapes(spec).values())


# ------------------------------- reference -----------------------------------
def _fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with one scale per tensor, back to float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, quant, spec="...k,kn->...n"):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision="highest")


def _layernorm(x, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def _rope(x, theta):
    """Rotary embedding on (B, S, H, hd); the two halves of each head rotate
    as one complex pair."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer_body(x, ws, spec_items, quant):
    """One decoder layer: pre-norm attention and SwiGLU MLP, both
    residual."""
    wq, wk, wv, wo, wi, wg, wf = ws
    spec = dict(spec_items)
    b, s, _ = x.shape
    h, hd = spec["n_heads"], spec["head_dim"]
    kvh = spec["n_kv_heads"]
    a = _layernorm(x, spec["norm_eps"])
    q = _rope(_mm(a, wq, quant).reshape(b, s, h, hd), spec["rope_theta"])
    k = _rope(_mm(a, wk, quant).reshape(b, s, kvh, hd), spec["rope_theta"])
    v = _mm(a, wv, quant).reshape(b, s, kvh, hd)
    if kvh != h:
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
    sc = _mm(q, k, quant, "bqhd,bkhd->bhqk") / math.sqrt(hd)
    causal = np.tril(np.ones((s, s), bool))
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = _mm(p, v, quant, "bhqk,bkhd->bqhd").reshape(b, s, h * hd)
    x = x + _mm(o, wo, quant)
    a = _layernorm(x, spec["norm_eps"])
    h = jax.nn.silu(_mm(a, wg, quant)) * _mm(a, wi, quant)
    return x + _mm(h, wf, quant)


_layer = jax.jit(_layer_body, static_argnames=("spec_items", "quant"))


@partial(jax.jit, static_argnames=("spec_items", "quant"))
def _head(x, embed, rows, spec_items, quant):
    spec = dict(spec_items)
    xs = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    return _mm(_layernorm(xs, spec["norm_eps"]), embed, quant,
               "brd,vd->brv")


def _items(spec: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in spec.items()
                        if isinstance(v, (int, float, str))))


def reference_logits(w: dict, tokens: np.ndarray, rows: np.ndarray,
                     quant: str | None = None, spec: dict = SPEC,
                     block: int = 8) -> np.ndarray:
    """Logits (B, R, V) at positions ``rows`` (B, R) of ``tokens`` (B, S),
    in blocks of ``block`` sequences and one layer at a time."""
    items = _items(spec)
    out = []
    for i in range(0, len(tokens), block):
        tok = jnp.asarray(tokens[i:i + block], jnp.int32)
        x = w["embed"][tok]
        for l in range(spec["n_layers"]):
            x = _layer(x, tuple(w[n][l] for n in WEIGHTS[1:]),
                       spec_items=items, quant=quant)
        out.append(np.asarray(_head(x, w["embed"],
                                    jnp.asarray(rows[i:i + block], jnp.int32),
                                    spec_items=items, quant=quant)))
    return np.concatenate(out)


def served_gaps(ref: np.ndarray, served: np.ndarray) -> np.ndarray:
    """How far each served token's reference logit lies below the
    reference's best at its position: ``ref`` (B, R, V), ``served`` (B, R).
    A served token that is not in the vocabulary reads ``inf``."""
    ok = (served >= 0) & (served < ref.shape[-1])
    tok = np.where(ok, served, 0)
    got = np.take_along_axis(ref, tok[..., None], axis=-1)[..., 0]
    return np.where(ok, ref.max(-1) - got, np.inf)


# --------------------------------- counts ------------------------------------
def _layer_matmul_params(spec: dict) -> int:
    d, hd = spec["d_model"], spec["n_heads"] * spec["head_dim"]
    kvd = spec["n_kv_heads"] * spec["head_dim"]
    return d * hd * 2 + d * kvd * 2 + 3 * d * spec["d_ff"]


def prefill_flops(batch: int, seq: int, spec: dict = SPEC) -> float:
    """A prefill of ``batch`` prompts of ``seq`` tokens: every layer's
    matmuls at every position, causal attention (QK^T and PV over the
    positions at or before each query), the LM head at the last position
    only."""
    L, d = spec["n_layers"], spec["d_model"]
    hd = spec["n_heads"] * spec["head_dim"]
    per_seq = (2.0 * seq * L * _layer_matmul_params(spec)
               + L * 2.0 * 2.0 * hd * seq * (seq + 1) / 2
               + 2.0 * d * spec["vocab"])
    return batch * per_seq


def prefill_bytes(batch: int, seq: int, spec: dict = SPEC) -> float:
    """Params once at their dtype, the prompt's K/V written to the cache."""
    return (param_count(spec) * 4.0
            + batch * seq * kv_bytes_per_token(spec))


def kv_bytes_per_token(spec: dict = SPEC) -> float:
    return (2.0 * spec["n_layers"] * spec["n_kv_heads"] * spec["head_dim"]
            * 2.0)  # K and V, bfloat16


def decode_flops(batch: int, pos: int, spec: dict = SPEC) -> float:
    """One decode step writing position ``pos``: matmuls for one token,
    attention over positions 0..pos, the LM head."""
    L, d = spec["n_layers"], spec["d_model"]
    hd = spec["n_heads"] * spec["head_dim"]
    return batch * (2.0 * L * _layer_matmul_params(spec)
                    + L * 4.0 * hd * (pos + 1)
                    + 2.0 * d * spec["vocab"])


def decode_bytes(batch: int, pos: int, spec: dict = SPEC) -> float:
    """Params at their dtype, the cache read up to ``pos``, one token's K/V
    written."""
    kv = kv_bytes_per_token(spec)
    return param_count(spec) * 4.0 + batch * (kv * (pos + 1) + kv)

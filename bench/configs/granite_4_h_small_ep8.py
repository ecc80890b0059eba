"""granite_4_h_small_ep8: weights from the seed, the plain reference, and the
counts of operations and bytes that each step needs.

Everything here is written from ``granite_4_h_small_ep8.json`` and imports
nothing of the program. The model is one chip's share of Granite-4.0-H-Small
(the file's ``deployment``): the first ``n_layers`` of ``layer_types``, each
a token mixer (Mamba-2, or GQA attention with no position embedding) and an
expert layer, both pre-RMSNorm and residual::

    x = embed[t] * embedding_multiplier
    per layer:
      h = x + residual_multiplier * mixer(rmsnorm(x))
      x = h + residual_multiplier * (routed(rmsnorm(h)) + shared(rmsnorm(h)))
    logits = rmsnorm(x) @ embed.T / logits_scaling

``routed`` scores all ``num_local_experts``, takes the top
``num_experts_per_tok`` logits of each token and a softmax over them, and
sums, of the experts held here (``experts_held`` from
``experts_held_first``), each one the token chose times its gate: this
chip's part of the layer.

The weights are made in the benchmark's own layout (a dict per layer);
:func:`program_params` re-labels the same arrays into the pytree that the
program's ``ServeEngine`` takes.

The reference is straightforward ``jax.numpy``: float32, every matmul at
``precision="highest"``, the Mamba-2 scan as the plain per-token
recurrence, the held experts as a dense loop over every token, one layer at
a time on blocks of sequences. ``quant="fp8"`` rounds every matmul operand
to float8 (e4m3, one scale per tensor): the control that a sound comparison
has to fail.
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

#: The weights of a Mamba-2 mixer, named as the program names them.
MAMBA = ("in_proj", "conv_w", "conv_b", "A_log", "D", "dt_bias", "norm_w",
         "out_proj")


def kinds(spec: dict = SPEC) -> list:
    """'mamba' or 'attention' for each layer held here."""
    return list(spec["layer_types"][:spec["n_layers"]])


def _mamba_dims(spec: dict) -> tuple:
    d_in = spec["mamba_expand"] * spec["d_model"]
    n = spec["mamba_n_groups"] * spec["mamba_d_state"]
    return d_in, n, spec["mamba_n_heads"], d_in + 2 * n


def layer_shapes(kind: str, spec: dict = SPEC) -> dict:
    """Each weight of one layer: (shape, fan-in)."""
    d, f, fs = spec["d_model"], spec["d_ff"], spec["shared_intermediate_size"]
    e, held = spec["num_local_experts"], spec["experts_held"]
    out = {"ln1": ((d,), None), "ln2": ((d,), None),
           "router": ((d, e), d), "wi": ((held, d, f), d),
           "wg": ((held, d, f), d), "wo": ((held, f, d), f),
           "shared_wi": ((d, fs), d), "shared_wg": ((d, fs), d),
           "shared_wo": ((fs, d), fs)}
    if kind == "mamba":
        d_in, n, nh, conv = _mamba_dims(spec)
        k = spec["mamba_d_conv"]
        out.update({"in_proj": ((d, 2 * d_in + 2 * n + nh), d),
                    "conv_w": ((k, conv), k), "conv_b": ((conv,), k),
                    "A_log": ((nh,), None), "D": ((nh,), None),
                    "dt_bias": ((nh,), None), "norm_w": ((d_in,), None),
                    "out_proj": ((d_in, d), d_in)})
    else:
        q = spec["n_heads"] * spec["head_dim"]
        kv = spec["n_kv_heads"] * spec["head_dim"]
        out.update({"wq": ((d, q), d), "wk": ((d, kv), d),
                    "wv": ((d, kv), d), "attn_wo": ((q, d), q)})
    return out


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number up to 2**63: both 32-bit halves count."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _init(name: str, key, shape, fan_in):
    """One weight as the file's ``init_std`` says, in float32."""
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1
    z = jax.random.normal(key, shape, jnp.float32)
    if fan_in is None:                              # norms and D
        return 1.0 + 0.1 * z
    return z / math.sqrt(fan_in)


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _weight(key, name, shape, fan_in, dtype):
    return _init(name, key, shape, fan_in).astype(dtype)


@partial(jax.jit, static_argnums=(1, 2, 3))
def _layer_weights(key, kind, spec_items, dtype):
    """One layer's weights: one program per kind of layer, so that set-up
    compiles two and not one per layer."""
    shapes = layer_shapes(kind, dict(spec_items))
    keys = jax.random.split(key, len(shapes))
    return {n: _init(n, k, *shapes[n]).astype(dtype)
            for n, k in zip(sorted(shapes), keys)}


def make_weights(seed: int, spec: dict = SPEC) -> dict:
    """Every weight from ``seed``, on the device, stored in the file's
    ``param_dtype``."""
    dtype = spec["param_dtype"]
    d, v = spec["d_model"], spec["vocab"]
    ke, kf, kl = jax.random.split(seed_key(seed), 3)
    # the scaled embedding has an N(0, 1/d) table's rows, so that the tied
    # head does not make every token predict itself
    fan_in = d * spec["embedding_multiplier"] ** 2
    items = _items(spec)
    return {"embed": _weight(ke, "embed", (v, d), fan_in, dtype),
            "final_norm": _weight(kf, "final_norm", (d,), None, dtype),
            "layers": [_layer_weights(jax.random.fold_in(kl, i), k, items,
                                      dtype)
                       for i, k in enumerate(kinds(spec))]}


def program_params(w: dict) -> dict:
    """The same arrays in the program's pytree: one scanned block of
    ``n_layers`` layers (a leading block axis of 1 on every layer weight),
    tied head. ``w``'s buffers are donated to it."""
    def relabel(w):
        stack = {}
        for i, lw in enumerate(w["layers"]):
            one = {n: a[None] for n, a in lw.items()}
            lp = {"ln1": {"w": one["ln1"]}, "ln2": {"w": one["ln2"]},
                  "moe": {"router": one["router"], "wi": one["wi"],
                          "wg": one["wg"], "wo": one["wo"]},
                  "shared": {"wi": one["shared_wi"], "wg": one["shared_wg"],
                             "wo": one["shared_wo"]}}
            if "in_proj" in lw:
                lp["ssm"] = {n: one[n] for n in MAMBA}
            else:
                lp["attn"] = {"wq": one["wq"], "wk": one["wk"],
                              "wv": one["wv"], "wo": one["attn_wo"]}
            stack[f"l{i}"] = lp
        return {"embed": w["embed"], "final_norm": {"w": w["final_norm"]},
                "stack": stack}

    return jax.jit(relabel, donate_argnums=0)(w)


def param_count(spec: dict = SPEC) -> int:
    return (spec["vocab"] * spec["d_model"] + spec["d_model"]
            + sum(int(np.prod(s)) for k in kinds(spec)
                  for s, _ in layer_shapes(k, spec).values()))


# ------------------------------- reference -----------------------------------
def _fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with one scale per tensor, back to float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, quant, spec="...k,kn->...n"):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision="highest")


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _mamba(x, w, spec, quant):
    """Mamba-2 over (B, S, d): in-projection, causal depthwise conv with
    bias, SiLU, the selective scan as a per-token recurrence in float32,
    the skip D, the gated RMSNorm over d_in, out-projection."""
    b, s, _ = x.shape
    d_in, n, nh, conv = _mamba_dims(spec)
    p, k = spec["mamba_d_head"], spec["mamba_d_conv"]
    zxbcdt = _mm(x, w["in_proj"], quant)
    z, xbc, dt = jnp.split(zxbcdt, [d_in, d_in + conv], axis=-1)
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = sum(pad[:, j:j + s] * w["conv_w"][j] for j in range(k))
    xbc = jax.nn.silu(xbc + w["conv_b"])
    xs, bm, cm = jnp.split(xbc, [d_in, d_in + n], axis=-1)
    xs = xs.reshape(b, s, nh, p)
    dt = jax.nn.softplus(dt + w["dt_bias"])                 # (B, S, H)
    a = -jnp.exp(w["A_log"])                                # (H,)

    def step(state, inp):                                   # (B, H, P, N)
        x_t, b_t, c_t, dt_t = inp
        state = (state * jnp.exp(dt_t * a)[:, :, None, None]
                 + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, None, None])
        y = jnp.einsum("bhpn,bn->bhp", state, c_t, precision="highest")
        return state, y

    _, ys = jax.lax.scan(step, jnp.zeros((b, nh, p, n), jnp.float32),
                         (xs.swapaxes(0, 1), bm.swapaxes(0, 1),
                          cm.swapaxes(0, 1), dt.swapaxes(0, 1)))
    y = ys.swapaxes(0, 1) + w["D"][:, None] * xs            # (B, S, H, P)
    y = y.reshape(b, s, d_in) * jax.nn.silu(z)
    y = _rmsnorm(y, w["norm_w"], spec["rms_norm_eps"])
    return _mm(y, w["out_proj"], quant)


def _attention(x, w, spec, quant):
    """Causal GQA attention with no position embedding, softmax scale
    ``attention_multiplier``."""
    b, s, _ = x.shape
    h, kvh, hd = spec["n_heads"], spec["n_kv_heads"], spec["head_dim"]
    q = _mm(x, w["wq"], quant).reshape(b, s, h, hd)
    k = _mm(x, w["wk"], quant).reshape(b, s, kvh, hd)
    v = _mm(x, w["wv"], quant).reshape(b, s, kvh, hd)
    k = jnp.repeat(k, h // kvh, axis=2)
    v = jnp.repeat(v, h // kvh, axis=2)
    sc = _mm(q, k, quant, "bqhd,bkhd->bhqk") * spec["attention_multiplier"]
    causal = np.tril(np.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal[None, None], sc, -jnp.inf), axis=-1)
    o = _mm(p, v, quant, "bhqk,bkhd->bqhd").reshape(b, s, h * hd)
    return _mm(o, w["attn_wo"], quant)


def _swiglu(x, wi, wg, wo, quant):
    return _mm(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wi, quant), wo, quant)


def _experts(x, w, spec, quant):
    """This chip's part of the expert layer, and the shared expert."""
    k = spec["num_experts_per_tok"]
    logits = _mm(x, w["router"], quant)                     # (B, S, E)
    top, idx = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top, axis=-1)
    y = _swiglu(x, w["shared_wi"], w["shared_wg"], w["shared_wo"], quant)
    for j in range(spec["experts_held"]):
        gate = (gates * (idx == spec["experts_held_first"] + j)).sum(-1)
        y = y + gate[..., None] * _swiglu(x, w["wi"][j], w["wg"][j],
                                          w["wo"][j], quant)
    return y


def _layer_body(x, w, kind, spec_items, quant):
    spec = dict(spec_items)
    w = {n: a.astype(jnp.float32) for n, a in w.items()}
    eps, r = spec["rms_norm_eps"], spec["residual_multiplier"]
    mixer = _mamba if kind == "mamba" else _attention
    x = x + r * mixer(_rmsnorm(x, w["ln1"], eps), w, spec, quant)
    return x + r * _experts(_rmsnorm(x, w["ln2"], eps), w, spec, quant)


_layer = jax.jit(_layer_body, static_argnames=("kind", "spec_items", "quant"))


@partial(jax.jit, static_argnames=("spec_items", "quant"))
def _head(x, embed, final_norm, rows, spec_items, quant):
    spec = dict(spec_items)
    xs = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    xs = _rmsnorm(xs, final_norm.astype(jnp.float32), spec["rms_norm_eps"])
    return _mm(xs, embed.astype(jnp.float32), quant,
               "brd,vd->brv") / spec["logits_scaling"]


@partial(jax.jit, static_argnames=("mult",))
def _embed(embed, tok, mult):
    return embed[tok].astype(jnp.float32) * mult


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
        x = _embed(w["embed"], tok, float(spec["embedding_multiplier"]))
        for kind, lw in zip(kinds(spec), w["layers"]):
            x = _layer(x, lw, kind=kind, spec_items=items, quant=quant)
        out.append(np.asarray(_head(
            x, w["embed"], w["final_norm"],
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
def routed_rows_per_token(spec: dict = SPEC) -> float:
    """Expected (token, expert) rows that held experts compute per token:
    k of the E experts, ``experts_held`` of them here."""
    return (spec["num_experts_per_tok"] * spec["experts_held"]
            / spec["num_local_experts"])


def _expert_params(spec: dict) -> int:
    return 3 * spec["d_model"] * spec["d_ff"]


def _token_flops(spec: dict) -> float:
    """Matmul and scan FLOPs of one token through every layer, without
    attention's scores and the LM head: the Mamba-2 projections, conv and
    recurrence (decay, state update, readout), the attention projections,
    the router, the held experts' expected rows and the shared expert."""
    d, fs = spec["d_model"], spec["shared_intermediate_size"]
    d_in, n, nh, conv = _mamba_dims(spec)
    q = spec["n_heads"] * spec["head_dim"]
    kv = spec["n_kv_heads"] * spec["head_dim"]
    mamba = (2.0 * d * (2 * d_in + 2 * n + nh) + 2.0 * spec["mamba_d_conv"]
             * conv + 6.0 * d_in * n + 2.0 * d_in * d)
    attn = 2.0 * d * (q + 2 * kv) + 2.0 * q * d
    ffn = (2.0 * d * spec["num_local_experts"]
           + routed_rows_per_token(spec) * 2.0 * _expert_params(spec)
           + 2.0 * 3 * d * fs)
    return sum((mamba if k == "mamba" else attn) + ffn for k in kinds(spec))


def _n_attn(spec: dict) -> int:
    return kinds(spec).count("attention")


def prefill_flops(batch: int, seq: int, spec: dict = SPEC) -> float:
    """A prefill of ``batch`` prompts of ``seq`` tokens: every layer at
    every position, causal attention (QK^T and PV over the positions at or
    before each query), the LM head at the last position only."""
    q = spec["n_heads"] * spec["head_dim"]
    per_seq = (seq * _token_flops(spec)
               + _n_attn(spec) * 2.0 * 2.0 * q * seq * (seq + 1) / 2
               + 2.0 * spec["d_model"] * spec["vocab"])
    return batch * per_seq


def kv_bytes_per_token(spec: dict = SPEC) -> float:
    return (2.0 * _n_attn(spec) * spec["n_kv_heads"] * spec["head_dim"]
            * 2.0)  # K and V, bfloat16


def state_bytes_per_sequence(spec: dict = SPEC) -> float:
    """A sequence's Mamba-2 state (float32) and conv window (bfloat16)."""
    d_in, n, _, conv = _mamba_dims(spec)
    return kinds(spec).count("mamba") * (
        d_in * n * 4.0 + (spec["mamba_d_conv"] - 1) * conv * 2.0)


def prefill_bytes(batch: int, seq: int, spec: dict = SPEC) -> float:
    """Params once at bfloat16 (every held expert is touched), the
    prompt's K/V and each sequence's state written to the cache."""
    return (param_count(spec) * 2.0 + batch * seq * kv_bytes_per_token(spec)
            + batch * state_bytes_per_sequence(spec))


def decode_flops(batch: int, pos: int, spec: dict = SPEC) -> float:
    """One decode step writing position ``pos``: one token through every
    layer, attention over positions 0..pos, the LM head."""
    q = spec["n_heads"] * spec["head_dim"]
    return batch * (_token_flops(spec)
                    + _n_attn(spec) * 4.0 * q * (pos + 1)
                    + 2.0 * spec["d_model"] * spec["vocab"])


def decode_bytes(batch: int, pos: int, spec: dict = SPEC) -> float:
    """Params at bfloat16, each held expert by its chance of being chosen
    by one of the ``batch`` tokens, 1 - (1 - k/E)^batch; the Mamba-2 state
    and conv window read and written; the cache read up to ``pos`` and one
    token's K/V written."""
    experts = (len(kinds(spec)) * spec["experts_held"]
               * _expert_params(spec))
    touched = 1.0 - (1.0 - spec["num_experts_per_tok"]
                     / spec["num_local_experts"]) ** batch
    kv = kv_bytes_per_token(spec)
    return ((param_count(spec) - experts + touched * experts) * 2.0
            + batch * (2.0 * state_bytes_per_sequence(spec)
                       + kv * (pos + 1) + kv))

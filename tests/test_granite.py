"""Granite-4.0-H (Mamba-2 + NoPE GQA + routed and shared experts) against
its plain float32 reference (``granite_reference.py``), at the SMOKE size
on the CPU, on seeded random weights.

Tolerances: both sides compute in float32 and differ only in order of
summation (the program's chunked SSD scan against the per-token recurrence,
``ragged_dot`` over sorted rows against a dense loop over experts, the
cache against a full forward); the logits are about 0.1 in size, and those
orders move them by about 1e-6. ``ATOL`` = 2e-5 leaves that room ten times
over and is still far below what a dropped token, a rotary embedding, a
wrong multiplier or bfloat16 anywhere in the path moves them (1e-3 and
more, checked below where a test shows the difference).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import granite_reference as reference
from repro.configs import get_config
from repro.models import decode_step, init_cache, init_params, prefill
from repro.models import layers as L
from repro.models.transformer import _ffn
from repro.serve.engine import ServeEngine

CFG = dataclasses.replace(get_config("granite_4_h_small", smoke=True),
                          dtype="float32")
ATOL = 2e-5
#: Far above float32's order of summation: a real fault moves logits this far.
FAULT = 1e-3


def _params(cfg, seed=0):
    """init_params with every vector weight (norms, A_log, D, dt_bias, conv
    bias) moved off its constant init, so that each one counts."""
    params = init_params(cfg, jax.random.PRNGKey(seed))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree.unflatten(tree, [
        a + 0.3 * jax.random.normal(k, a.shape, a.dtype)
        if a.ndim == 1 or (a.ndim == 2 and a.shape[0] == cfg.n_blocks)
        else a
        for a, k in zip(leaves, keys)])


def _tokens(b, s, seed=2):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, s), 0, CFG.vocab)


def test_prefill_logits_match_the_reference():
    params = _params(CFG)
    toks = _tokens(2, 24)
    logits, _ = prefill(CFG, params, toks)
    ref = reference.forward(CFG, params, toks)
    assert float(jnp.abs(ref).max()) > 100 * ATOL
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=0, atol=ATOL)


def test_decode_through_the_hybrid_cache_matches_the_reference():
    """Prefill, then 10 decode steps of the engine's donated step through
    the hybrid cache (KV and SSM state side by side), teacher forced: the
    logits at every decoded position match the reference's full forward,
    and the step updates every byte of the cache in place."""
    params = _params(CFG)
    s, n = 16, 10
    toks = _tokens(2, s + n)
    engine = ServeEngine(CFG, params, max_batch=2, max_len=s + n)
    logits, cache0 = engine._prefill(params, toks[:, :s], None)
    cache = engine._rehome(cache0, 2, s)
    assert {"k", "v", "ssm", "conv"} <= set(cache)
    step, share, _, cache = engine._decoder(cache, toks[:, s], None)
    got = [logits[:, -1]]
    for pos in range(s, s + n):
        out, cache = step(params, cache, toks[:, pos], jnp.int32(pos), None)
        got.append(out)
    ref = reference.forward(CFG, params, toks)
    np.testing.assert_allclose(np.stack(got, 1), np.asarray(ref[:, s - 1:]),
                               rtol=0, atol=ATOL)
    assert share == 1.0


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """Eight chips hold one expert each: what each share's layer gives,
    plus the shared expert once, is the uncut layer's output."""
    kr, ks = jax.random.split(jax.random.PRNGKey(3))
    moe = L.init_moe(kr, CFG)
    shared = L.init_mlp(ks, dataclasses.replace(CFG, d_ff=CFG.moe_shared_ff))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 12, CFG.d_model))
    e = CFG.moe_experts
    parts = L.mlp(shared, x, CFG)
    for j in range(e):
        share = dict(moe, **{n: moe[n][j:j + 1] for n in ("wi", "wg", "wo")})
        parts = parts + L.moe_dropless(
            share, x, dataclasses.replace(CFG, moe_held=(j, 1)))
    with jax.default_matmul_precision("highest"):
        whole = reference.routed(CFG, moe, x) + reference._swiglu(shared, x)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=0, atol=ATOL)


def test_a_token_routed_past_the_old_capacity_still_counts_in_prefill():
    """With every router at zero, every token takes experts 0..k-1 (top-k
    breaks ties to the lower index): each of them gets all T tokens, far
    past the capacity-bounded layer's ceil(T·k/E·1.25). That layer drops
    the later tokens; prefill's dropless layer keeps every one and matches
    the reference."""
    params = _params(CFG)
    params["stack"] = {
        name: dict(lp, moe=dict(lp["moe"],
                                router=jnp.zeros_like(lp["moe"]["router"])))
        for name, lp in params["stack"].items()}
    toks = _tokens(2, 16)
    logits, _ = prefill(CFG, params, toks)
    ref = reference.forward(CFG, params, toks)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=0, atol=ATOL)

    lp = jax.tree.map(lambda a: a[0], params["stack"]["l0"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, CFG.d_model))
    dropless = _ffn(CFG, lp, x, dropless=True)
    capped = _ffn(CFG, lp, x, dropless=False)
    assert float(jnp.abs(dropless - capped)[1, -1].max()) > FAULT
    np.testing.assert_allclose(np.asarray(dropless[0, :2]),
                               np.asarray(capped[0, :2]), rtol=0, atol=ATOL)


@pytest.mark.parametrize("held", [(0, 8), (0, 2), (6, 2)])
@pytest.mark.parametrize("skewed", [False, True], ids=["random", "skewed"])
def test_the_held_experts_part_matches_the_reference(held, skewed):
    """256 tokens, top-3 of 8, with every expert held or two: a random
    router, or one at zero, which sends every token to experts 0-2 (top-k
    breaks ties to the lower index), so experts 0-1 get all 512 pairs they
    can and experts 6-7 none. Each gives the reference's sum over the held
    experts."""
    first, n = held
    moe = L.init_moe(jax.random.PRNGKey(3), CFG)
    p = dict(moe, **{w: moe[w][first:first + n] for w in ("wi", "wg", "wo")})
    if skewed:
        p["router"] = jnp.zeros_like(p["router"])
    cfg = dataclasses.replace(CFG, moe_held=held)
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 64, CFG.d_model))
    with jax.default_matmul_precision("highest"):
        want = reference.routed(cfg, p, x)
    np.testing.assert_allclose(np.asarray(L.moe_dropless(p, x, cfg)),
                               np.asarray(want), rtol=0, atol=ATOL)


@pytest.mark.parametrize("arch", ["granite_4_h_small",
                                  "granite_4_h_small_ep8"])
def test_the_parameter_count_counts_the_held_experts(arch):
    """``param_count`` counts the experts a chip holds, not all 72: within
    1% of the SMOKE model's parameters (it leaves out norms and biases),
    and 2.414 B for the chip's share at full width, 32.2 B for the whole
    published model."""
    cfg = get_config(arch, smoke=True)
    n = sum(a.size for a in jax.tree.leaves(init_params(
        cfg, jax.random.PRNGKey(0))))
    assert cfg.param_count() == pytest.approx(n, rel=0.01)
    full = {"granite_4_h_small": 32.2e9, "granite_4_h_small_ep8": 2.414e9}
    assert get_config(arch).param_count() == pytest.approx(full[arch],
                                                           rel=0.001)


@pytest.fixture
def no_rope(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("a rotary embedding was applied")

    monkeypatch.setattr(L, "apply_rope", refuse)
    monkeypatch.setattr(L, "rope_freqs", refuse)


def test_no_rotary_embedding_is_applied_anywhere(no_rope):
    """Prefill, the cache fill and a decode step of the NoPE model run with
    the rotary embedding refusing to be called; the same model with RoPE
    does call it."""
    params = _params(CFG)
    toks = _tokens(2, 9)
    _, cache0 = prefill(CFG, params, toks[:, :8])
    cache = init_cache(CFG, 2, 9)
    cache = dict(cache0, k=cache["k"].at[:, :, :, :8].set(cache0["k"]),
                 v=cache["v"].at[:, :, :, :8].set(cache0["v"]))
    decode_step(CFG, params, cache, toks[:, 8], jnp.int32(8))
    with pytest.raises(AssertionError, match="rotary"):
        prefill(dataclasses.replace(CFG, pos_emb="rope"), params, toks)


@pytest.mark.parametrize("neutral", [
    {"embed_mult": 1.0}, {"residual_mult": 1.0}, {"logits_div": 1.0},
    {"attn_scale": None},
    {"embed_mult": 1.0, "residual_mult": 1.0, "logits_div": 1.0,
     "attn_scale": None}], ids=["embed", "residual", "logits", "attn_scale",
                                "all"])
def test_a_multiplier_at_one_gives_the_plain_block(neutral):
    """Each muP multiplier set to 1 (the attention scale to 1/sqrt(hd))
    gives the plain block: the program matches the reference without it,
    and differs from the program with it."""
    params = _params(CFG)
    toks = _tokens(2, 16)
    plain = dataclasses.replace(CFG, **neutral)
    got, _ = prefill(plain, params, toks)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(reference.forward(plain, params, toks)),
        rtol=0, atol=ATOL * max(1.0, CFG.logits_div / plain.logits_div))
    granite, _ = prefill(CFG, params, toks)
    assert float(jnp.abs(got - granite).max()) > FAULT

"""Serving engine + §VIII analytical serving/spec-decode model tests."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.serving import (expected_accepted, serving_sweep,
                                speculative_throughput)
from repro.models import decode_step, init_params, synth_batch
from repro.models.transformer import _memory_from_batch
from repro.serve.engine import DecodeStep, ServeEngine, fused_weight_share
from repro.systems.chips import HBM_V5E, ICI, SN40L
from repro.systems.system import SystemSpec
from repro.systems.topology import torus2d
from repro.workloads.llm import LLAMA3_8B, decode_layer_graph, gpt_layer_graph
from test_models_smoke import DECODE_ARCHS

KEY = jax.random.PRNGKey(0)


# ------------------------------ executable engine -----------------------------
def test_engine_generates_tokens():
    cfg = get_config("olmo_1b", smoke=True)
    params = init_params(cfg, KEY)
    eng = ServeEngine(cfg, params, max_batch=2, max_len=64)
    prompts = jax.random.randint(KEY, (2, 8), 0, cfg.vocab)
    res = eng.generate(prompts, n_tokens=6)
    toks = jnp.asarray(res.tokens).T          # (B, n_tokens)
    assert toks.shape == (2, 6)
    assert res.ttft > 0 and res.tpot > 0 and res.tokens_per_s > 0
    assert bool((toks >= 0).all()) and bool((toks < cfg.vocab).all())


def test_engine_greedy_matches_forward_continuation():
    """Greedy generation must follow the model's own argmax continuation."""
    from repro.models import forward
    cfg = get_config("olmo_1b", smoke=True)
    params = init_params(cfg, KEY)
    eng = ServeEngine(cfg, params, max_batch=1, max_len=32)
    prompts = jax.random.randint(jax.random.PRNGKey(3), (1, 8), 0, cfg.vocab)
    res = eng.generate(prompts, n_tokens=3)
    # reference: iterated full forward
    seq = prompts
    want = []
    for _ in range(3):
        logits = forward(cfg, params, seq, remat=False)
        nxt = logits[:, -1].argmax(-1)
        want.append(int(nxt[0]))
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    got = [int(t[0]) for t in res.tokens]
    assert got == want


def test_engine_ssm_generates():
    cfg = get_config("mamba2_130m", smoke=True)
    params = init_params(cfg, KEY)
    eng = ServeEngine(cfg, params, max_batch=1, max_len=48)
    prompts = jax.random.randint(KEY, (1, 8), 0, cfg.vocab)
    res = eng.generate(prompts, n_tokens=4)
    assert len(res.tokens) == 4


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_generate_matches_undonated_decode_steps(arch):
    """The engine's donated decode step, in the layout its compiler picks,
    serves exactly the greedy tokens of a plain loop of undonated
    ``decode_step`` calls in the default layout."""
    cfg = get_config(arch, smoke=True)
    params = init_params(cfg, KEY)
    b, s, n = 2, 8, 5
    batch = synth_batch(cfg, batch=b, seq=s, seed=1)
    memory = _memory_from_batch(cfg, params, batch)
    eng = ServeEngine(cfg, params, max_batch=b, max_len=s + n)
    got = eng.generate(batch["tokens"], n_tokens=n, memory=memory).tokens

    logits, cache0 = eng._prefill(params, batch["tokens"], memory)
    cache = eng._rehome(cache0, b, s)
    plain = jax.jit(lambda p, c, t, i, m: decode_step(cfg, p, c, t, i,
                                                       memory=m))
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    want = [tok.tolist()]
    for pos in range(s, s + n - 1):
        logits_i, cache = plain(params, cache, tok, jnp.int32(pos), memory)
        tok = jnp.argmax(logits_i, -1).astype(jnp.int32)
        want.append(tok.tolist())
    assert got == want


@pytest.mark.parametrize("arch", ["olmo_1b", "mistral_nemo_12b"])
def test_f32_weights_decode_as_their_bf16_cast(arch):
    """A decode step on f32 weights with bf16 activations (the layer loop
    unrolled, each matmul casting its layer's slice in its own fusion)
    returns the logits and cache, bit for bit, of the same step on the
    weights cast to bf16 beforehand (the rolled loop), and the engine
    serves the same greedy tokens from either. The two configs' f32
    parameters outside the matmuls are norm weights of ones, which the
    cast leaves as they are."""
    cfg = get_config(arch, smoke=True)
    params = init_params(cfg, KEY)
    cast = jax.tree.map(lambda w: w.astype(jnp.bfloat16), params)
    b, s, n = 2, 8, 5
    prompts = jax.random.randint(KEY, (b, s), 0, cfg.vocab)
    eng = ServeEngine(cfg, params, max_batch=b, max_len=s + n)
    logits, cache0 = eng._prefill(params, prompts, None)
    cache = eng._rehome(cache0, b, s)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    step = jax.jit(lambda p, c, t, i: decode_step(cfg, p, c, t, i))
    for p, loops in ((params, False), (cast, True)):
        text = step.lower(p, cache, tok, jnp.int32(s)).as_text()
        assert ("stablehlo.while" in text) == loops
    wide, narrow = (step(p, cache, tok, jnp.int32(s)) for p in (params, cast))
    for a, c in zip(jax.tree.leaves(wide), jax.tree.leaves(narrow)):
        assert a.dtype == c.dtype and bool(jnp.array_equal(a, c))
    tokens = [ServeEngine(cfg, p, max_batch=b, max_len=s + n).generate(
        prompts, n_tokens=n).tokens for p in (params, cast)]
    assert tokens[0] == tokens[1]


def test_fused_weight_share_counts_weights_cast_apart():
    """A program that writes a bf16 copy of one stacked weight (here as an
    output) reads only the other weight as stored: the share is the other
    weight's bytes over both."""
    stack = {"w": jnp.ones((2, 8, 16)), "v": jnp.ones((2, 16, 32)),
             "norm": jnp.ones((2, 8))}
    x = jnp.ones((4, 8), jnp.bfloat16)

    def program(s, x):
        w, v = s["w"][1], s["v"][1]
        y = x @ w.astype(x.dtype) @ v.astype(x.dtype)
        return y, w.astype(jnp.bfloat16)

    run = jax.jit(program).lower(stack, x).compile()
    step = DecodeStep(enter=None, run=run)
    assert fused_weight_share(step, {"stack": stack}) == 1 - 8 * 16 / (
        8 * 16 + 16 * 32)
    clean = jax.jit(lambda s, x: program(s, x)[0]).lower(stack, x).compile()
    assert fused_weight_share(DecodeStep(None, clean),
                              {"stack": stack}) == 1.0


def _decoding_engine():
    cfg = get_config("olmo_1b", smoke=True)
    params = init_params(cfg, KEY)
    eng = ServeEngine(cfg, params, max_batch=2, max_len=16)
    prompts = jax.random.randint(KEY, (2, 8), 0, cfg.vocab)
    logits, cache0 = eng._prefill(params, prompts, None)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    return eng, eng._rehome(cache0, 2, 8), tok


def test_compiled_decode_step_aliases_every_cache_byte():
    eng, cache, tok = _decoding_engine()
    step, share, _, cache = eng._decoder(cache, tok, None)
    cache_bytes = sum(x.nbytes for x in jax.tree.leaves(cache))
    assert step.memory_analysis().alias_size_in_bytes == cache_bytes
    assert share == 1.0


def test_decode_step_consumes_the_callers_cache():
    eng, rehomed, tok = _decoding_engine()
    step, _, _, cache = eng._decoder(rehomed, tok, None)
    _, out = step(eng.params, cache, tok, jnp.int32(8), None)
    assert all(x.is_deleted() for x in jax.tree.leaves(cache))
    assert not any(x.is_deleted() for x in jax.tree.leaves(out))
    # the updated cache chains into the next step as it is
    _, out2 = step(eng.params, out, tok, jnp.int32(9), None)
    assert all(x.is_deleted() for x in jax.tree.leaves(out))
    assert out2["k"].shape == cache["k"].shape


@pytest.mark.parametrize("arch", ["olmo_1b", "mamba2_130m"])
def test_decode_steady_runs_through_the_donated_step(arch):
    """decode_steady drives the same compiled, donated step as generate,
    call after call, and its steady steps time the same work."""
    cfg = get_config(arch, smoke=True)
    params = init_params(cfg, KEY)
    eng = ServeEngine(cfg, params, max_batch=2, max_len=32)
    prompts = jax.random.randint(KEY, (2, 8), 0, cfg.vocab)
    for _ in range(2):
        t = eng.decode_steady(prompts, n_steps=3, warmup=1)
        assert len(t.step_times) == 3 and all(x > 0 for x in t.step_times)
    eng.generate(prompts, n_tokens=3)
    assert len(eng._steps) == 1
    [(_, share, _)] = eng._steps.values()
    assert share == 1.0


# ------------------------------ analytical §VIII.A ----------------------------
def _sn40l_system(n=16):
    topo = torus2d(n, ICI)
    return SystemSpec("sn40l", SN40L, HBM_V5E, topo)


def test_serving_sweep_tradeoffs():
    """Paper Fig 20: increasing TP decreases TTFT/TPOT; increasing PP
    increases system-level throughput."""
    s = dataclasses.replace(LLAMA3_8B, batch=1)
    pre = gpt_layer_graph(s)
    dec = decode_layer_graph(s, kv_len=8192)
    pts = serving_sweep(pre, dec, n_layers=32, system=_sn40l_system(16))
    assert len(pts) >= 3
    by_tp = {p.tp: p for p in pts}
    tps = sorted(by_tp)
    # TTFT monotonically non-increasing in TP (more chips shard the prefill)
    assert by_tp[tps[-1]].ttft < by_tp[tps[0]].ttft
    # PP>1 point has higher decode throughput than its TPOT-1/x implies
    pp_pts = [p for p in pts if p.pp > 1]
    if pp_pts:
        p = pp_pts[0]
        assert p.decode_throughput * p.tpot > 0.99  # pipelined slots ≥ 1/x


def test_decode_is_memory_or_network_bound():
    """Paper: 'in the decode phase most time is spent on memory and network'."""
    s = dataclasses.replace(LLAMA3_8B, batch=8)
    dec = decode_layer_graph(s, kv_len=8192)
    pre = gpt_layer_graph(dataclasses.replace(s, batch=1))
    pts = serving_sweep(pre, dec, n_layers=32, system=_sn40l_system(16))
    tp16 = [p for p in pts if p.tp == 16]
    assert tp16
    bd = tp16[0].breakdown_decode
    assert bd["memory"] + bd["network"] > bd["compute"]


# ------------------------------ §VIII.B spec decode ----------------------------
def test_expected_accepted_formulas():
    # sequence: geometric series
    assert expected_accepted(3, 0.0, "sequence") == pytest.approx(1.0)
    assert expected_accepted(3, 1.0, "sequence") == pytest.approx(4.0)
    assert expected_accepted(2, 0.5, "sequence") == pytest.approx(1.75)
    # tree boosts the effective acceptance
    assert expected_accepted(3, 0.5, "tree") > expected_accepted(
        3, 0.5, "sequence")


def test_specdecode_monotonic_in_acceptance_and_window():
    td, tv = 1e-3, 1e-2
    t1 = speculative_throughput(td, tv, window=4, acceptance=0.5)
    t2 = speculative_throughput(td, tv, window=4, acceptance=0.9)
    assert t2 > t1
    t3 = speculative_throughput(td, tv, window=8, acceptance=0.9)
    assert t3 > t1


def test_specdecode_tree_prefers_small_windows():
    """Paper: tree-based needs small windows — the 2^K draft cost blows up."""
    td, tv = 1e-3, 1e-2
    small = speculative_throughput(td, tv, window=2, acceptance=0.7,
                                   scheme="tree")
    huge = speculative_throughput(td, tv, window=10, acceptance=0.7,
                                  scheme="tree")
    assert small > huge


def test_specdecode_large_draft_model_overhead():
    """Paper: a 70B draft for a 405B target has too much overhead vs 8B."""
    tv = 20e-3
    t8 = speculative_throughput(1e-3, tv, window=4, acceptance=0.8)
    t70 = speculative_throughput(8e-3, tv, window=4, acceptance=0.9)
    assert t8 > t70


# ------------------------------ timing + window guards ------------------------
def test_decode_steady_timing_fields():
    cfg = get_config("olmo_1b", smoke=True)
    params = init_params(cfg, KEY)
    eng = ServeEngine(cfg, params, max_batch=2, max_len=64)
    prompts = jax.random.randint(KEY, (2, 8), 0, cfg.vocab)
    t = eng.decode_steady(prompts, n_steps=3, warmup=1)
    assert t.ttft > 0 and t.warmup == 1 and t.batch == 2
    assert len(t.step_times) == 3 and all(s > 0 for s in t.step_times)
    assert t.tpot == pytest.approx(sum(t.step_times) / 3)
    assert t.tokens_per_s == pytest.approx(2 / t.tpot)


def test_generate_window_overflow_raises():
    """Overflowing the KV cache must be a loud ValueError, not a silent
    out-of-range `.at[].set` drop."""
    cfg = get_config("olmo_1b", smoke=True)
    params = init_params(cfg, KEY)
    eng = ServeEngine(cfg, params, max_batch=1, max_len=16)
    prompts = jax.random.randint(KEY, (1, 8), 0, cfg.vocab)
    with pytest.raises(ValueError, match="max_len"):
        eng.generate(prompts, n_tokens=9)
    with pytest.raises(ValueError, match="max_len"):
        eng.decode_steady(prompts, n_steps=8, warmup=0)
    # the largest window that fits must not raise
    eng.generate(prompts, n_tokens=8)


def test_memory_threads_both_jitted_paths():
    """The cross-attention memory operand must reach the prefill AND the
    decode jitted functions — a dropped operand leaves logits unchanged."""
    cfg = get_config("llama32_vision_11b", smoke=True)
    params = init_params(cfg, KEY)
    eng = ServeEngine(cfg, params, max_batch=1, max_len=32)
    prompts = jax.random.randint(KEY, (1, 4), 0, cfg.vocab)
    m1 = jnp.zeros((1, cfg.n_image_tokens, cfg.d_model))
    m2 = jnp.ones((1, cfg.n_image_tokens, cfg.d_model))
    pre1, cache1 = eng._prefill(params, prompts, m1)
    pre2, _ = eng._prefill(params, prompts, m2)
    assert not jnp.allclose(pre1, pre2)
    tok = jnp.argmax(pre1[:, -1], -1).astype(jnp.int32)
    decoded = []
    for m in (m1, m2):      # the step donates its cache: one cache each
        step, _, _, cache = eng._decoder(eng._rehome(cache1, 1, 4), tok,
                                         m)
        decoded.append(step(params, cache, tok, jnp.int32(4), m)[0])
    assert not jnp.allclose(*decoded)
    # and the end-to-end driver accepts it
    res = eng.generate(prompts, n_tokens=3, memory=m1)
    assert len(res.tokens) == 3


# ------------------------------ sampling determinism --------------------------
def test_sampled_generation_seeded_deterministic():
    cfg = get_config("olmo_1b", smoke=True)
    params = init_params(cfg, KEY)
    eng = ServeEngine(cfg, params, max_batch=1, max_len=48)
    prompts = jax.random.randint(jax.random.PRNGKey(7), (1, 8), 0, cfg.vocab)
    kw = dict(n_tokens=8, temperature=1.0)
    a = eng.generate(prompts, rng=jax.random.PRNGKey(11), **kw)
    b = eng.generate(prompts, rng=jax.random.PRNGKey(11), **kw)
    assert a.tokens == b.tokens
    c = eng.generate(prompts, rng=jax.random.PRNGKey(12), **kw)
    assert c.tokens != a.tokens
    # per-step subkeys: a sampled run must not emit one token forever
    # (the degenerate fixed-key bug this engine refactor removed)
    flat = [t[0] for t in a.tokens]
    assert len(set(flat)) > 1


def test_sampling_without_rng_degrades_to_greedy():
    cfg = get_config("olmo_1b", smoke=True)
    params = init_params(cfg, KEY)
    eng = ServeEngine(cfg, params, max_batch=1, max_len=32)
    prompts = jax.random.randint(KEY, (1, 8), 0, cfg.vocab)
    hot = eng.generate(prompts, n_tokens=4, temperature=1.0, rng=None)
    cold = eng.generate(prompts, n_tokens=4)
    assert hot.tokens == cold.tokens


# ------------------------------ executable spec decode ------------------------
def test_specdecode_self_draft_bit_identical_to_greedy():
    """With the target as its own draft every proposal is accepted and the
    speculative stream must equal plain greedy decoding bit-for-bit."""
    from repro.serve.specdecode import speculative_generate
    cfg = get_config("olmo_1b", smoke=True)
    params = init_params(cfg, KEY)
    eng = ServeEngine(cfg, params, max_batch=1, max_len=48)
    prompts = jax.random.randint(jax.random.PRNGKey(5), (1, 8), 0, cfg.vocab)
    n = 8
    plain = [t[0] for t in eng.generate(prompts, n_tokens=n).tokens]
    spec, rate, target_calls = speculative_generate(
        cfg, params, cfg, params, prompts, n_tokens=n, window=4)
    assert spec == plain
    assert rate == pytest.approx(1.0)
    # window-4 self-drafting emits 5 tokens per target call
    assert target_calls < n

"""The ``serve.granite_4_h_small_ep8.decode`` cell: ``bench/run.py`` end to
end at smoke size on the CPU (the look for a chip steered here in the test),
the check's teeth, the counts against a hand count, and the configuration
file against the program's configuration."""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from repro import tracing

from bench import program
from bench import run as bench_run
from bench.tests.test_bench_program_spans import cpu_trace  # noqa: F401
from bench.tests.test_bench_run import SEED, cpu_chips, last_line

CELL = "serve.granite_4_h_small_ep8.decode"
#: The program's ``granite_4_h_small_ep8`` SMOKE, in the file's keys.
SMOKE_SPEC = dict(
    n_layers=4, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=64,
    vocab=512, num_local_experts=8, num_experts_per_tok=3,
    shared_intermediate_size=96, experts_held=2, experts_held_first=0,
    mamba_n_heads=8, mamba_d_head=32, mamba_d_state=16,
    attention_multiplier=0.05,
    layer_types=["mamba", "mamba", "attention", "mamba"])
SMOKE_MIX = dict(batch=2, prompt_len=8, new_tokens=6, max_len=32,
                 check_requests=2)
METRICS = {"decode_step_roofline", "step_mfu.decode",
           "device_idle_share.decode", "cache_aliased.decode"}
LOAD_CELL = bench_run.load_cell


def smoke_cell():
    cell = LOAD_CELL(CELL)
    cell.spec = dict(cell.spec, **SMOKE_SPEC)
    cell.mix = dict(cell.mix, **SMOKE_MIX)
    return cell


@pytest.fixture
def steered(monkeypatch):
    """The smoke cell, CPU devices, peaks for the CPU's device kind, and no
    persistent compilation cache."""
    from repro.configs import get_config

    monkeypatch.setattr(bench_run, "use_compile_cache", lambda: None)
    monkeypatch.setattr(program, "program_config", lambda spec: get_config(
        "granite_4_h_small_ep8", smoke=True))
    monkeypatch.setattr(bench_run, "load_cell",
                        lambda name, bench=None: smoke_cell())
    monkeypatch.setattr(bench_run, "peaks_for", lambda kind: {
        "flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10})


def test_a_run_prints_the_contract_line(steered, capsys):
    assert bench_run.main(["--workload", CELL, "--seed", str(SEED),
                           "--seconds", "0.5"], require=cpu_chips) == 0
    out = last_line(capsys)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["compiles_in_window"] == 0
    assert set(out["metrics"]) == {"setup_s", "output_tokens_per_s"}
    gap = out["check"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]


def test_a_traced_run_reports_the_four_metrics(steered, cpu_trace,
                                               capsys):
    tracing.reset()
    bench_run.main(["--workload", CELL, "--seed", "7", "--seconds", "0.5",
                    "--trace", "1"], require=cpu_chips)
    tracing.reset()
    out = last_line(capsys)
    assert out["correct"] is True
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(metrics) == METRICS
    assert metrics["cache_aliased.decode"] == 100.0
    assert all(0 < v <= 100 for v in metrics.values())


def test_a_token_altered_where_it_is_produced_fails(steered, capsys,
                                                    monkeypatch):
    from repro.serve.engine import ServeEngine

    sample = ServeEngine._sample

    def altered(logits, temperature, rng):
        tok = sample(logits, temperature, rng)
        return (tok + 1) % logits.shape[-1]

    monkeypatch.setattr(ServeEngine, "_sample", staticmethod(altered))
    bench_run.main(["--workload", CELL, "--seed", "3", "--seconds", "0.3"],
                   require=cpu_chips)
    out = last_line(capsys)
    assert out["correct"] is False
    gap = out["check"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_the_float8_control_fails_the_limit():
    cell = smoke_cell()
    spec, model = cell.spec, cell.model
    w = model.make_weights(5, spec)
    rng = np.random.default_rng(5)
    seqs = rng.integers(0, spec["vocab"], (4, 64), dtype=np.int32)
    rows = np.broadcast_to(np.arange(64), (4, 64)).copy()
    ref = model.reference_logits(w, seqs, rows, spec=spec)
    low = model.reference_logits(w, seqs, rows, quant="fp8", spec=spec)
    assert model.served_gaps(ref, ref.argmax(-1)).max() == 0
    assert (model.served_gaps(ref, low.argmax(-1)).max()
            > cell.limits["max_logit_gap"]["limit"])


# A shape small enough to count by hand: d 8; 2 heads of 4, 1 KV head;
# experts 4 wide, 4 of them, top 2, 2 held; shared expert 6 wide; vocab 10;
# Mamba-2 with d_in 16 (2 heads of 8), state 2, conv 2; one layer of each.
TINY = dict(n_layers=2, d_model=8, n_heads=2, n_kv_heads=1, head_dim=4,
            d_ff=4, vocab=10, num_local_experts=4, num_experts_per_tok=2,
            experts_held=2, shared_intermediate_size=6, mamba_expand=2,
            mamba_n_heads=2, mamba_d_head=8, mamba_d_state=2,
            mamba_n_groups=1, mamba_d_conv=2,
            layer_types=["mamba", "attention"])


def test_the_counts_match_a_hand_count():
    spec = dict(smoke_cell().spec, **TINY)
    m = smoke_cell().model
    # every layer: router 8x4, experts 3 x (8x4) each (2 held), shared
    # 3 x (8x6), two norms of 8; Mamba-2: in_proj 8 x (2*16 + 2*2 + 2),
    # conv 2 x 20 and bias 20, A_log/D/dt_bias 2 each, norm 16, out 16x8;
    # attention: q 8x8, k and v 8x4, o 8x8; embed 10x8, final norm 8
    common = 32 + 2 * 96 + 144 + 16
    mamba = 8 * 38 + 40 + 20 + 6 + 16 + 128
    attn = 64 + 32 + 32 + 64
    assert m.param_count(spec) == 2 * common + mamba + attn + 80 + 8 == 1562
    # a token: Mamba-2 2*8*38 + conv 2*2*20 + scan 6*16*2 + out 2*16*8;
    # attention projections 2*8*(8+4+4) + 2*8*8; per layer the router
    # 2*8*4, held rows 2*2/4 = 1 of 2*96, shared 2*144
    ffn = 64 + 192 + 288
    token = (608 + 80 + 192 + 256) + (256 + 128) + 2 * ffn
    assert m.routed_rows_per_token(spec) == 1.0
    head = 2 * 8 * 10
    attn_scores = 2 * 2 * 8 * 3 * 4 / 2       # seq 3: 6 (q, k) pairs
    assert m.prefill_flops(2, 3, spec) == 2 * (3 * token + attn_scores
                                               + head)
    assert m.decode_flops(2, 5, spec) == 2 * (token + 4 * 8 * 6 + head)
    kv = 2 * 1 * 4 * 2                          # K and V of one layer, bf16
    state = 16 * 2 * 4 + 1 * 20 * 2             # SSM f32, conv window bf16
    assert m.kv_bytes_per_token(spec) == kv
    assert m.state_bytes_per_sequence(spec) == state
    assert m.prefill_bytes(2, 3, spec) == 1562 * 2 + 2 * 3 * kv + 2 * state
    touched = 1 - (1 - 2 / 4) ** 2              # an expert, by 2 tokens
    experts = 2 * 2 * 96
    assert m.decode_bytes(2, 5, spec) == pytest.approx(
        (1562 - experts + touched * experts) * 2
        + 2 * (2 * state + 6 * kv + kv))


def test_the_file_states_every_size_it_runs():
    """Every size and Granite key the benchmark's file states is the one
    the program's ``granite_4_h_small_ep8`` runs, and the published keys
    are the published model's."""
    from repro.configs import get_config
    from repro.models import init_params

    cell = LOAD_CELL(CELL)
    spec = cell.spec
    cfg = program.program_config(spec)
    full = get_config("granite_4_h_small")
    d_in = cfg.ssm_expand * cfg.d_model
    assert cfg == dataclasses.replace(full, name=cfg.name, n_layers=10,
                                      moe_held=(0, 9),
                                      param_dtype="bfloat16")
    stated = {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "intermediate_size": cfg.d_ff,
        "vocab_size": cfg.vocab, "num_local_experts": cfg.moe_experts,
        "num_experts_per_tok": cfg.moe_top_k,
        "shared_intermediate_size": cfg.moe_shared_ff,
        "experts_held": cfg.moe_held[1],
        "experts_held_first": cfg.moe_held[0],
        "mamba_d_state": cfg.ssm_state, "mamba_d_head": cfg.ssm_head_dim,
        "mamba_expand": cfg.ssm_expand, "mamba_d_conv": cfg.ssm_conv,
        "mamba_n_heads": d_in // cfg.ssm_head_dim, "mamba_n_groups": 1,
        "mamba_conv_bias": cfg.ssm_conv_bias, "mamba_proj_bias": False,
        "attention_bias": cfg.qkv_bias,
        "position_embedding_type": cfg.pos_emb,
        "attention_multiplier": cfg.attn_scale,
        "embedding_multiplier": cfg.embed_mult,
        "residual_multiplier": cfg.residual_mult,
        "logits_scaling": cfg.logits_div, "rms_norm_eps": cfg.rms_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "num_hidden_layers": full.n_layers, "hidden_act": "silu",
        "normalization_function": cfg.norm,
    }
    assert {k: spec[k] for k in stated} == stated
    assert spec["layer_types"] == [
        "attention" if full.layer_kind(i) == "attn" else "mamba"
        for i in range(full.n_layers)]
    assert set(spec["reduced"]) == {"n_layers", "experts_held"}
    program_params = jax.eval_shape(lambda k: init_params(cfg, k),
                                     jax.ShapeDtypeStruct((2,), jnp.uint32))
    count = sum(math.prod(x.shape) for x in jax.tree.leaves(program_params))
    assert count == cell.model.param_count(spec) == spec["param_count"]

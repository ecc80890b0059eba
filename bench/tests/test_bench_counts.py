"""The olmo_1b counts of needed operations and bytes, against the repo's
analytic workload graph for the same shapes and against totals worked by
hand for one small shape."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import pytest

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _model():
    spec = importlib.util.spec_from_file_location(
        "bench_config_olmo_1b_test", CONFIGS / "olmo_1b.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


M = _model()
SPEC = M.SPEC

# A shape small enough to count by hand: 2 layers, d 8, 2 heads of 4,
# d_ff 16, vocab 10.
TINY = dict(SPEC, n_layers=2, d_model=8, n_heads=2, n_kv_heads=2,
            head_dim=4, d_ff=16, vocab=10)


def _shape(seq: int, batch: int):
    from repro.workloads.llm import LLMShape

    return LLMShape(name="olmo_1b", n_layers=SPEC["n_layers"],
                    d_model=SPEC["d_model"], n_heads=SPEC["n_heads"],
                    n_kv_heads=SPEC["n_kv_heads"], d_ff=SPEC["d_ff"],
                    vocab=SPEC["vocab"], seq=seq, batch=batch,
                    gated=SPEC["gated"])


def _flops(graph, names) -> float:
    return sum(k.flops for k in graph.kernels if k.name in names)


MATMULS = ("QKV", "MHA1", "MHA2", "Proj", "FFN0", "FFN1")


def test_program_config_matches_the_file():
    from bench import program

    cfg = program.program_config(SPEC)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
            cfg.d_ff, cfg.vocab, cfg.gated) == (
        SPEC["n_layers"], SPEC["d_model"], SPEC["n_heads"],
        SPEC["n_kv_heads"], SPEC["head_dim"], SPEC["d_ff"], SPEC["vocab"],
        SPEC["gated"])
    # OLMo-1B's published count
    assert M.param_count() == 1_176_764_416 == cfg.param_count()
    assert M.param_count() == SPEC["param_count"]


@pytest.mark.parametrize("batch,seq", [(8, 1536), (16, 1024), (1, 2048)])
def test_prefill_flops_match_the_workload_graph(batch, seq):
    from repro.workloads.llm import gpt_layer_graph, lm_head_graph

    s = _shape(seq, batch)
    layer = _flops(gpt_layer_graph(s, causal=True), MATMULS)
    head = lm_head_graph(_shape(1, batch)).total_flops()  # last position
    graph = SPEC["n_layers"] * layer + head
    # the graph halves S x S for causality; the count takes the S(S+1)/2
    # positions at or before each query
    assert M.prefill_flops(batch, seq) == pytest.approx(graph, rel=2e-3)
    assert M.prefill_flops(batch, seq) > graph


@pytest.mark.parametrize("batch,pos", [(16, 1024), (16, 1150), (1, 2047)])
def test_decode_flops_match_the_workload_graph(batch, pos):
    from repro.workloads.llm import decode_layer_graph, lm_head_graph

    s = _shape(1, batch)
    layer = _flops(decode_layer_graph(s, kv_len=pos + 1),
                   ("QKV", "AttnDec", "Proj", "FFN"))
    graph = SPEC["n_layers"] * layer + lm_head_graph(s).total_flops()
    assert M.decode_flops(batch, pos) == pytest.approx(graph, rel=1e-12)


def test_counts_by_hand_on_a_tiny_shape():
    # per layer: wq, wk, wv, wo 4 x 8 x 8 = 256; wi, wg, wf 3 x 8 x 16 =
    # 384 -> 640 params, 2 FLOPs each per token; embed 10 x 8 = 80
    assert M.param_count(TINY) == 2 * 640 + 80
    # prefill of 1 x 3: matmuls 2 x 3 x 2 x 640 = 7,680; causal attention
    # 2 layers x 2 (QK, PV) x 2 x 8 x (1 + 2 + 3) = 384; head 2 x 8 x 10
    assert M.prefill_flops(1, 3, TINY) == 7680 + 384 + 160
    # decode at pos 4: matmuls 2 x 2 x 640 = 2,560; attention over 5
    # positions 2 x 4 x 8 x 5 = 320; head 160
    assert M.decode_flops(1, 4, TINY) == 2560 + 320 + 160
    # bytes: f32 params 1,360 x 4; K and V bf16 per token 2 x 2 x 8 x 2 = 64;
    # read 5 positions, write one
    assert M.kv_bytes_per_token(TINY) == 64
    assert M.decode_bytes(2, 4, TINY) == 1360 * 4 + 2 * (64 * 5 + 64)
    assert M.prefill_bytes(2, 3, TINY) == 1360 * 4 + 2 * 3 * 64


def test_config_file_states_every_size_it_runs():
    spec = json.loads((CONFIGS / "olmo_1b.json").read_text())
    for key in ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
                "d_ff", "vocab", "param_dtype", "compute_dtype",
                "kv_cache_dtype", "reduced", "assumed", "source"):
        assert key in spec, key

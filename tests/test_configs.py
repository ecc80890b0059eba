"""Assigned architecture configs must match the public-literature numbers."""
from __future__ import annotations

import pytest

from repro.configs import ARCH_IDS, SHAPES, cells, get_config

# (arch, layers, d_model, heads, kv, d_ff, vocab, experts, top_k)
ASSIGNED = {
    "command_r_35b": (40, 8192, 64, 8, 22528, 256000, 0, 0),
    "minitron_4b": (32, 3072, 24, 8, 9216, 256000, 0, 0),
    "mistral_nemo_12b": (40, 5120, 32, 8, 14336, 131072, 0, 0),
    "olmo_1b": (16, 2048, 16, 16, 8192, 50304, 0, 0),
    "llama32_vision_11b": (40, 4096, 32, 8, 14336, 128256, 0, 0),
    "olmoe_1b_7b": (16, 2048, 16, 16, 1024, 50304, 64, 8),
    "qwen3_moe_235b": (94, 4096, 64, 4, 1536, 151936, 128, 8),
    "jamba_v01_52b": (32, 4096, 32, 8, 14336, 65536, 16, 2),
    "seamless_m4t_medium": (12, 1024, 16, 16, 4096, 256206, 0, 0),
    "mamba2_130m": (24, 768, 0, 0, 0, 50280, 0, 0),
    "granite_4_h_small": (40, 4096, 32, 8, 768, 100352, 72, 10),
}


@pytest.mark.parametrize("arch", list(ASSIGNED))
def test_exact_config_numbers(arch):
    cfg = get_config(arch)
    nl, d, h, kv, ff, v, e, k = ASSIGNED[arch]
    assert cfg.n_layers == nl
    assert cfg.d_model == d
    assert cfg.vocab == v
    assert cfg.moe_experts == e
    assert cfg.moe_top_k == k
    if h:
        assert cfg.n_heads == h and cfg.n_kv_heads == kv
    assert cfg.d_ff == ff


def test_family_flags():
    assert get_config("mamba2_130m").family == "ssm"
    assert get_config("mamba2_130m").attention_free
    assert get_config("jamba_v01_52b").family == "hybrid"
    assert get_config("llama32_vision_11b").family == "vlm"
    assert get_config("llama32_vision_11b").cross_attn_every > 0
    assert get_config("seamless_m4t_medium").is_enc_dec
    assert get_config("olmo_1b").norm == "nonparam_ln"
    assert get_config("qwen3_moe_235b").family == "moe"


def test_granite_pattern_and_share():
    """Granite-4.0-H-Small: attention at layers 5, 15, 25, 35 of 40 (one
    per period of 10), MoE with a shared expert on every layer; its chip's
    share holds one period and experts 0-8 of 72, routing over all 72."""
    cfg = get_config("granite_4_h_small")
    assert [i for i in range(cfg.n_layers)
            if cfg.layer_kind(i % cfg.block_size) == "attn"] == [5, 15, 25, 35]
    assert cfg.moe_shared_ff == 1536 and cfg.pos_emb == "nope"
    assert all(cfg.layer_is_moe(i) for i in range(cfg.block_size))
    share = get_config("granite_4_h_small_ep8")
    assert share.n_layers == cfg.block_size == 10
    assert share.experts_held == (0, 9) and share.moe_experts == 72
    assert cfg.experts_held == (0, 72)


def test_jamba_interleave():
    """Jamba: mamba:attention 1:7 interleave (one attn layer per 8), MoE on
    alternating layers (16e top-2)."""
    cfg = get_config("jamba_v01_52b")
    kinds = [cfg.layer_kind(i) for i in range(cfg.block_size)]
    assert kinds.count("attn") == 1
    assert kinds.count("ssm") == cfg.block_size - 1
    moes = [cfg.layer_is_moe(i) for i in range(cfg.block_size)]
    assert sum(moes) == cfg.block_size // 2


def test_shape_table():
    assert SHAPES["train_4k"].seq_len == 4096
    assert SHAPES["train_4k"].global_batch == 256
    assert SHAPES["prefill_32k"].seq_len == 32768
    assert SHAPES["prefill_32k"].global_batch == 32
    assert SHAPES["decode_32k"].global_batch == 128
    assert SHAPES["long_500k"].seq_len == 524288
    assert SHAPES["long_500k"].global_batch == 1


def test_long_context_cells_only_for_subquadratic():
    for arch in ASSIGNED:
        names = cells(arch)
        if arch in ("mamba2_130m", "jamba_v01_52b", "granite_4_h_small"):
            assert "long_500k" in names, arch
        else:
            assert "long_500k" not in names, arch


def test_block_pattern_divides_layers():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        assert cfg.n_layers % cfg.block_size == 0
        assert cfg.n_blocks * cfg.block_size == cfg.n_layers


def test_smoke_configs_same_family():
    for arch in ARCH_IDS:
        full, smoke = get_config(arch), get_config(arch, smoke=True)
        assert smoke.family == full.family
        assert smoke.norm == full.norm
        assert bool(smoke.moe_experts) == bool(full.moe_experts)
        assert smoke.n_layers <= 4
        assert smoke.d_model <= 256

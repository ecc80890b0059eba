"""Granite-4.0-H-Small's one-chip share compiles for a described TPU v5e,
and the lowered programs of olmo_1b and of the share are pinned by hash.

Nothing runs: programs are lowered from shapes placed on one chip of a
described ``v5e:2x2`` topology and compiled by the TPU's own compiler. The
topology is described only inside the module fixture, so only the test
worker given this file loads the TPU library; where it cannot be described,
the compile tests skip.
"""
from __future__ import annotations

import dataclasses
import hashlib
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import decode_step, init_cache, init_params, prefill
from repro.serve.engine import (aliased_share, compile_decode,
                                fused_weight_share)

#: Device memory of one TPU v5e chip.
V5E_HBM = 16 * 2**30
#: The serving cells' batch and lengths (bench/traffic/decode_closed.json).
BATCH, PROMPT, MAX_LEN = 16, 1024, 1152
#: sha256 of olmo_1b's (SwiGLU, as the benchmark runs it) lowered programs
#: at BATCH x MAX_LEN (decode step) and BATCH x PROMPT (prefill), without
#: debug locations, under JAX 0.9.0. The prefill is as it was before
#: Granite's fields (NoPE, attention scale, muP multipliers, shared expert,
#: conv bias, held experts, dropless serving MoE) came in: every default
#: leaves it alone. The decode step changed once since, on purpose: its f32
#: weights under bf16 activations unroll the layer loop and put the q/k/v
#: projections' outputs behind an optimization barrier, so that each matmul
#: casts its layer's weight inside its own fusion and no bf16 copy of a
#: weight is made.
OLMO_HLO = {
    "decode":
        "6f6f571bd211ba15a35b34e0d1106bf0a57b6a70a8e019d7ab536545cc19bea4",
    "prefill":
        "c9eb159140ee9322940f5f4a49fd8b615c94cc4eabcb5b55df36a0e53f5a1131",
}
#: sha256 of the chip's share's lowered decode step at BATCH x MAX_LEN, as
#: it was before f32 weights took the unrolled path: bf16 weights keep the
#: rolled layer loop.
GRANITE_DECODE_HLO = (
    "b894d1deefee6221ad0086ac2475a885bc894f008d145b601af1e6fd7cd6ee04")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile cannot be read back without a chip
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(desc.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _on(tree, sharding):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding), tree)


def _program(cfg, one_chip):
    params = _on(jax.eval_shape(lambda k: init_params(cfg, k),
                                jax.ShapeDtypeStruct((2,), jnp.uint32)),
                 one_chip)
    cache = _on(jax.eval_shape(lambda: init_cache(cfg, BATCH, MAX_LEN)),
                one_chip)
    token = jax.ShapeDtypeStruct((BATCH,), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    prompts = jax.ShapeDtypeStruct((BATCH, PROMPT), jnp.int32,
                                   sharding=one_chip)
    return params, cache, token, pos, prompts


def _bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def test_granite_share_decode_step_fits_and_aliases_the_hybrid_cache(
        one_chip):
    """The engine's donated decode step of the chip's share at 16 x 1,152
    fits one chip, writes every byte of the hybrid cache (K/V, SSM
    state, conv window) back into its argument's buffers, and reads every
    bf16 weight as stored."""
    cfg = get_config("granite_4_h_small_ep8")
    params, cache, token, pos, _ = _program(cfg, one_chip)
    assert set(cache) == {"k", "v", "ssm", "conv"}
    step = compile_decode(cfg, params, cache, token, pos)
    assert aliased_share(step, cache) == 1.0
    assert fused_weight_share(step, params) == 1.0
    assert 0.25 * V5E_HBM < _bytes(step.run) < V5E_HBM


def test_granite_share_prefill_fits_one_chip(one_chip):
    """The chip's share's prefill at 16 x 1,024 (full-position logits,
    the dropless expert layer, the hybrid cache fill) fits one chip."""
    cfg = get_config("granite_4_h_small_ep8")
    params, *_, prompts = _program(cfg, one_chip)
    compiled = jax.jit(lambda p, t: prefill(cfg, p, t)).lower(
        params, prompts).compile()
    assert 0.5 * V5E_HBM < _bytes(compiled) < V5E_HBM


@pytest.mark.parametrize("program", sorted(OLMO_HLO))
def test_olmo_1b_programs_are_unchanged(one_chip, program):
    cfg = dataclasses.replace(get_config("olmo_1b"), gated=True)
    params, cache, token, pos, prompts = _program(cfg, one_chip)
    if program == "decode":
        lowered = jax.jit(lambda p, c, t, i: decode_step(cfg, p, c, t, i)
                          ).lower(params, cache, token, pos)
    else:
        lowered = jax.jit(lambda p, t: prefill(cfg, p, t)).lower(params,
                                                                 prompts)
    text = lowered.as_text(debug_info=False)
    assert hashlib.sha256(text.encode()).hexdigest() == OLMO_HLO[program]


def test_granite_share_decode_program_is_unchanged(one_chip):
    cfg = get_config("granite_4_h_small_ep8")
    params, cache, token, pos, _ = _program(cfg, one_chip)
    lowered = jax.jit(lambda p, c, t, i: decode_step(cfg, p, c, t, i)
                      ).lower(params, cache, token, pos)
    text = lowered.as_text(debug_info=False)
    assert hashlib.sha256(text.encode()).hexdigest() == GRANITE_DECODE_HLO

"""Granite-4.0-H-Small's one-chip share compiles for a described TPU v5e,
and olmo_1b's programs are untouched by the fields Granite added.

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
from repro.serve.engine import aliased_share, compile_decode

#: Device memory of one TPU v5e chip.
V5E_HBM = 16 * 2**30
#: The serving cells' batch and lengths (bench/traffic/decode_closed.json).
BATCH, PROMPT, MAX_LEN = 16, 1024, 1152
#: sha256 of olmo_1b's (SwiGLU, as the benchmark runs it) lowered programs
#: at BATCH x MAX_LEN (decode step) and BATCH x PROMPT (prefill), without
#: debug locations, under JAX 0.9.0, as they were before Granite's fields
#: (NoPE, attention scale, muP multipliers, shared expert, conv bias, held
#: experts, dropless serving MoE) came in: every default leaves them alone.
OLMO_HLO = {
    "decode":
        "5794ac3ea02ac10010f325bc5ca9b24c8c19c0b5d8df374938289e43cc3266c0",
    "prefill":
        "c9eb159140ee9322940f5f4a49fd8b615c94cc4eabcb5b55df36a0e53f5a1131",
}


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
    fits one chip and writes every byte of the hybrid cache (K/V, SSM
    state, conv window) back into its argument's buffers."""
    cfg = get_config("granite_4_h_small_ep8")
    params, cache, token, pos, _ = _program(cfg, one_chip)
    assert set(cache) == {"k", "v", "ssm", "conv"}
    step = compile_decode(cfg, params, cache, token, pos)
    assert aliased_share(step, cache) == 1.0
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

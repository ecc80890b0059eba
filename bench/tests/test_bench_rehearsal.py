"""Compile rehearsal for a described TPU v5e (no chip): the olmo_1b decode
step and prefill at the serving cells' own batch and lengths fit one chip's
memory. Nothing runs; the TPU compiler only places and sizes the programs.
"""
from __future__ import annotations

import json
import os
from pathlib import Path

import pytest

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
CONFIGS = TRAFFIC.parent / "configs"
#: One v5e chip's HBM, 16 GiB.
CHIP_BYTES = 16 * 2**30


def _mix(name: str) -> dict:
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _shapes(tree, sharding):
    import jax

    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


def _bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


@pytest.fixture(scope="module")
def olmo(one_chip):
    import jax
    from repro.models import init_params

    from bench import program

    spec = json.loads((CONFIGS / "olmo_1b.json").read_text())
    cfg = program.program_config(spec)
    params = _shapes(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))), one_chip)
    return cfg, params


def test_decode_step_fits_one_chip(one_chip, olmo):
    import jax
    import jax.numpy as jnp
    from repro.models import decode_step, init_cache

    cfg, params = olmo
    mix = _mix("decode_closed")
    b = mix["batch"]
    cache = _shapes(jax.eval_shape(
        lambda: init_cache(cfg, b, mix["max_len"])), one_chip)
    tok = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda p, c, t, i: decode_step(cfg, p, c, t, i)
                       ).lower(params, cache, tok, pos).compile()
    used = _bytes(compiled)
    print(f"decode {b} x {mix['max_len']}: {used} bytes")
    assert 0.5 * CHIP_BYTES < used < CHIP_BYTES


def test_prefill_fits_one_chip(one_chip, olmo):
    import jax
    import jax.numpy as jnp
    from repro.models import prefill

    cfg, params = olmo
    mix = _mix("prefill_closed")
    tokens = jax.ShapeDtypeStruct((mix["batch"], mix["prompt_len"]),
                                  jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda p, t: prefill(cfg, p, t)
                       ).lower(params, tokens).compile()
    used = _bytes(compiled)
    print(f"prefill {mix['batch']} x {mix['prompt_len']}: {used} bytes")
    assert 0.5 * CHIP_BYTES < used < CHIP_BYTES

"""The main path's device programs compile for a described TPU v5e.

Nothing runs: each program is lowered from shapes placed on one chip of a
described ``v5e:2x2`` topology and compiled by the TPU's own compiler, which
refuses what interpret mode accepts (unaligned tiles, too much fast memory,
a program that does not fit HBM). The topology is described only inside the
module fixture, so only the test worker given this file loads the TPU
library; where it cannot be described, every test here skips.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.pricing import FIELDS, _price, _roofline
from repro.core.roofline import stack_terms
from repro.kernels.pricing.ops import lower_f32
from repro.models import decode_step, init_cache, init_params

#: The dense grid's pricing chunk (``DSEEngine.price_chunk_rows``).
CHUNK_ROWS = 65_536
#: Device memory of one TPU v5e chip.
V5E_HBM = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile cannot be read back without a chip
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("formula,columns", [
    (_price, FIELDS),
    (_roofline, tuple(stack_terms([]))),
], ids=["price", "roofline"])
def test_f32_pricing_kernel_compiles_to_mosaic(one_chip, formula, columns):
    """Every formula ``pallas-compiled`` reaches (``price_plans`` and
    ``batched_roofline``) compiles, at the dense grid's chunk size, to the
    Mosaic kernel and not to an interpreted loop."""
    compiled = lower_f32(formula, columns, CHUNK_ROWS, interpret=False,
                         sharding=one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_olmo_1b_decode_step_fits_one_chip(one_chip):
    """The full-width olmo_1b decode step at batch 8 over a 2,048-slot
    cache compiles, and its arguments, outputs and temporaries fit one
    chip's memory."""
    cfg = get_config("olmo_1b")
    batch, max_len = 8, 2048

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda k: init_params(cfg, k),
                                    jax.ShapeDtypeStruct((2,), jnp.uint32)))
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, batch, max_len)))
    token = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda p, c, t, i: decode_step(cfg, p, c, t, i)
                       ).lower(params, cache, token, pos).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < V5E_HBM, total

"""The main path's device programs compile for a described TPU v5e.

Nothing runs: each program is lowered from shapes placed on one chip of a
described ``v5e:2x2`` topology and compiled by the TPU's own compiler, which
refuses what interpret mode accepts (unaligned tiles, too much fast memory,
a program that does not fit HBM). The topology is described only inside the
module fixture, so only the test worker given this file loads the TPU
library; where it cannot be described, every test here skips.
"""
from __future__ import annotations

import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.pricing import FIELDS, _price, _roofline
from repro.core.roofline import stack_terms
from repro.kernels.pricing.ops import lower_f32
from repro.models import decode_step, init_cache, init_params
from repro.serve.engine import aliased_share, compile_decode

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


def _computations(hlo: str) -> dict:
    """Each computation of an HLO module's text: name -> instruction lines."""
    comps, lines = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            comps[head.group(1)] = lines = []
        elif lines is not None and line.startswith("  "):
            lines.append(line.strip())
    return comps


def test_olmo_1b_engine_decode_step_writes_the_cache_in_place(one_chip):
    """The serving engine's decode executable at the benchmark's olmo_1b
    widths (SwiGLU MLP), batch 16 over a 1,152-slot cache: the only copies
    or fusions that output a buffer of one layer's cache or of the stacked
    cache are the two row writes, each an in-place dynamic-update-slice,
    and every byte of the cache is aliased from argument to output. The
    executable's own entry layouts are the default ones, which JAX's
    persistent compilation cache gives back as they were compiled."""
    cfg = dataclasses.replace(get_config("olmo_1b"), gated=True)
    batch, max_len = 16, 1152

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda k: init_params(cfg, k),
                                    jax.ShapeDtypeStruct((2,), jnp.uint32)))
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, batch, max_len)))
    token = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    step = compile_decode(cfg, params, cache, token, pos)

    assert aliased_share(step, cache) == 1.0
    for fmt in jax.tree.leaves(step.run.input_formats[0][1]):
        order = fmt.layout.major_to_minor
        assert order == tuple(range(len(order))), fmt
    held = step.enter.out_info["k"].shape
    shapes = {f"bf16[{batch},{max_len},{cfg.n_kv_heads},{cfg.hd}]",
              "bf16[{}]".format(",".join(map(str, cache["k"].shape))),
              "bf16[{}]".format(",".join(map(str, held)))}
    comps = _computations(step.run.as_text())
    writes = []
    for lines in comps.values():
        for line in lines:
            m = re.match(r"(?:ROOT )?%(\S+) = (\w+\[[\d,]*\])\S* "
                         r"(copy|fusion)\(", line)
            if m and m.group(2) in shapes:
                writes.append((m.group(1), m.group(3),
                               re.search(r"calls=%([\w.-]+)", line)))
    assert len(writes) == 2, writes
    for name, op, called in writes:
        assert op == "fusion" and called, name
        root = [ln for ln in comps[called.group(1)] if ln.startswith("ROOT")]
        assert " dynamic-update-slice(" in root[0], (name, root)


@pytest.mark.parametrize("arch", ["olmoe_1b_7b", "jamba_v01_52b"])
def test_served_experts_stay_on_their_chips_on_a_mesh(topo, arch):
    """On the described 2x2 mesh, with each chip holding its share of the
    experts as ``param_shardings`` places them, the serving prefill and
    decode step gather no layer's experts onto one chip: each chip runs
    its own experts and one psum adds the parts. (GSPMD alone gathers
    every expert's weights for ``ragged_dot``.)"""
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_axis_rules
    from repro.launch.shardings import param_shardings
    from repro.models import prefill
    from repro.parallel.logical import use_rules

    cfg = get_config(arch, smoke=True)
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=topo.devices)
    batch, prompt = 4, 64

    def placed(tree, shardings):
        return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), tree, shardings)

    whole = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("data"))
    with mesh, use_rules(make_axis_rules(mesh, cfg), mesh):
        params = placed(jax.eval_shape(
            lambda k: init_params(cfg, k),
            jax.ShapeDtypeStruct((2,), jnp.uint32)),
            param_shardings(cfg, mesh))
        cache = jax.eval_shape(lambda: init_cache(cfg, batch, prompt))
        cache = placed(cache, jax.tree.map(lambda _: whole, cache))
        prompts = jax.ShapeDtypeStruct((batch, prompt), jnp.int32,
                                       sharding=rows)
        token = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=rows)
        pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=whole)
        programs = [
            jax.jit(lambda p, t: prefill(cfg, p, t)).lower(params, prompts),
            jax.jit(lambda p, c, t, i: decode_step(cfg, p, c, t, i)).lower(
                params, cache, token, pos)]
        hlos = [lowered.compile().as_text() for lowered in programs]

    one_layer = cfg.moe_experts * cfg.d_model * cfg.d_ff
    for hlo in hlos:
        assert "psum" in hlo or "all-reduce" in hlo
        for line in hlo.splitlines():
            if " all-gather(" in line or " all-gather-start(" in line:
                out = line.split("=", 1)[1].split(" all-gather")[0]
                for dims in re.findall(r"\w+\[([\d,]*)\]", out):
                    size = math.prod(int(n) for n in dims.split(",") if n)
                    assert size < one_layer, ("experts gathered", line)

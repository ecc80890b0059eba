"""Correctness of the §Perf hillclimb knobs (EXPERIMENTS.md §Perf):
FSDP sharding, shard_map MoE dispatch, mixed precision, bf16 matmuls."""
from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params, synth_batch
from repro.train.optimizer import AdamWConfig, adamw_init, adamw_update

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, f"OUT:\n{proc.stdout}\nERR:\n{proc.stderr}"
    return proc.stdout


def test_moe_shard_map_matches_gspmd_dispatch():
    """The hand-scheduled EP dispatch must equal the GSPMD capacity-buffer
    path bit-for-tolerance (same routing, same drops)."""
    _run("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.models import layers as L
    from repro.parallel.logical import use_rules
    from repro.launch.mesh import make_axis_rules, make_mesh

    cfg = get_config("olmoe_1b_7b", smoke=True)
    p = L.init_moe(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 16, cfg.d_model),
                          jnp.float32)
    ref = L.moe(p, x, cfg)                      # no mesh: gspmd path

    mesh = make_mesh((2, 4), ("data", "model"))
    cfg_sm = dataclasses.replace(cfg, moe_dispatch="shard_map")
    rules = make_axis_rules(mesh)
    with mesh, use_rules(rules, mesh):
        out = jax.jit(lambda pp, xx: L.moe(pp, xx, cfg_sm))(p, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    print("moe shard_map OK")
    """)


def test_fsdp_shards_every_large_param():
    _run("""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.launch.shardings import param_shardings

    cfg = get_config("olmo_1b", smoke=True)
    mesh = make_mesh((2, 4), ("data", "model"))
    base = param_shardings(cfg, mesh, fsdp=False)
    fsdp = param_shardings(cfg, mesh, fsdp=True)
    n_more = 0
    for (pb, b), (pf, f) in zip(
            jax.tree_util.tree_leaves_with_path(base),
            jax.tree_util.tree_leaves_with_path(fsdp)):
        flat_b = [a for a in b.spec if a is not None]
        flat_f = [a for a in f.spec if a is not None]
        assert len(flat_f) >= len(flat_b)
        n_more += len(flat_f) > len(flat_b)
    assert n_more >= 5, n_more   # the big matrices picked up the data axis
    print("fsdp shardings OK", n_more)
    """)


def test_fsdp_train_step_matches_baseline_loss():
    """FSDP changes layout, not math: same loss as the replicated step."""
    _run("""
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.models import init_params, synth_batch
    from repro.parallel.logical import use_rules
    from repro.launch.mesh import make_axis_rules, make_mesh
    from repro.launch.shardings import (batch_shardings, opt_shardings,
                                        param_shardings)
    from repro.train.optimizer import AdamWConfig, adamw_init
    from repro.train.trainer import make_train_step

    cfg = get_config("olmo_1b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = synth_batch(cfg, 8, 32)
    opt = adamw_init(params)
    step = make_train_step(cfg, AdamWConfig(lr=1e-3))
    _, _, m_ref = jax.jit(step)(params, opt, batch)

    mesh = make_mesh((2, 4), ("data", "model"))
    with mesh, use_rules(make_axis_rules(mesh), mesh):
        ps = param_shardings(cfg, mesh, fsdp=True)
        os_ = opt_shardings(cfg, mesh, fsdp=True)
        bs = batch_shardings(cfg, mesh, 8)
        sp = jax.device_put(params, ps)
        so = jax.device_put(opt, os_)
        sb = {k: jax.device_put(v, bs[k]) for k, v in batch.items()}
        _, _, m = jax.jit(step, in_shardings=(ps, os_, bs),
                          out_shardings=(ps, os_, None))(sp, so, sb)
    assert abs(float(m["loss"]) - float(m_ref["loss"])) < 1e-2
    print("fsdp step OK", float(m["loss"]))
    """)


def test_mixed_precision_tracks_fp32_training():
    cfg = get_config("olmo_1b", smoke=True)
    batch = synth_batch(cfg, 2, 32)
    ocfg = AdamWConfig(lr=1e-3, weight_decay=0.0)
    from repro.train.trainer import make_train_step

    p32 = init_params(cfg, jax.random.PRNGKey(0))
    o32 = adamw_init(p32)
    s32 = jax.jit(make_train_step(cfg, ocfg))

    cfg16 = dataclasses.replace(cfg, param_dtype="bfloat16")
    p16 = init_params(cfg16, jax.random.PRNGKey(0))
    o16 = adamw_init(p16, master=True)
    s16 = jax.jit(make_train_step(cfg16, ocfg))

    for _ in range(5):
        p32, o32, m32 = s32(p32, o32, batch)
        p16, o16, m16 = s16(p16, o16, batch)
    assert float(m16["loss"]) == pytest.approx(float(m32["loss"]), rel=0.05)
    # master stays fp32 and close to the fp32 run's params
    master_leaf = jax.tree.leaves(o16["master"])[0]
    assert master_leaf.dtype == jnp.float32


def test_bf16_matmul_out_close_to_default():
    cfg = get_config("olmo_1b", smoke=True)
    cfg16 = dataclasses.replace(cfg, matmul_out="bf16")
    from repro.models import loss_fn
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = synth_batch(cfg, 2, 32)
    l_a = float(jax.jit(lambda p, b: loss_fn(cfg, p, b))(params, batch))
    l_b = float(jax.jit(lambda p, b: loss_fn(cfg16, p, b))(params, batch))
    assert l_b == pytest.approx(l_a, rel=0.02)


def test_remat_policies_equal_forward_and_grads():
    cfg = get_config("olmo_1b", smoke=True)
    from repro.models import loss_fn
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = synth_batch(cfg, 2, 32)
    grads = {}
    for pol in ("full", "dots", "none"):
        c = dataclasses.replace(cfg, remat=pol)
        loss, g = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(c, p, batch)))(params)
        grads[pol] = (float(loss), g)
    l0 = grads["full"][0]
    for pol in ("dots", "none"):
        assert grads[pol][0] == pytest.approx(l0, rel=1e-4)
        for a, b in zip(jax.tree.leaves(grads["full"][1]),
                        jax.tree.leaves(grads[pol][1])):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=1e-2, atol=1e-4)


def test_context_parallel_decode_matches_gspmd():
    """cfg.decode_attn='context_parallel' (shard_map LSE-combine over the
    seq-sharded KV cache) must match the GSPMD decode path."""
    _run("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.models import decode_step, init_cache, init_params
    from repro.parallel.logical import use_rules
    from repro.launch.mesh import make_axis_rules, make_mesh

    cfg = get_config("mistral_nemo_12b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    b, smax = 4, 64
    cache = init_cache(cfg, b, smax)
    cache["k"] = jax.random.normal(jax.random.PRNGKey(1), cache["k"].shape,
                                   cache["k"].dtype) * 0.3
    cache["v"] = jax.random.normal(jax.random.PRNGKey(2), cache["v"].shape,
                                   cache["v"].dtype) * 0.3
    tok = jax.random.randint(jax.random.PRNGKey(3), (b,), 0, cfg.vocab)
    pos = jnp.int32(17)
    ref, _ = jax.jit(lambda p, c: decode_step(cfg, p, c, tok, pos))(
        params, cache)

    mesh = make_mesh((2, 4), ("data", "model"))
    cfg_cp = dataclasses.replace(cfg, decode_attn="context_parallel")
    with mesh, use_rules(make_axis_rules(mesh), mesh):
        got, _ = jax.jit(lambda p, c: decode_step(cfg_cp, p, c, tok, pos))(
            params, cache)
    # bf16 cache + different accumulation order: tolerance is dtype noise
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=6e-2, atol=6e-2)
    agree = (np.asarray(got).argmax(-1) == np.asarray(ref).argmax(-1)).mean()
    assert agree == 1.0, agree
    print("cp-decode OK")
    """)

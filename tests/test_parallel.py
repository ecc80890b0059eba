"""Distributed-execution integration tests.

JAX fixes the device count at first init, so multi-device cases run in
subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count=8. Each
script asserts internally and exits nonzero on failure.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    script = textwrap.dedent(body)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nERR:\n{proc.stderr}"
    return proc.stdout


def test_pipeline_forward_matches_sequential():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.parallel.pipeline import pipeline_forward
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4,), ("stage",))
    n_stages, n_micro, mb, d = 4, 8, 2, 16
    key = jax.random.PRNGKey(0)
    params = jax.random.normal(key, (n_stages, d, d)) * 0.3
    xs = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    with mesh:
        run = pipeline_forward(mesh, stage_fn, n_stages, axis="stage")
        out = run(params, xs)

    ref = xs
    for s in range(n_stages):
        ref = jnp.tanh(ref @ params[s])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    print("pipeline OK")
    """)


def test_context_parallel_decode_matches_dense():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.parallel.context import context_parallel_decode
    from repro.kernels.decode_attention.ref import decode_attention_ref
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((8,), ("model",))
    b, h, s, hd = 2, 4, 1024, 64
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (b, h, hd), jnp.float32)
    k = jax.random.normal(kk, (b, h, s, hd), jnp.float32)
    v = jax.random.normal(kv, (b, h, s, hd), jnp.float32)
    kv_len = jnp.int32(777)

    fn = context_parallel_decode(mesh, axis="model")
    with mesh:
        out = fn(q, k, v, kv_len)
    ref = decode_attention_ref(q, k, v, 777)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    print("context parallel OK")
    """)


def test_sharded_train_step_matches_single_device():
    """The production sharding assembly (param/batch shardings on a (2, 4)
    mesh) must compute the same loss and updates as single-device."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.models import init_params, loss_fn, synth_batch
    from repro.parallel.logical import use_rules
    from repro.launch.mesh import make_axis_rules, make_mesh
    from repro.launch.shardings import batch_shardings, param_shardings
    from repro.train.optimizer import AdamWConfig, adamw_init
    from repro.train.trainer import make_train_step

    cfg = get_config("olmo_1b", smoke=True)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    batch = synth_batch(cfg, batch=8, seq=32)
    opt = adamw_init(params)
    step = make_train_step(cfg, AdamWConfig(lr=1e-3))

    # single device reference
    p_ref, o_ref, m_ref = jax.jit(step)(params, opt, batch)

    mesh = make_mesh((2, 4), ("data", "model"))
    rules = make_axis_rules(mesh)
    with mesh, use_rules(rules):
        ps = param_shardings(cfg, mesh)
        bs = batch_shardings(cfg, mesh, 8)
        os_ = {"m": ps, "v": ps, "step": NamedSharding(mesh, P())}
        sp = jax.device_put(params, ps)
        sb = {k: jax.device_put(v, bs[k]) for k, v in batch.items()}
        so = jax.device_put(opt, os_)
        p_sh, o_sh, m_sh = jax.jit(step, in_shardings=(ps, os_, bs),
                                   out_shardings=(ps, os_, None))(sp, so, sb)

    assert abs(float(m_ref["loss"]) - float(m_sh["loss"])) < 1e-2, (
        float(m_ref["loss"]), float(m_sh["loss"]))
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(jax.device_get(b), np.float32),
                                   rtol=3e-2, atol=3e-3)
    print("sharded train step OK")
    """)


def test_dp_grad_allreduce_emitted():
    """Data-parallel training must emit a gradient all-reduce in the
    compiled HLO — and hlocost must find and price it."""
    out = _run("""
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch import hlocost
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((8,), ("data",))
    w = jnp.zeros((64, 64))

    def step(w, x):
        def loss(w):
            return jnp.sum((x @ w) ** 2)
        g = jax.grad(loss)(w)
        return w - 0.1 * g

    xs = NamedSharding(mesh, P("data", None))
    ws = NamedSharding(mesh, P())
    with mesh:
        comp = jax.jit(step, in_shardings=(ws, xs),
                       out_shardings=ws).lower(
            jax.ShapeDtypeStruct((64, 64), jnp.float32),
            jax.ShapeDtypeStruct((128, 64), jnp.float32)).compile()
    s = hlocost.analyze(comp.as_text())
    ar = s.collective_bytes.get("all-reduce", 0.0)
    assert ar >= 64 * 64 * 4, s.collective_bytes
    print("AR_BYTES", ar)
    """)
    assert "AR_BYTES" in out


def test_moe_expert_parallel_lowms_to_collectives():
    """Expert-sharded MoE under GSPMD must produce collective ops."""
    _run("""
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import init_params, loss_fn, synth_batch
    from repro.parallel.logical import use_rules
    from repro.launch.mesh import make_axis_rules, make_mesh
    from repro.launch.shardings import batch_shardings, param_shardings
    from repro.launch import hlocost

    cfg = get_config("olmoe_1b_7b", smoke=True)
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = make_axis_rules(mesh)
    with mesh, use_rules(rules):
        ps = param_shardings(cfg, mesh)
        bs = batch_shardings(cfg, mesh, 8)
        pspec = jax.eval_shape(lambda k: init_params(cfg, k),
                               jax.ShapeDtypeStruct((2,), jnp.uint32))
        from repro.models.inputs import train_batch_specs
        specs = train_batch_specs(cfg, 8, 32)
        comp = jax.jit(lambda p, b: loss_fn(cfg, p, b),
                       in_shardings=(ps, bs)).lower(pspec, specs).compile()
    s = hlocost.analyze(comp.as_text())
    total = s.total_collective_bytes
    assert total > 0, "expert parallelism emitted no collectives"
    print("EP collective bytes", total)
    """)


@pytest.mark.parametrize("arch", ["olmoe_1b_7b", "jamba_v01_52b"])
def test_dropless_moe_serves_on_a_mesh_as_on_one_device(arch):
    """A MoE config served on a (2, 4) mesh, its experts split over 'model'
    as ``param_shardings`` places them: prefill and three decode steps give
    the single-device logits, and the launcher serves the single-device
    engine's greedy tokens (that no expert is gathered onto a chip is
    checked on the TPU's compiler, ``test_tpu_compile.py``). Float32,
    so sharding moves only the order of summation (about 1e-5 on logits of
    about 4; a lost or doubled expert moves them by 1e-1 and more)."""
    _run("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.models import decode_step, init_params, prefill
    from repro.parallel.logical import use_rules
    from repro.launch.mesh import make_axis_rules, make_mesh
    from repro.launch.serve import run_serve
    from repro.launch.shardings import param_shardings
    from repro.serve.engine import ServeEngine

    cfg = dataclasses.replace(get_config("ARCH", smoke=True),
                              dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                 cfg.vocab)

    def serve(params):
        fill = jax.jit(lambda p, t: prefill(cfg, p, t))
        step = jax.jit(lambda p, c, t, pos: decode_step(cfg, p, c, t, pos))
        logits, cache = fill(params, prompts)
        room = [(0, 0)] * 3 + [(0, 3), (0, 0), (0, 0)]   # 3 more K/V rows
        cache.update({n: jnp.pad(cache[n], room) for n in ("k", "v")
                      if n in cache})
        out, tok = [logits], jnp.argmax(logits[:, -1], -1)
        for pos in range(16, 19):
            logits, cache = step(params, cache, tok, jnp.int32(pos))
            out.append(logits)
            tok = jnp.argmax(logits, -1)
        return out

    ref = serve(params)
    mesh = make_mesh((2, 4), ("data", "model"))
    with mesh, use_rules(make_axis_rules(mesh, cfg), mesh):
        got = serve(jax.device_put(params, param_shardings(cfg, mesh)))
    for a, b in zip(ref, got):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=0, atol=1e-4)

    engine = ServeEngine(cfg, params, max_batch=4, max_len=16 + 8 + 1)
    want = engine.generate(prompts, n_tokens=8).tokens
    assert run_serve(cfg, tokens=8, mesh_spec="2x4").tokens == want
    print("mesh serving OK")
    """.replace("ARCH", arch))

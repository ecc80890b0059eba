"""Elastic scaling: a checkpoint written under one mesh restores onto a
different topology (the restart-after-resize path of a multi-pod job)."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, devices: int = 8):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, f"OUT:\n{proc.stdout}\nERR:\n{proc.stderr}"
    return proc.stdout


def test_checkpoint_elastic_across_mesh_shapes(tmp_path):
    """Save on a (4, 2) mesh with FSDP; restore onto (2, 4) and keep
    training — losses must continue from the same state."""
    ckpt = tmp_path / "ckpt"
    script = f"""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.models import init_params, synth_batch
    from repro.parallel.logical import use_rules
    from repro.launch.mesh import make_axis_rules, make_mesh
    from repro.launch.shardings import (batch_shardings, opt_shardings,
                                        param_shardings)
    from repro.train.checkpoint import CheckpointManager
    from repro.train.optimizer import AdamWConfig, adamw_init
    from repro.train.trainer import make_train_step

    cfg = get_config("olmo_1b", smoke=True)
    step = make_train_step(cfg, AdamWConfig(lr=1e-3))
    batches = [synth_batch(cfg, 8, 32, seed=s) for s in range(4)]
    mgr = CheckpointManager({str(ckpt)!r})

    def run_on(shape, params, opt, batches):
        mesh = make_mesh(shape, ("data", "model"))
        with mesh, use_rules(make_axis_rules(mesh), mesh):
            ps = param_shardings(cfg, mesh, fsdp=True)
            os_ = opt_shardings(cfg, mesh, fsdp=True)
            bs = batch_shardings(cfg, mesh, 8)
            p = jax.device_put(params, ps)
            o = jax.device_put(opt, os_)
            fn = jax.jit(step, in_shardings=(ps, os_, bs),
                         out_shardings=(ps, os_, None))
            losses = []
            for b in batches:
                sb = {{k: jax.device_put(v, bs[k]) for k, v in b.items()}}
                p, o, m = fn(p, o, sb)
                losses.append(float(m["loss"]))
            return jax.device_get(p), jax.device_get(o), losses

    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)

    # reference: all four steps on the (4,2) mesh
    _, _, ref = run_on((4, 2), params, opt, batches)

    # elastic: two steps on (4,2), checkpoint, resize to (2,4), resume
    p1, o1, l1 = run_on((4, 2), params, opt, batches[:2])
    mgr.save(2, {{"params": p1, "opt": o1}})
    _, tree = mgr.restore(2)
    tree["opt"]["step"] = jnp.asarray(tree["opt"]["step"], jnp.int32)
    _, _, l2 = run_on((2, 4), tree["params"], tree["opt"], batches[2:])

    got = l1 + l2
    np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4)
    print("elastic resume OK", got)
    """
    _run(script)

"""Production training launcher: mesh + shardings + trainer on real devices.

Builds a (data, model) mesh from whatever devices exist (host CPUs, one TPU
pod slice, ...), applies the production sharding rules (optionally FSDP),
and runs the synthetic-data training loop with checkpointing.

:func:`run_train` is the importable body (no argv, no subprocess); ``main``
is the argparse shell.

  # 8 host devices:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \\
      python -m repro.launch.train --arch olmo_1b --smoke --steps 20 \\
      --mesh 2x4 --fsdp
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from ..configs import ARCH_IDS, get_config
from ..models import init_params, param_count, synth_batch
from ..models.config import ModelConfig
from ..parallel.logical import use_rules
from ..train.checkpoint import CheckpointManager
from ..train.fault import StragglerMonitor
from ..train.optimizer import AdamWConfig, adamw_init
from ..train.trainer import make_train_step
from .compile_cache import use_compile_cache
from .mesh import make_axis_rules, make_mesh
from .shardings import batch_shardings, opt_shardings, param_shardings


def parse_mesh(spec: str | None):
    devs = jax.devices()
    if spec:
        shape = tuple(int(x) for x in spec.split("x"))
    else:
        shape = (max(1, len(devs) // 2), min(2, len(devs)))
    axes = ("data", "model") if len(shape) == 2 else ("pod", "data", "model")
    return make_mesh(shape, axes)


@dataclasses.dataclass
class TrainResult:
    params: dict                 # final params, placed by the mesh shardings
    losses: list[float]          # per step; step 0's is the initial params'


def run_train(cfg: ModelConfig, steps: int = 20, batch: int = 8,
              seq: int = 128, accum: int = 1, mesh_spec: str | None = None,
              fsdp: bool = False, bf16_params: bool = False,
              ckpt_dir: str | None = None, ckpt_every: int = 0
              ) -> TrainResult:
    """Initialize params on the mesh and run ``steps`` training steps on
    synthetic batches (``synth_batch(cfg, batch, seq, seed=step)``).
    Deterministic: params are ``init_params(cfg, PRNGKey(0))``.
    """
    if bf16_params:
        cfg = dataclasses.replace(cfg, param_dtype="bfloat16")
    mesh = parse_mesh(mesh_spec)
    rules = make_axis_rules(mesh, cfg)
    print(f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"on {len(jax.devices())} {jax.devices()[0].platform} devices")

    losses: list[float] = []
    with mesh, use_rules(rules, mesh):
        ps = param_shardings(cfg, mesh, fsdp=fsdp)
        os_ = opt_shardings(cfg, mesh, fsdp=fsdp, master=bf16_params)
        bs = batch_shardings(cfg, mesh, batch)
        params = jax.device_put(init_params(cfg, jax.random.PRNGKey(0)), ps)
        opt = jax.device_put(adamw_init(params, master=bf16_params), os_)
        print(f"{cfg.name}: {param_count(params):,} params "
              f"({'fsdp' if fsdp else 'replicated over data'})")
        step_fn = jax.jit(
            make_train_step(cfg, AdamWConfig(lr=3e-4), accum=accum),
            in_shardings=(ps, os_, bs), out_shardings=(ps, os_, None),
            donate_argnums=(0, 1))

        mgr = CheckpointManager(ckpt_dir) if ckpt_every else None
        mon = StragglerMonitor()
        for step in range(steps):
            data = synth_batch(cfg, batch, seq, seed=step)
            data = {k: jax.device_put(v, bs[k]) for k, v in data.items()}
            t0 = time.perf_counter()
            params, opt, metrics = step_fn(params, opt, data)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            losses.append(loss)
            mon.record(step, dt)
            if step % 5 == 0 or step == steps - 1:
                print(f"step {step:4d}  loss {loss:7.4f}  "
                      f"{dt * 1e3:8.1f} ms")
            if mgr and (step + 1) % ckpt_every == 0:
                mgr.save_async(step + 1, {"params": params, "opt": opt})
        if mgr:
            mgr.wait()
    print("done")
    return TrainResult(params=params, losses=losses)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="olmo_1b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--mesh", help="e.g. 2x4 (data x model)")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--bf16-params", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    ap.add_argument("--ckpt-every", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()
    run_train(get_config(args.arch, smoke=args.smoke), steps=args.steps,
              batch=args.batch, seq=args.seq, accum=args.accum,
              mesh_spec=args.mesh, fsdp=args.fsdp,
              bf16_params=args.bf16_params, ckpt_dir=args.ckpt_dir,
              ckpt_every=args.ckpt_every)


if __name__ == "__main__":
    main()

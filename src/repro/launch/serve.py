"""Serving launcher: mesh + cache shardings + batched generation.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \\
      python -m repro.launch.serve --arch olmo_1b --smoke --requests 4

:func:`run_serve` is the importable body — validation and tests call it
in-process (no argv, no subprocess); ``main`` is the argparse shell.
"""
from __future__ import annotations

import argparse

import jax

from ..configs import ARCH_IDS, get_config
from ..models import init_params
from ..models.config import ModelConfig
from ..parallel.logical import use_rules
from ..serve.engine import GenerationResult, ServeEngine
from .compile_cache import use_compile_cache
from .mesh import make_axis_rules
from .train import parse_mesh


def run_serve(cfg: ModelConfig, requests: int = 4, prompt_len: int = 16,
              tokens: int = 16, mesh_spec: str | None = None,
              seed: int = 0) -> GenerationResult:
    """Initialize params on the mesh, serve one batched generation, return
    its timings. Deterministic in ``seed`` (params and prompts)."""
    mesh = parse_mesh(mesh_spec)
    rules = make_axis_rules(mesh, cfg)
    with mesh, use_rules(rules, mesh):
        params = init_params(cfg, jax.random.PRNGKey(seed))
        engine = ServeEngine(cfg, params, max_batch=requests,
                             max_len=prompt_len + tokens + 1)
        prompts = jax.random.randint(
            jax.random.PRNGKey(seed + 1), (requests, prompt_len),
            0, cfg.vocab)
        res = engine.generate(prompts, n_tokens=tokens)
    print(f"{cfg.name} on mesh "
          f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")
    print(f"TTFT {res.ttft * 1e3:.1f} ms  TPOT {res.tpot * 1e3:.2f} ms "
          f" throughput {res.tokens_per_s:.1f} tok/s")
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="olmo_1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--mesh")
    args = ap.parse_args()
    use_compile_cache()
    run_serve(get_config(args.arch, smoke=args.smoke),
              requests=args.requests, prompt_len=args.prompt_len,
              tokens=args.tokens, mesh_spec=args.mesh)


if __name__ == "__main__":
    main()

"""DFModel plan → real sharded execution, closing the loop on 8 host devices.

1. DFModel's planner analyzes the architecture's dataflow graph and predicts
   the mapping's bottleneck.
2. The launcher builds the mesh + shardings and jit-compiles the real
   train step.
3. The trip-count-aware HLO cost model extracts the compiled collective
   schedule, which is compared against DFModel's prediction.

  PYTHONPATH=src python examples/plan_and_launch.py --arch olmoe_1b_7b
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse   # noqa: E402

import jax        # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo_1b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.launch import hlocost
    from repro.launch.mesh import make_axis_rules, make_mesh
    from repro.launch.shardings import batch_shardings, param_shardings
    from repro.models import init_params, loss_fn, synth_batch
    from repro.parallel.logical import use_rules

    cfg = get_config(args.arch, smoke=True)

    # --- 1. analytical plan (one block of the real architecture) -----------
    from repro.launch.plan import block_graph, v5e_system
    from repro.core.sharding import solve_sharding
    from repro.core.intrachip import optimize_intra_chip
    sys_ = v5e_system()
    g = block_graph(get_config(args.arch), 4096, 16)
    sol = solve_sharding(g, 16, sys_.topology, [0])
    sharded = g.scaled(1 / 16, 1 / 16)
    pred = optimize_intra_chip(sharded, sys_.chip, sys_.memory,
                               h_n=sol.h_n, h_m=sol.h_m)
    print(f"DFModel prediction for {args.arch} (one block, TP=16):")
    print(f"  bottleneck={pred.bottleneck}  partitions={pred.n_partitions}  "
          f"comm bytes/block={sol.comm_bytes / 1e6:.1f} MB")

    # --- 2. real sharded step on the local 2x4 mesh ------------------------
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = make_axis_rules(mesh, cfg)
    with mesh, use_rules(rules, mesh):
        ps = param_shardings(cfg, mesh)
        bs = batch_shardings(cfg, mesh, args.batch)
        params = jax.device_put(init_params(cfg, jax.random.PRNGKey(0)), ps)
        batch = synth_batch(cfg, args.batch, args.seq)
        batch = {k: jax.device_put(v, bs[k]) for k, v in batch.items()}
        step = jax.jit(lambda p, b: loss_fn(cfg, p, b),
                       in_shardings=(ps, bs))
        compiled = step.lower(params, batch).compile()
        loss = compiled(params, batch)
    print(f"\nreal sharded step on {mesh.devices.shape} mesh: "
          f"loss={float(loss):.4f}")

    # --- 3. compiled collective schedule vs the model -----------------------
    s = hlocost.analyze(compiled.as_text())
    print("\ncompiled collective schedule (top 5):")
    for rec in hlocost.collective_schedule(s, top=5):
        print(f"  {rec['kind']:>20s}  {rec['payload_bytes'] / 1e6:8.2f} MB "
              f"x{rec['trips']:.0f} trips  (S={rec['participants']})")
    print(f"total per-device link traffic: "
          f"{s.link_traffic_bytes / 1e6:.1f} MB")


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Smoke check of the system's main path on the TPU.

  python3 chip_smoke.py             # one chip: the DSE fast path, then a
                                    # full-width olmo_1b server
  python3 chip_smoke.py --chips 4   # four chips: olmo_1b FSDP training on
                                    # a 2x2 mesh against one chip

Everything runs in this one process: a chip belongs to one process, and
the DSE engine's pool workers plan with numpy, pinned to the CPU. Each
phase checks its own result, and any failure exits non-zero. The last line
of standard output is one JSON object naming the device; nothing is printed
there unless every phase passed. Without a TPU the script stops before any
phase. Timings printed here are single-run smoke readings, not benchmark
numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import warnings
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

#: Serving reference tolerance on max|Δlogit| / std(logits), chip (bf16
#: activations, f32 params) against the same params in f32 on the host CPU.
#: Rounding the LM head's two operands to bf16 (unit roundoff 2^-8) alone
#: moves each logit by ~0.007 std; the maximum over 32 x 50,304 logits sits
#: ~6 sigma out, 0.044-0.047 on the host's own bf16 at full width and 1-4
#: layers, barely growing with depth because every layer re-normalizes. 0.1
#: leaves 2x for the chip's own bf16 rounding; a wrong weight, layer or
#: position puts the ratio near 1 or above.
LOGIT_TOL = 0.1

#: Four-chip reference tolerance on |Δ loss| at step 0. The loss is a mean
#: over 8 x 1,024 tokens of an f32 log-sum-exp over bf16 logits, and a
#: sound layout change only reorders reductions: 4.6e-5 on four v5e chips.
#: A fault on the data axis moves it further: the three steps' losses there,
#: each on its own batch, were 11.3297, 11.3129 and 11.3236, a spread of
#: 0.017, so a run that drops one data shard or repeats it in place of the
#: other moves the step-0 loss by about 0.008. 1e-3 sits 20x above the
#: sound reading and well below that shift.
LOSS_TOL = 1e-3


def require_tpu(count: int) -> list:
    """The device phase: a TPU, with at least ``count`` chips."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU: JAX's devices are "
                         f"{len(devs)} x {devs[0].platform}; this check runs "
                         f"on the chip only")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips, JAX "
                         f"found {len(devs)}")
    print(f"device: {devs[0].device_kind}, count {len(devs)}", flush=True)
    return devs


def check_pricing_compiled(n_rows: int) -> None:
    """The compiled f32 pricing kernel resolves to its real lowering, not
    the interpret-mode twin: the flag ``run_columns_f32`` resolves for
    ``interpret="auto"`` is False, and the program it ran for a chunk of
    ``n_rows`` (the same cached callable, on the same argument shapes)
    lowers to the Mosaic kernel (``tpu_custom_call``)."""
    from repro.core.pricing import FIELDS, _price
    from repro.kernels.pricing.kernel import resolve_interpret
    from repro.kernels.pricing.ops import lower_f32

    if resolve_interpret("auto"):
        raise AssertionError("pallas-compiled resolves to interpret mode "
                             f"on backend {jax.default_backend()!r}")
    hlo = lower_f32(_price, FIELDS, n_rows).as_text()
    if "tpu_custom_call" not in hlo:
        raise AssertionError("compiled pricing lowering holds no "
                             "tpu_custom_call")
    print(f"dse: pricing kernel compiled (interpret=False, tpu_custom_call "
          f"at {n_rows} rows)", flush=True)


def dse_phase(grid_spec, sweep_scenario: str = "llm",
              workers: int = 4) -> dict:
    """The DSE fast path on ``pallas-compiled``.

    1. ``reprice_grid`` over ``grid_spec`` for the ``llm`` scenario; it
       certifies every group's winners or raises.
    2. A parallel phased sweep of the smoke ``sweep_scenario`` on a warm
       session pool: pool workers plan, this process prices. A pool that
       falls back to serial is an error here, and its rows must equal the
       serial numpy reference sweep's. Then every worker of that pool is
       probed (:func:`worker_platforms`): each must be pinned to the CPU
       and have initialized no backend but the CPU's.
    """
    from repro.core import DSEEngine, clear_caches
    from repro.workloads.scenarios import get_scenario

    grid = DSEEngine(parallel=False, pricing_backend="pallas-compiled"
                     ).reprice_grid(get_scenario("llm").work_fn, grid_spec)
    if not grid["winners_identical"] or grid["backend"] != "pallas-compiled":
        raise AssertionError(f"reprice_grid not certified: {grid}")
    drift = grid["drift"]
    print(f"dse: reprice_grid cells {grid['cells']}, groups {grid['groups']}, "
          f"enumerated {grid['enumerated']}, priced rows "
          f"{grid['priced_rows']} in {grid['chunks']} chunk(s), drift band "
          f"{drift['band']}: repriced {drift['repriced']} of {drift['rows']} "
          f"rows, ambiguous_mem {drift['ambiguous_mem']}, max_iter_drift "
          f"{drift['max_iter_drift']}, max_mem_drift "
          f"{drift['max_mem_drift']}; plan_s {grid['plan_s']}, price_s "
          f"{grid['price_s']}; winners_identical True", flush=True)

    sc = get_scenario(sweep_scenario, smoke=True)
    clear_caches()
    with warnings.catch_warnings():
        for message in ("parallel sweep unavailable",
                        "warm session pool unavailable"):
            warnings.filterwarnings("error", message=message,
                                    category=RuntimeWarning)
        with DSEEngine(parallel=True, max_workers=workers,
                       pricing_backend="pallas-compiled") as engine:
            t0 = time.perf_counter()
            rows = [p.row() for p in engine.sweep(sc.work_fn, sc.spec)]
            sweep_s = time.perf_counter() - t0
            seen = list(engine._session_pool.map(
                worker_platforms, range(8 * workers), chunksize=1))
    clear_caches()
    pids = {s[0] for s in seen}
    reports = {s[1:] for s in seen}
    if len(pids) != workers or reports != {("cpu", "cpu", ("cpu",))}:
        raise AssertionError(f"pool workers not pinned to the CPU: "
                             f"{len(pids)} of {workers} probed, (environ, "
                             f"config, backends) {reports}")
    ref = [p.row() for p in DSEEngine(parallel=False, pricing_backend="numpy"
                                      ).sweep(sc.work_fn, sc.spec)]
    if rows != ref:
        raise AssertionError(f"parallel pallas-compiled sweep of "
                             f"{sweep_scenario} differs from the numpy "
                             f"reference ({len(rows)} vs {len(ref)} rows)")
    stats = engine.last_plan_stats
    if stats["backend"] != "pallas-compiled" or not stats["verified"]:
        raise AssertionError(f"sweep not priced on pallas-compiled: {stats}")
    print(f"dse: parallel sweep {sweep_scenario} (smoke), {workers} "
          f"workers: {len(rows)} rows identical to the numpy reference, "
          f"plan groups {stats['groups']}, priced {stats['priced']} on "
          f"{stats['backend']}; {sweep_s} s; every worker pinned to the CPU "
          f"(JAX_PLATFORMS, config, backends {sorted(reports)[0]})",
          flush=True)
    return grid


def worker_platforms(_) -> tuple:
    """Run in a DSE pool worker: its pid, its ``JAX_PLATFORMS``, the
    platform its JAX is pinned to, and the backends initialized once it has
    asked for its devices, as a careless worker would. A worker that is not
    pinned reports without asking, so it never reaches for the chip."""
    from jax._src import xla_bridge

    pinned = jax.config.jax_platforms
    if pinned == "cpu":
        jax.devices()
    time.sleep(0.05)  # hold the task, so that every worker takes some
    return (os.getpid(), os.environ.get("JAX_PLATFORMS"), pinned,
            tuple(sorted(xla_bridge._backends)))


def serve_phase(cfg, requests: int = 8, prompt_len: int = 128,
                tokens: int = 64, ref_len: int = 32, seed: int = 0) -> float:
    """Serve one batch through ``run_serve``, then check the chip's prefill
    logits for one ``ref_len``-token prompt against the same params in f32
    on the host's CPU device. Returns max|Δlogit| / std(logits)."""
    from repro.launch.mesh import make_axis_rules
    from repro.launch.serve import run_serve
    from repro.launch.train import parse_mesh
    from repro.models import init_params, prefill
    from repro.parallel.logical import use_rules

    res = run_serve(cfg, requests=requests, prompt_len=prompt_len,
                    tokens=tokens, seed=seed)
    toks = np.asarray(res.tokens)
    if toks.shape != (tokens, requests):
        raise AssertionError(f"served token block {toks.shape}, expected "
                             f"{(tokens, requests)}")
    if toks.min() < 0 or toks.max() >= cfg.vocab:
        raise AssertionError(f"served tokens outside [0, {cfg.vocab}): "
                             f"[{toks.min()}, {toks.max()}]")
    print(f"serve: {cfg.name} {requests} requests x {prompt_len}-token "
          f"prompts, {tokens} new tokens each, all in [0, {cfg.vocab}); "
          f"one-run smoke reading: TTFT {res.ttft * 1e3} ms (first call, "
          f"compile included), TPOT {res.tpot * 1e3} ms", flush=True)

    prompt = jax.random.randint(jax.random.PRNGKey(seed + 2), (1, ref_len),
                                0, cfg.vocab)
    mesh = parse_mesh(None)
    with mesh, use_rules(make_axis_rules(mesh, cfg), mesh):
        params = init_params(cfg, jax.random.PRNGKey(seed))
        chip = jax.jit(lambda p, t: prefill(cfg, p, t)[0])(params, prompt)
    chip = np.asarray(chip, np.float32)
    cpu = jax.devices("cpu")[0]
    host_params = jax.device_put(params, cpu)
    del params
    ref_cfg = dataclasses.replace(cfg, dtype="float32")
    ref = np.asarray(jax.jit(lambda p, t: prefill(ref_cfg, p, t)[0])(
        host_params, jax.device_put(prompt, cpu)), np.float32)
    if not np.isfinite(chip).all():
        raise AssertionError("non-finite prefill logits on the chip")
    err = float(np.abs(chip - ref).max() / ref.std())
    mean_err = float(np.abs(chip - ref).mean() / ref.std())
    print(f"serve: prefill logits ({ref_len} tokens) vs f32 on "
          f"{cpu.platform}: max|dlogit|/std {err}, mean {mean_err} "
          f"(tolerance {LOGIT_TOL})", flush=True)
    if err > LOGIT_TOL:
        raise AssertionError(f"chip prefill logits off the f32 reference: "
                             f"max|dlogit|/std {err} > {LOGIT_TOL}")
    return err


def train_phase(cfg, mesh_spec: str = "2x2", steps: int = 3, batch: int = 8,
                seq: int = 1024) -> float:
    """FSDP training through ``run_train`` on ``mesh_spec``. Checks that the
    params are spread over every mesh device and compares the step-0 loss
    with the same params (``run_train``'s ``PRNGKey(0)``) and batch on one
    chip (forward only). Returns the |Δ loss|."""
    from repro.launch.train import run_train
    from repro.models import init_params, loss_fn, synth_batch

    res = run_train(cfg, steps=steps, batch=batch, seq=seq,
                    mesh_spec=mesh_spec, fsdp=True)
    if not np.isfinite(res.losses).all():
        raise AssertionError(f"non-finite training loss: {res.losses}")
    held: dict = {}
    total = 0
    for leaf in jax.tree.leaves(res.params):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            held[shard.device] = held.get(shard.device, 0) + shard.data.nbytes
    n_dev = int(np.prod([int(x) for x in mesh_spec.split("x")]))
    if len(held) != n_dev:
        raise AssertionError(f"params on {len(held)} devices, mesh has "
                             f"{n_dev}")
    most = max(held.values())
    if most > total / 2:
        raise AssertionError(f"params not sharded: one device holds {most} "
                             f"of {total} bytes")
    losses = res.losses
    del res
    print(f"train: {cfg.name} FSDP on a {mesh_spec} mesh, {steps} steps, "
          f"batch {batch} x {seq}: losses {losses}; params on {len(held)} "
          f"devices, at most {most} of {total} bytes on one; one-run smoke "
          f"reading of step times in run_train's lines above", flush=True)

    one = jax.devices()[0]
    with jax.default_device(one):
        params = init_params(cfg, jax.random.PRNGKey(0))
        data = synth_batch(cfg, batch, seq, seed=0)
        ref = float(jax.jit(lambda p, b: loss_fn(cfg, p, b))(params, data))
    delta = abs(losses[0] - ref)
    print(f"train: step-0 loss {losses[0]} vs one chip {ref}: "
          f"|dloss| {delta} (tolerance {LOSS_TOL})", flush=True)
    if delta > LOSS_TOL:
        raise AssertionError(f"sharded step-0 loss off the single-chip "
                             f"loss: {delta} > {LOSS_TOL}")
    return delta


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2 FSDP training path and its "
                         "single-chip comparison")
    args = ap.parse_args(argv)

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    devs = require_tpu(args.chips)
    olmo = get_config("olmo_1b")
    if args.chips == 4:
        train_phase(olmo)
    else:
        from repro.search import DenseGridSpec

        grid = dse_phase(DenseGridSpec.dense().spec())
        check_pricing_compiled(grid["priced_rows"])
        serve_phase(olmo)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this produces (no device allocation — ShapeDtypeStruct inputs):
  · compiled.memory_analysis()  — proves the cell fits per-chip HBM
  · compiled.cost_analysis()    — HLO FLOPs / bytes for §Roofline
  · collective payload bytes    — parsed from the post-SPMD HLO text
  · the three roofline terms against TPU v5e constants
  · DFModel's own prediction for the cell (core/ planner) side by side

Results are cached as JSON under results/dryrun/ so the sweep is resumable.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmo_1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--single-pod]

The production meshes need 512 placeholder CPU devices; ``main`` installs
the XLA flag before jax initializes its backend. In-process callers of
:func:`run_dryrun` / :func:`run_cell` must do the same *before anything
touches jax* (the flag is inert once the backend exists) — importing this
module deliberately no longer mutates the environment, so importers that
never lower a production mesh keep their real device count.
"""
from __future__ import annotations

import argparse
import os
import gzip
import json
import pathlib
import time

import jax
import jax.numpy as jnp

from ..configs import ARCH_IDS, SHAPES, cells, get_config
from ..core.roofline import RooflineTerms
from ..models import (decode_step, init_params, input_specs, loss_fn)
from ..models.config import ModelConfig
from ..parallel.logical import use_rules
from ..train.optimizer import AdamWConfig, adamw_update
from . import hlocost
from .mesh import make_axis_rules, make_production_mesh, batch_axes
from .shardings import (batch_shardings, decode_input_shardings,
                        opt_shardings, param_shardings)

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


# ------------------------------ step builders --------------------------------
def build_train_step(cfg: ModelConfig, accum: int = 1):
    """The production train step (trainer.make_train_step): AdamW + global-
    norm clipping, with optional gradient accumulation over ``accum``
    microbatches (bounds live activation memory — §Perf knob)."""
    from ..train.trainer import make_train_step
    return make_train_step(cfg, AdamWConfig(), accum=accum)


def build_prefill_step(cfg: ModelConfig):
    from ..models import forward
    from ..models.transformer import _memory_from_batch

    def prefill_step(params, batch):
        memory = _memory_from_batch(cfg, params, batch)
        return forward(cfg, params, batch["tokens"], memory=memory)

    return prefill_step


def build_serve_step(cfg: ModelConfig):
    def serve_step(params, inputs):
        return decode_step(cfg, params, inputs["cache"], inputs["token"],
                           inputs["pos"], memory=inputs.get("memory"))

    return serve_step


# ------------------------------ one cell -------------------------------------
def run_cell(arch: str, shape_name: str, multi_pod: bool,
             force: bool = False, extra_tag: str = "",
             planner: bool = True,
             fsdp: bool = False, remat: str | None = None,
             moe_dispatch: str | None = None, accum: int = 1,
             kv_replicate: bool = False, bf16_params: bool = False,
             bf16_ar: bool = False, cp_decode: bool = False) -> dict:
    """Lower + compile one (arch × shape × mesh) cell.

    ``fsdp`` / ``remat`` / ``moe_dispatch`` are the §Perf hillclimb knobs;
    when any is set the result is tagged separately so baseline (paper-
    faithful) and optimized artifacts coexist under results/dryrun/.
    """
    import dataclasses as _dc
    opt_tag = ""
    if fsdp:
        opt_tag += "__fsdp"
    if remat:
        opt_tag += f"__remat-{remat}"
    if moe_dispatch:
        opt_tag += f"__moe-{moe_dispatch}"
    if accum > 1:
        opt_tag += f"__accum{accum}"
    if kv_replicate:
        opt_tag += "__kvrep"
    if bf16_params:
        opt_tag += "__bf16"
    if bf16_ar:
        opt_tag += "__bf16ar"
    if cp_decode:
        opt_tag += "__cpdec"
    tag = (f"{arch}__{shape_name}__{'pod2' if multi_pod else 'pod1'}"
           f"{opt_tag}{extra_tag}")
    RESULTS.mkdir(parents=True, exist_ok=True)
    out_path = RESULTS / f"{tag}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    cfg = get_config(arch)
    if remat:
        cfg = _dc.replace(cfg, remat=remat)
    if moe_dispatch:
        cfg = _dc.replace(cfg, moe_dispatch=moe_dispatch)
    if bf16_params:
        cfg = _dc.replace(cfg, param_dtype="bfloat16")
    if bf16_ar:
        cfg = _dc.replace(cfg, matmul_out="bf16")
    if cp_decode:
        cfg = _dc.replace(cfg, decode_attn="context_parallel")
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = make_axis_rules(mesh, cfg, kv_replicate=kv_replicate)
    specs = input_specs(cfg, shape)
    t0 = time.time()

    with mesh, use_rules(rules, mesh):
        pshard = param_shardings(cfg, mesh, fsdp=fsdp)
        if shape.phase == "train":
            from ..train.optimizer import adamw_init
            fn = build_train_step(cfg, accum=accum)
            oshard = opt_shardings(cfg, mesh, fsdp=fsdp, master=bf16_params)
            bshard = batch_shardings(cfg, mesh, shape.global_batch)
            pspec = jax.eval_shape(
                lambda k: init_params(cfg, k),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
            ospec = jax.eval_shape(
                lambda pp: adamw_init(pp, master=bf16_params), pspec)
            jitted = jax.jit(fn, in_shardings=(pshard, oshard, bshard),
                             out_shardings=(pshard, oshard, None))
            lowered = jitted.lower(pspec, ospec, specs)
            tokens = shape.global_batch * shape.seq_len
            model_flops = cfg.model_flops(tokens, training=True)
        elif shape.phase == "prefill":
            fn = build_prefill_step(cfg)
            bshard = batch_shardings(cfg, mesh, shape.global_batch)
            bshard.pop("labels", None)
            pspec = jax.eval_shape(
                lambda k: init_params(cfg, k),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
            jitted = jax.jit(fn, in_shardings=(pshard, bshard))
            lowered = jitted.lower(pspec, specs)
            tokens = shape.global_batch * shape.seq_len
            model_flops = cfg.model_flops(tokens, training=False)
        else:  # decode
            fn = build_serve_step(cfg)
            ishard = decode_input_shardings(cfg, mesh, shape.global_batch,
                                            shape.seq_len)
            pspec = jax.eval_shape(
                lambda k: init_params(cfg, k),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
            jitted = jax.jit(fn, in_shardings=(pshard, ishard),
                             out_shardings=(None, ishard["cache"]))
            lowered = jitted.lower(pspec, specs)
            tokens = shape.global_batch  # one token per request
            model_flops = cfg.model_flops(tokens, training=False,
                                          decode_kv=shape.seq_len)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    raw_cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    summary = hlocost.analyze(hlo)          # trip-count-aware (see hlocost.py)
    n_chips = mesh.devices.size

    # hlocost quantities are per-device (post-SPMD module); the roofline
    # terms want global sums, which RooflineTerms divides back per chip.
    terms = RooflineTerms(
        name=tag, chips=n_chips,
        hlo_flops=summary.flops * n_chips,
        hlo_bytes=summary.bytes_accessed * n_chips,
        collective_bytes=summary.link_traffic_bytes * n_chips,
        model_flops=model_flops)

    hlo_path = RESULTS / f"{tag}.hlo.gz"
    with gzip.open(hlo_path, "wt", compresslevel=6) as fh:
        fh.write(hlo)

    result = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "n_chips": n_chips,
        "opts": {"fsdp": fsdp, "remat": remat or cfg.remat,
                 "moe_dispatch": moe_dispatch or cfg.moe_dispatch,
                 "accum": accum, "kv_replicate": kv_replicate,
                 "bf16_params": bf16_params, "bf16_ar": bf16_ar,
                 "cp_decode": cp_decode},
        "lower_s": t_lower, "compile_s": t_compile,
        "memory": {
            "bytes_per_device": getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "argument_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
        },
        "cost_per_device": summary.row(),
        "bytes_by_opcode": summary.bytes_by_opcode,
        "cost_raw_xla": {k: float(v) for k, v in raw_cost.items()
                         if isinstance(v, (int, float))
                         and not k.endswith("}")},
        "collective_schedule": hlocost.collective_schedule(summary),
        "roofline": terms.row(),
        "hlo": hlo_path.name,
    }
    if planner:
        try:
            from .plan import plan_cell
            result["dfmodel_plan"] = plan_cell(arch, shape_name, multi_pod)
        except Exception as e:  # planner issues must not fail the dry-run
            result["dfmodel_plan"] = {"error": str(e)}

    out_path.write_text(json.dumps(result, indent=1))
    return result


def run_dryrun(targets: list[tuple[str, str]], pods: list[bool] | None = None,
               force: bool = False, **cell_opts) -> list[dict]:
    """Importable sweep body: run every (arch, shape) target across the
    requested pod settings, collecting per-cell results (a failing cell
    records its error and the sweep continues — same contract as the CLI).
    ``cell_opts`` forward to :func:`run_cell` (fsdp/remat/accum/...)."""
    results: list[dict] = []
    for mp in (pods if pods is not None else [False]):
        for arch, shp in targets:
            try:
                r = run_cell(arch, shp, mp, force=force, **cell_opts)
                rf = r["roofline"]
                print(f"[OK ] {arch:22s} {shp:12s} pod{2 if mp else 1} "
                      f"compile={r['compile_s']:.1f}s "
                      f"dom={rf['dominant']:10s} "
                      f"tbound={max(rf['t_compute_s'], rf['t_memory_s'], rf['t_collective_s']):.4f}s "
                      f"frac={rf['roofline_fraction']:.3f}", flush=True)
            except Exception as e:
                print(f"[FAIL] {arch} {shp} pod{2 if mp else 1}: {e}",
                      flush=True)
                r = {"arch": arch, "shape": shp, "multi_pod": mp,
                     "error": str(e)}
            results.append(r)
    return results


def main():
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=512")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--force", action="store_true")
    # §Perf hillclimb knobs (baseline when unset)
    ap.add_argument("--fsdp", action="store_true",
                    help="ZeRO-3: shard params+optimizer over the data axes")
    ap.add_argument("--remat", choices=["full", "dots", "none"])
    ap.add_argument("--moe-dispatch", choices=["gspmd", "shard_map"])
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation microbatches (train cells)")
    ap.add_argument("--kv-replicate", action="store_true",
                    help="replicate GQA K/V instead of sharding on 'model'")
    ap.add_argument("--bf16-params", action="store_true",
                    help="mixed precision: bf16 live params + fp32 master")
    ap.add_argument("--bf16-ar", action="store_true",
                    help="emit bf16 dots so row-parallel partial-sum "
                         "all-reduces move bf16 instead of f32")
    ap.add_argument("--cp-decode", action="store_true",
                    help="context-parallel decode attention (shard_map "
                         "LSE-combine over the seq-sharded KV cache)")
    args = ap.parse_args()

    pods = []
    if args.single_pod or not args.multi_pod:
        pods.append(False)
    if args.multi_pod or args.all:
        pods.append(True)

    targets = []
    if args.all:
        for arch in ARCH_IDS:
            if arch == "gpt3_175b":
                continue  # paper workload exercised via benchmarks
            for shp in cells(arch):
                targets.append((arch, shp))
    else:
        targets.append((args.arch, args.shape))

    results = run_dryrun(targets, pods=pods, force=args.force,
                         fsdp=args.fsdp, remat=args.remat,
                         moe_dispatch=args.moe_dispatch, accum=args.accum,
                         kv_replicate=args.kv_replicate,
                         bf16_params=args.bf16_params, bf16_ar=args.bf16_ar,
                         cp_decode=args.cp_decode)
    failed = [f"{r['arch']}/{r['shape']}/pod{2 if r['multi_pod'] else 1}"
              for r in results if "error" in r]
    if failed:
        raise SystemExit(f"dry-run failed for {len(failed)} of "
                         f"{len(results)} cells: {', '.join(failed)}")


if __name__ == "__main__":
    main()

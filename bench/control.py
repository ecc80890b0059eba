#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip, in one process.

  python3 bench/control.py --workload serve.olmo_1b.decode \\
      --seeds 101,102,103 --out chiprun_out/control.json

For each seed it serves, at the cell's own batch and
lengths, as many batches as a run compares, then reads two numbers over the
same sampled requests against the float32 reference:

- ``program``: the widest gap by which a served token's reference logit
  lies below the reference's best (what a run compares; its largest over
  the seeds is the lower reading);
- ``control``: the same gap for the token that the reference computed in
  float8 (e4m3, the precision below the configuration's bfloat16) puts
  first at each position (its smallest over the seeds is the upper
  reading).

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

sys.path[0] = str(Path(__file__).resolve().parents[1])
import bench.run as R  # noqa: E402  (puts src on the path)
from bench import program, traffic  # noqa: E402


def readings(ctx, seed: int) -> dict:
    from repro.serve.engine import ServeEngine

    drv, mix, spec, model = ctx.driver, ctx.mix, ctx.spec, ctx.model
    ctx.seed = seed
    engine = ServeEngine(program.program_config(spec), model.program_params(
        model.make_weights(seed, spec)), max_batch=mix["batch"],
        max_len=mix["max_len"])
    wanted = math.ceil(mix["check_requests"] / mix["batch"])
    units, _ = drv.serve_window(ctx, engine, mix, spec["vocab"],
                                units_wanted=wanted)
    del engine
    gc.collect()
    prompts, served = drv.served_requests(mix, seed, units, spec["vocab"])
    pick = traffic.check_sample(mix, seed, len(served))
    seqs, rows = drv.teacher_forced(prompts[pick], served[pick])
    w = model.make_weights(seed, spec)
    ref = model.reference_logits(w, seqs, rows, spec=spec)
    low = model.reference_logits(w, seqs, rows, quant="fp8", spec=spec)
    del w
    return {"seed": seed,
            "program": float(model.served_gaps(ref, served[pick]).max()),
            "control": float(model.served_gaps(ref, low.argmax(-1)).max()),
            "compared": int(served[pick].size)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    ctx = R.Context(cell, 0, 0.0, False, R.require_chips(cell.cell["chips"]))
    R.use_compile_cache()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        rows.append(readings(ctx, seed))
        rows[-1]["seconds"] = time.perf_counter() - t
        print(json.dumps(rows[-1]), flush=True)
    summary = {"workload": args.workload, "runs": rows,
               "lower": max(r["program"] for r in rows),
               "upper": min(r["control"] for r in rows)}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(summary, indent=1))
    print(json.dumps({k: summary[k] for k in ("lower", "upper")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

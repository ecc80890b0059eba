#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

  python3 bench/run.py --workload serve.olmo_1b.decode --seed 7 \\
      --seconds 30 --trace 0

Everything a cell needs is found by name from its entry in
``BENCHMARK.json``: the configuration's file and the module beside it
(``bench/configs/<config>.{json,py}``: weights, reference, counts), the
traffic mix (``bench/traffic/<mix>.json``), the driver the mix names
(``bench/drivers/<driver>.py``), the limits of the check
(``bench/limits/<cell>.json``), one reader per per-layer metric
(``bench/metrics/<metric>.py``) and the chip's peaks (``bench/peaks.json``).

The run loads, warms up every shape the window uses (set-up), measures for
``--seconds`` through the end of the last unit started in that time, checks
what the window produced against the plain reference, and prints one JSON
line last: the end-to-end metrics with ``--trace 0``, the per-layer ones
with ``--trace 1``. Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if sys.path and Path(sys.path[0]).resolve() == BENCH:
    sys.path[0] = str(ROOT)      # run as a script: import bench.* from ROOT
elif str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "src"))

from bench import trace as trace_mod  # noqa: E402
from bench import traffic  # noqa: E402

OUT = ROOT / ".bench_out"


def load_module(path: Path, name: str) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def for_cell(metrics: list, cell: str) -> list:
    """The metrics a cell reports: those that list it, or list no cells."""
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, bench: dict | None = None) -> types.SimpleNamespace:
    """Resolve every part of cell ``name`` by name."""
    bench = bench or json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg_file = ROOT / config["file"]
    mix = traffic.load(cell["traffic"])
    return types.SimpleNamespace(
        cell=cell, config=config,
        spec=json.loads(cfg_file.read_text()),
        model=load_module(cfg_file.with_suffix(".py"),
                          f"bench_config_{cell['config']}"),
        mix=mix,
        driver=load_module(BENCH / "drivers" / f"{mix['driver']}.py",
                           f"bench_driver_{mix['driver']}"),
        limits=json.loads((BENCH / "limits" / f"{name}.json").read_text()),
        end_to_end=for_cell(bench["end_to_end"], name),
        per_layer=for_cell(bench["per_layer"], name))


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json; known: {sorted(table)}")
    return table[kind]


def require_chips(count: int) -> list:
    """The first ``count`` TPU chips; anything less is an error."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU: JAX's devices are {len(devs)} x "
                         f"{devs[0].platform}; the benchmark runs on the "
                         f"chip only")
    if len(devs) < count:
        raise SystemExit(f"bench: the cell needs {count} TPU chips, JAX "
                         f"found {len(devs)}")
    return devs[:count]


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at the program's fixed path
    inside the checkout (or ``JAX_COMPILATION_CACHE_DIR``), for every
    program however short its compile, so that set-up finds all of them."""
    from repro.launch.compile_cache import use_compile_cache as use
    import jax

    use()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class Window:
    """The measured window: units started before ``seconds`` run to their
    end; ``t1`` is the end of the last one."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.t0 = time.perf_counter()
        self.t1 = None

    def expired(self) -> bool:
        return time.perf_counter() - self.t0 >= self.seconds

    @property
    def length(self) -> float:
        return self.t1 - self.t0


class Context:
    """What a driver gets: the cell's parts, the seed, the chips, and the
    clock of set-up and window."""

    def __init__(self, cell: types.SimpleNamespace, seed: int,
                 seconds: float, trace: bool, devices: list):
        self.__dict__.update(vars(cell))
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices = devices
        self.setup_s = None
        self.setup_phases = {"devices": time.perf_counter() - T_START}
        self.compiles_in_window = 0
        self.gc_pauses: list = []
        self.memory_peak_bytes = None
        self.trace_dir = OUT / "trace" / cell.cell["name"]

    def phase(self, name: str, *ready) -> None:
        """Mark the end of a set-up phase, once ``ready``'s arrays are
        computed: seconds from process start, reported beside ``setup_s``
        so that its spread can be traced to a phase."""
        import jax

        jax.block_until_ready(ready)
        self.setup_phases[name] = time.perf_counter() - T_START

    def setup_done(self) -> None:
        """Set-up ends here. What it left on the host is collected once and
        frozen, so that the window's garbage collections walk only what the
        window makes."""
        gc.collect()
        gc.freeze()
        self.setup_s = time.perf_counter() - T_START

    @staticmethod
    def span(name: str):
        import jax

        return jax.profiler.TraceAnnotation(f"bench.{name}")

    @contextlib.contextmanager
    def window(self):
        """Time the window; with ``--trace 1`` record it. Compiles that
        happen inside are counted."""
        import jax
        from jax import monitoring

        if self.setup_s is None:
            self.setup_done()
        counted = [0]
        started: list = []

        def on_event(event, *_, **__):
            if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                counted[0] += 1

        def on_gc(phase, info):
            if phase == "start":
                started.append(time.perf_counter())
            elif started:
                self.gc_pauses.append(time.perf_counter() - started.pop())

        monitoring.register_event_duration_secs_listener(on_event)
        gc.callbacks.append(on_gc)
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.trace_dir))
        win = Window(self.seconds)
        try:
            with self.span("window"):
                yield win
                win.t1 = time.perf_counter()
        finally:
            if self.trace:
                jax.profiler.stop_trace()
            monitoring.unregister_event_duration_listener(on_event)
            gc.callbacks.remove(on_gc)
            self.compiles_in_window = counted[0]

    def read_memory(self) -> int:
        """The peak device memory of the fullest chip so far."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        self.memory_peak_bytes = int(max(peaks))
        return self.memory_peak_bytes


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             require=require_chips, cell: types.SimpleNamespace | None = None
             ) -> dict:
    """One run of cell ``name``: the result line as a dict."""
    cell = cell or load_cell(name)
    devices = require(cell.cell["chips"])
    use_compile_cache()
    peaks = peaks_for(devices[0].device_kind)
    ctx = Context(cell, seed, seconds, trace, devices)
    rec = cell.driver.run(ctx)
    if ctx.memory_peak_bytes is None:
        raise RuntimeError("driver did not read the memory peak")

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    out: dict = {"correct": bool(rec["correct"]),
                 "attempted": int(rec["attempted"]),
                 "failed": int(rec["failed"])}
    if trace:
        summary = trace_mod.reduce(trace_mod.load(
            trace_mod.find_xplane(str(ctx.trace_dir))))
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        run = types.SimpleNamespace(record=rec, trace=summary, peaks=peaks,
                                    chips=len(devices), model=cell.model,
                                    spec=cell.spec, mix=cell.mix)
        metrics = {}
        for m in cell.per_layer:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name']}")
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = summary["breakdown"]
    else:
        values = dict(rec["end_to_end"], setup_s=ctx.setup_s)
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
        out["device"] = device
    out["setup_phases_s"] = ctx.setup_phases
    out["compiles_in_window"] = ctx.compiles_in_window
    out["gc_in_window"] = {"collections": len(ctx.gc_pauses),
                           "longest_s": max(ctx.gc_pauses, default=0.0)}
    out["check"] = rec["checks"]
    return out


def main(argv: list[str] | None = None, require=require_chips) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("bench: --seed must be >= 0")
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   require=require)
    print(f"set-up phases (s from start): {out['setup_phases_s']}",
          flush=True)
    print(f"compiles in window: {out['compiles_in_window']}; garbage "
          f"collections {out['gc_in_window']}", flush=True)
    for name, c in out["check"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

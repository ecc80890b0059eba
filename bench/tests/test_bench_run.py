"""``bench/run.py`` end to end at smoke size on the CPU: the window, the
check and the last line, with the look for a chip steered here in the test.
A run whose served tokens are altered where they are produced reads
``correct`` false, and so does the float8 control."""
from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from bench import program
from bench import run as bench_run
from bench import trace as trace_mod

SMOKE_SIZES = dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=4,
                   head_dim=32, d_ff=512, vocab=512)
SMOKE_MIX = {
    "serve.olmo_1b.decode": dict(batch=2, prompt_len=8, new_tokens=6,
                                 max_len=32, check_requests=2),
    "serve.olmo_1b.prefill": dict(batch=2, prompt_len=16, new_tokens=1,
                                  max_len=32, check_requests=4),
}
SEED = 12_345_678_901  # wider than 32 bits, as the driver's seeds are
LOAD_CELL = bench_run.load_cell


def smoke_cell(name: str):
    cell = LOAD_CELL(name)
    cell.spec = dict(cell.spec, **SMOKE_SIZES)
    cell.mix = dict(cell.mix, **SMOKE_MIX[name])
    return cell


def cpu_chips(count):
    return jax.devices()[:count]


@pytest.fixture
def steered(monkeypatch):
    """Smoke cells, CPU devices, peaks for the CPU's device kind, and no
    persistent compilation cache."""
    from dataclasses import replace

    from repro.configs import get_config

    monkeypatch.setattr(bench_run, "use_compile_cache", lambda: None)
    monkeypatch.setattr(program, "program_config", lambda spec: replace(
        get_config("olmo_1b", smoke=True), gated=spec["gated"]))
    monkeypatch.setattr(bench_run, "load_cell",
                        lambda name, bench=None: smoke_cell(name))
    monkeypatch.setattr(bench_run, "peaks_for", lambda kind: {
        "flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10})


def last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_no_tpu_means_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        bench_run.main(["--workload", "serve.olmo_1b.decode", "--seed", "1",
                        "--seconds", "1"])
    assert "no TPU" in str(e.value)
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("cell", sorted(SMOKE_MIX))
def test_a_run_prints_the_contract_line(cell, steered, capsys):
    assert bench_run.main(["--workload", cell, "--seed", str(SEED),
                           "--seconds", "0.5"], require=cpu_chips) == 0
    out = last_line(capsys)
    assert list(out)[-1] == "check"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    want = {"setup_s", "ttft_p95_ms" if "prefill" in cell
            else "output_tokens_per_s"}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert out["compiles_in_window"] == 0
    phases = out["setup_phases_s"]
    assert (0 < phases["devices"] < phases["weights"]
            < out["metrics"]["setup_s"]["value"])
    gap = out["check"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]


def test_a_traced_run_reports_per_layer_metrics(steered, capsys,
                                                monkeypatch):
    # the CPU records no TPU plane: stand the host's generate spans in for
    # device operations, so the reduction and the readers run
    real_load = trace_mod.load

    def load(path):
        t = real_load(path)
        ops = [(n, s, e) for n, s, e in t.spans if n == "bench.generate"]
        return trace_mod.Trace({"/device:TPU:0": ops}, t.spans)

    monkeypatch.setattr(bench_run.trace_mod, "load", load)
    cell = "serve.olmo_1b.decode"
    bench_run.main(["--workload", cell, "--seed", "7", "--seconds", "0.5",
                    "--trace", "1"], require=cpu_chips)
    out = last_line(capsys)
    assert set(out["metrics"]) == {"step_mfu.decode", "decode_step_roofline",
                                   "device_idle_share.decode"}
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["breakdown"]["device_ops"][0][0] == "bench.generate"
    assert len(out["breakdown"]["idle_gaps"]) <= trace_mod.TOP


@pytest.mark.parametrize("cell", sorted(SMOKE_MIX))
def test_a_token_altered_where_it_is_produced_fails(cell, steered, capsys,
                                                    monkeypatch):
    from repro.serve.engine import ServeEngine

    sample = ServeEngine._sample

    def altered(logits, temperature, rng):
        tok = sample(logits, temperature, rng)
        return (tok + 1) % logits.shape[-1]

    monkeypatch.setattr(ServeEngine, "_sample", staticmethod(altered))
    bench_run.main(["--workload", cell, "--seed", "3", "--seconds", "0.3"],
                   require=cpu_chips)
    out = last_line(capsys)
    assert out["correct"] is False
    gap = out["check"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("name", sorted(SMOKE_MIX))
def test_the_float8_control_fails_the_limit(name):
    """The reference in float8 in the program's place: at each position of
    the same sequences its first token, read under the float32 reference,
    lies further below the best than the cell's limit allows."""
    cell = smoke_cell(name)
    spec, model = cell.spec, cell.model
    w = model.make_weights(5, spec)
    rng = np.random.default_rng(5)
    seqs = rng.integers(0, spec["vocab"], (4, 64), dtype=np.int32)
    rows = np.broadcast_to(np.arange(64), (4, 64)).copy()
    ref = model.reference_logits(w, seqs, rows, spec=spec)
    low = model.reference_logits(w, seqs, rows, quant="fp8", spec=spec)
    assert model.served_gaps(ref, ref.argmax(-1)).max() == 0
    assert (model.served_gaps(ref, low.argmax(-1)).max()
            > cell.limits["max_logit_gap"]["limit"])


def test_tokens_outside_the_vocabulary_read_infinite():
    cell = smoke_cell("serve.olmo_1b.decode")
    ref = np.zeros((1, 2, 5))
    gaps = cell.model.served_gaps(ref, np.array([[1, 7]]))
    assert gaps[0, 0] == 0 and np.isinf(gaps[0, 1])

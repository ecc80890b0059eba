"""The per-layer metrics read from the program's own recorder
(``repro.tracing``): on a steered smoke traced run of each cell the
recorder holds the window's ``generate`` calls, each metric of the cell is
present and positive, and a reader finds nothing in an empty recorder."""
from __future__ import annotations

import pytest
from repro import tracing

from bench import run as bench_run
from bench import trace as trace_mod
from bench.tests.test_bench_run import (SEED, cpu_chips, last_line,  # noqa: F401
                                        steered)

NEW = {"serve.olmo_1b.decode": [],
       "serve.olmo_1b.prefill": ["host_exposed_ms.prefill"]}


@pytest.fixture
def cpu_trace(monkeypatch, tmp_path):
    """The CPU records no TPU plane: the host's generate spans stand in for
    device operations, so the reduction and every reader run. The trace
    goes to a directory of the test's own, apart from other tests' runs."""
    monkeypatch.setattr(bench_run, "OUT", tmp_path)
    real_load = trace_mod.load

    def load(path):
        t = real_load(path)
        ops = [(n, s, e) for n, s, e in t.spans if n == "bench.generate"]
        return trace_mod.Trace({"/device:TPU:0": ops}, t.spans)

    monkeypatch.setattr(bench_run.trace_mod, "load", load)


def reader(name):
    return bench_run.load_module(bench_run.BENCH / "metrics" / f"{name}.py",
                                 f"bench_metric_{name}")


@pytest.mark.parametrize("cell", sorted(NEW))
def test_a_traced_run_reports_the_program_metrics(cell, steered, cpu_trace,
                                                  capsys):
    tracing.reset()
    bench_run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                    "0.5", "--trace", "1"], require=cpu_chips)
    out = last_line(capsys)
    snap = tracing.snapshot()
    tracing.reset()
    batches = snap["names"]["serve.generate"]["count"]
    exposed_ms = 1e3 * snap["counters"]["serve.exposed_s"] / batches
    per_batch_ms = 1e3 * out["device"]["window_s"] / batches
    assert 0 < exposed_ms <= per_batch_ms
    metrics = out["metrics"]
    assert set(NEW[cell]) <= set(metrics)
    assert all(0 < metrics[m]["value"] <= per_batch_ms for m in NEW[cell])


@pytest.mark.parametrize("name", sorted(m for ms in NEW.values() for m in ms))
def test_a_reader_finds_nothing_in_an_empty_recorder(name):
    tracing.reset()
    assert reader(name).read(None) is None


def test_host_exposed_ms_is_the_counter_per_generate_call(monkeypatch):
    monkeypatch.setattr(tracing, "snapshot", lambda: {
        "names": {"serve.generate": {"count": 4}},
        "counters": {"serve.exposed_s": 0.2}})
    assert reader("host_exposed_ms.prefill").read(None) == pytest.approx(50.0)

"""The recorder (``repro.tracing``), the spans and counters of
``ServeEngine.generate``, and the layer names on the model's device ops."""
from __future__ import annotations

import dataclasses
import gc
import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro import tracing
from repro.configs import get_config
from repro.models import decode_step, init_cache, init_params, prefill
from repro.serve.engine import ServeEngine

KEY = jax.random.PRNGKey(0)


@pytest.fixture
def profiled(tmp_path):
    """A profiler session around the test body, on a cleared recorder."""
    tracing.reset()
    with jax.profiler.trace(str(tmp_path)):
        yield tracing.RECORDER
    tracing.reset()


def test_without_a_profiler_nothing_is_recorded():
    tracing.reset()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with tracing.span("outer", call=1):
        with tracing.span("inner"):
            tracing.count("n", 3)
    snap = tracing.snapshot()
    assert snap["spans"] == [] and snap["counters"] == {}
    assert snap["names"] == {} and snap["dropped"] == 0


def test_records_carry_name_times_parent_and_call(profiled):
    with tracing.span("a", call=7):
        with tracing.span("b"):
            pass
        with tracing.span("c"):
            with tracing.span("d"):
                pass
    with tracing.span("e"):
        pass
    spans = tracing.snapshot()["spans"]
    assert [s.name for s in spans] == ["a", "b", "c", "d", "e"]
    assert all(s.start_ns <= s.end_ns for s in spans)
    assert [s.parent for s in spans] == [None, 0, 0, 2, None]
    assert [s.call for s in spans] == [7, 7, 7, 7, None]
    a, b, c, d, _ = spans
    assert a.start_ns <= b.start_ns and b.end_ns <= c.start_ns
    assert c.start_ns <= d.start_ns and d.end_ns <= c.end_ns <= a.end_ns


def test_self_time_and_counters(profiled):
    with tracing.span("outer"):
        time.sleep(0.02)
        for _ in range(2):
            with tracing.span("inner"):
                time.sleep(0.01)
    tracing.count("n", 2)
    tracing.count("n", 0.5)
    snap = tracing.snapshot()
    outer, inner = snap["names"]["outer"], snap["names"]["inner"]
    spans = snap["spans"]
    inner_s = sum(s.end_ns - s.start_ns for s in spans[1:]) * 1e-9
    assert inner["count"] == 2 and inner["self_s"] == inner["total_s"]
    assert inner["total_s"] == pytest.approx(inner_s)
    assert inner["longest_s"] == pytest.approx(max(
        s.end_ns - s.start_ns for s in spans[1:]) * 1e-9)
    assert outer["count"] == 1 and outer["total_s"] >= 0.04
    assert outer["self_s"] == pytest.approx(outer["total_s"] - inner_s)
    assert outer["self_s"] >= 0.02
    assert snap["counters"] == {"n": 2.5}


def test_a_full_buffer_drops_and_counts(profiled):
    rec = tracing.Recorder(capacity=3)
    for i in range(5):
        with rec.span(f"s{i}"):
            pass
    snap = rec.snapshot()
    assert [s.name for s in snap["spans"]] == ["s0", "s1", "s2"]
    assert snap["dropped"] == 2
    rec.reset()
    assert rec.snapshot()["spans"] == [] and rec.snapshot()["dropped"] == 0


def test_a_span_open_across_a_reset_is_forgotten(profiled):
    with tracing.span("before"):
        tracing.reset()
        with tracing.span("after"):
            pass
    spans = tracing.snapshot()["spans"]
    assert [(s.name, s.parent) for s in spans] == [("after", None)]
    assert spans[0].end_ns is not None


def test_recording_leaves_nothing_for_the_garbage_collector(profiled):
    gc.collect()
    gc.disable()
    try:
        before = gc.get_count()[0]
        for i in range(1000):
            with tracing.span("outer", call=i):
                with tracing.span("inner"):
                    tracing.count("n", 1.0)
        grown = gc.get_count()[0] - before
    finally:
        gc.enable()
    assert len(tracing.snapshot()["spans"]) == 2000
    assert grown < 100


def _engine():
    cfg = get_config("olmo_1b", smoke=True)
    params = init_params(cfg, KEY)
    return cfg, ServeEngine(cfg, params, max_batch=2, max_len=32)


def test_generate_records_its_span_tree(tmp_path):
    cfg, eng = _engine()
    prompts = jax.random.randint(KEY, (2, 8), 0, cfg.vocab)
    n = 5
    plain = eng.generate(prompts, n_tokens=n)       # compiles, not recorded
    assert tracing.snapshot()["spans"] == []
    tracing.reset()
    with jax.profiler.trace(str(tmp_path)):
        traced = [eng.generate(prompts, n_tokens=n) for _ in range(2)]
    snap = tracing.snapshot()
    tracing.reset()
    assert all(t.tokens == plain.tokens for t in traced)

    spans = snap["spans"]
    roots = [i for i, s in enumerate(spans) if s.parent is None]
    assert [spans[i].name for i in roots] == ["serve.generate"] * 2
    assert spans[roots[0]].call != spans[roots[1]].call
    want = (["serve.prefill", "serve.rehome", "serve.sample", "serve.wait"]
            + ["serve.decode_step"] * (n - 1)
            + ["serve.wait", "serve.to_host"])
    for r in roots:
        kids = [s for s in spans if s.parent == r]
        assert [s.name for s in kids] == want
        assert all(s.call == spans[r].call for s in kids)
        assert all(spans[r].start_ns <= s.start_ns <= s.end_ns
                   <= spans[r].end_ns for s in kids)
    assert snap["names"]["serve.decode_step"]["count"] == 2 * (n - 1)
    exposed = snap["counters"]["serve.exposed_s"]
    assert 0 < exposed < snap["names"]["serve.generate"]["total_s"]
    assert snap["dropped"] == 0


def test_generate_counts_the_aliased_cache_share(tmp_path):
    """``serve.cache_aliased`` adds 1.0 (every cache byte updated in place)
    once per ``generate`` call that decodes, and nothing for a call that
    ends with its first token."""
    cfg, eng = _engine()
    prompts = jax.random.randint(KEY, (2, 8), 0, cfg.vocab)
    eng.generate(prompts, n_tokens=3)                # compiles, not recorded
    tracing.reset()
    with jax.profiler.trace(str(tmp_path)):
        eng.generate(prompts, n_tokens=1)
        prefill_only = tracing.snapshot()["counters"]
        for _ in range(2):
            eng.generate(prompts, n_tokens=3)
        counters = tracing.snapshot()["counters"]
    tracing.reset()
    assert "serve.cache_aliased" not in prefill_only
    assert counters["serve.cache_aliased"] == 2.0


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_generate_counts_the_fused_weight_share(tmp_path, dtype):
    """``serve.weights_fused`` adds 1.0 (every matmul reads its weight as
    stored) once per decoding ``generate`` call under a recording
    profiler, for f32 weights under bf16 activations (cast inside each
    dot) and under f32 ones (no cast), and nothing without one. (The CPU
    widens bf16 weights to f32 for its dots, apart from them, so a
    bf16-weight config reads 0 here; on the TPU's compiler it reads 1.0,
    ``test_granite_compile.py``.)"""
    cfg = dataclasses.replace(get_config("olmo_1b", smoke=True),
                              dtype=dtype)
    eng = ServeEngine(cfg, init_params(cfg, KEY), max_batch=2, max_len=32)
    prompts = jax.random.randint(KEY, (2, 8), 0, cfg.vocab)
    tracing.reset()
    eng.generate(prompts, n_tokens=3)                # compiles, not recorded
    assert tracing.snapshot()["counters"] == {}
    with jax.profiler.trace(str(tmp_path)):
        eng.generate(prompts, n_tokens=3)
        counters = tracing.snapshot()["counters"]
    tracing.reset()
    assert counters["serve.weights_fused"] == 1.0


SCOPES = ("layers", "attention", "kv_cache", "mlp", "lm_head", "fill_cache")


def _scopes(compiled_text: str) -> set:
    names = re.findall(r'op_name="([^"]*)"', compiled_text)
    return {p for n in names for p in n.split("/") if p in SCOPES}


@pytest.mark.parametrize("program", ["decode_step", "prefill"])
def test_device_ops_carry_layer_names(program):
    cfg = get_config("olmo_1b", smoke=True)
    params = init_params(cfg, KEY)
    if program == "decode_step":
        cache = init_cache(cfg, 2, 16)
        fn = jax.jit(lambda p, c, t, i: decode_step(cfg, p, c, t, i))
        args = (params, cache, jnp.zeros((2,), jnp.int32), jnp.int32(3))
        want = {"layers", "attention", "kv_cache", "mlp", "lm_head"}
    else:
        fn = jax.jit(lambda p, t: prefill(cfg, p, t))
        args = (params, jnp.zeros((2, 8), jnp.int32))
        want = {"attention", "mlp", "lm_head", "fill_cache"}
    assert _scopes(fn.lower(*args).compile().as_text()) == want

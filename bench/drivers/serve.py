"""Closed-loop serving through ``ServeEngine.generate``.

One caller sends a batch of ``batch`` prompts of ``prompt_len`` tokens,
waits for its ``new_tokens`` greedy tokens each, and sends the next. Every
request of a batch arrives when the batch is sent, so its time to first
token is the time of the ``generate`` call on the benchmark's clock (for
one new token), and the window's output rate is every token generated over
the whole window.

The check: a sample of the finished requests, drawn from the seed, run
through the plain reference over prompt plus served tokens (teacher
forced); the number compared is the widest gap by which a served token's
reference logit lies below the reference's best at its position.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import program, traffic


def teacher_forced(prompts: np.ndarray, served: np.ndarray):
    """Sequences and read positions for the reference: prompt plus every
    served token but the last; the logits at the last prompt position and
    after each served token predict the served tokens."""
    s, n = prompts.shape[1], served.shape[1]
    seqs = np.concatenate([prompts, served[:, :n - 1]], axis=1)
    rows = np.broadcast_to(np.arange(s - 1, s - 1 + n), served.shape)
    return seqs, np.ascontiguousarray(rows)


def serve_window(ctx, engine, mix: dict, vocab: int,
                 units_wanted: int | None = None) -> tuple:
    """The measured window: batches back to back until ``ctx.seconds`` (or
    until ``units_wanted`` batches have finished). Returns per unit
    ``(index, seconds, tokens (B, n))`` and the window's length."""
    units = []
    with ctx.window() as win:
        i = 0
        while (not win.expired() if units_wanted is None
               else i < units_wanted):
            with ctx.span("prompt_build"):
                prompts = traffic.unit(mix, ctx.seed, i, vocab)
            with ctx.span("generate"):
                t = time.perf_counter()
                res = engine.generate(prompts, mix["new_tokens"])
                dt = time.perf_counter() - t
            units.append((i, dt, np.asarray(res.tokens, np.int64).T))
            i += 1
    return units, win.length


def served_requests(mix: dict, seed: int, units: list, vocab: int):
    """Prompts and served tokens of every finished request, in order."""
    prompts = np.concatenate([traffic.unit(mix, seed, i, vocab)
                              for i, _, _ in units])
    served = np.concatenate([tok for _, _, tok in units])
    return prompts, served


def check(ctx, prompts: np.ndarray, served: np.ndarray, limit: float,
          model, seed: int) -> dict:
    """Reference over the sampled requests; the widest served-token gap."""
    pick = traffic.check_sample(ctx.mix, seed, len(served))
    seqs, rows = teacher_forced(prompts[pick], served[pick])
    with ctx.span("reference"):
        w = model.make_weights(seed, ctx.spec)
        ref = model.reference_logits(w, seqs, rows, spec=ctx.spec)
        del w
    gap = float(model.served_gaps(ref, served[pick]).max())
    return {"max_logit_gap": {"value": gap, "limit": limit}}


def run(ctx) -> dict:
    from repro.serve.engine import ServeEngine

    mix, spec, model = ctx.mix, ctx.spec, ctx.model
    cfg = program.program_config(spec)
    vocab = spec["vocab"]
    params = model.program_params(model.make_weights(ctx.seed, spec))
    ctx.phase("weights", params)
    engine = ServeEngine(cfg, params, max_batch=mix["batch"],
                         max_len=mix["max_len"])
    del params
    # warm-up: every shape of the window (the prefill at the prompt's
    # length, the decode step, sampling), on prompts no unit sends
    warm = traffic.rng(ctx.seed, 3).integers(
        0, vocab, (mix["batch"], mix["prompt_len"]), dtype=np.int32)
    engine.generate(warm, min(mix["new_tokens"], 2))
    ctx.setup_done()

    units, window_s = serve_window(ctx, engine, mix, vocab)
    ctx.read_memory()
    del engine
    gc.collect()

    prompts, served = served_requests(mix, ctx.seed, units, vocab)
    b, n = mix["batch"], mix["new_tokens"]
    checks = check(ctx, prompts, served,
                   ctx.limits["max_logit_gap"]["limit"], model, ctx.seed)
    shape_ok = served.shape == (len(units) * b, n)
    end_to_end = {"output_tokens_per_s": len(units) * b * n / window_s}
    if n == 1:   # the call ends with the first token: its time is the TTFT
        ttft_ms = np.repeat([dt * 1e3 for _, dt, _ in units], b)
        end_to_end["ttft_p95_ms"] = float(np.percentile(ttft_ms, 95))
    return {
        "correct": shape_ok and all(c["value"] <= c["limit"]
                                    for c in checks.values()),
        "attempted": len(units) * b,
        "failed": 0 if shape_ok else len(units) * b,
        "end_to_end": end_to_end,
        "units": {"count": len(units), "batch": b,
                  "prompt_len": mix["prompt_len"], "new_tokens": n,
                  "window_s": window_s},
        "checks": checks,
    }

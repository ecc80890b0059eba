"""Batched serving engine: prefill + decode with a slot-based KV cache
(continuous-batching-lite: fixed slots, per-slot position counters, greedy or
temperature sampling). This is the executable twin of the paper's §VIII.A
serving model — TTFT = prefill latency, TPOT = decode step latency.

Decode runs one compiled step per batch and memory shape, compiled on first
use (:func:`compile_decode`): it donates the cache and keeps it in the
layout the compiler picks, so each step writes its new K/V rows into the
cache it was given and hands that cache on to the next. Two decode drivers
share it:

* :meth:`ServeEngine.generate` — the serving path: one sync at the end of
  the decode loop, so XLA pipelines step dispatch (throughput-faithful
  TPOT over the whole run);
* :meth:`ServeEngine.decode_steady` — the measurement path: warmup steps
  are discarded (compile + cache effects), then every steady-state step is
  individually synced and timed, so the validation loop gets a per-step
  sample distribution instead of one average.
"""
from __future__ import annotations

import dataclasses
import itertools
import re
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout

from ..models import decode_step, init_cache, prefill
from ..models.config import ModelConfig
from ..tracing import count, span


@dataclasses.dataclass
class GenerationResult:
    tokens: list
    ttft: float
    tpot: float
    tokens_per_s: float


@dataclasses.dataclass
class SteadyTiming:
    """Steady-state decode timings: ``step_times`` are post-warmup decode
    steps, each synced (``block_until_ready``) before its clock is read."""

    ttft: float                  # prefill + first sampled token, synced
    warmup: int                  # discarded decode steps before timing
    step_times: list[float]      # seconds per timed steady-state step
    batch: int                   # request slots served per step

    @property
    def tpot(self) -> float:
        """Mean steady-state time-per-output-token (seconds)."""
        return sum(self.step_times) / max(len(self.step_times), 1)

    @property
    def tokens_per_s(self) -> float:
        t = self.tpot
        return self.batch / t if t > 0 else 0.0


class DecodeStep(NamedTuple):
    """The decode step compiled for one batch and memory shape.

    The engine holds each cache entry with its axes in the order, major
    to minor, in which the compiler chose to keep it for the step
    (``Layout.AUTO``), so the step, compiled on that cache with default
    layouts, updates it in place without a relayout copy. ``enter`` moves
    a cache in ``init_cache``'s order there (one copy); ``run(params,
    cache, token, pos, memory) -> (logits, cache)`` donates the cache it
    is given and returns it updated, in the same order."""

    enter: jax.stages.Compiled
    run: jax.stages.Compiled


def _permute(cache: dict, axes: dict) -> dict:
    return {k: jnp.transpose(v, axes[k]) for k, v in cache.items()}


def compile_decode(cfg: ModelConfig, params, cache, token, pos,
                   memory=None) -> DecodeStep:
    """``decode_step`` compiled for the shapes of its arguments (arrays or
    ``jax.ShapeDtypeStruct``) on a donated cache kept in the layout the
    compiler picks (see :class:`DecodeStep`).

    The layout is read from a compile with ``Layout.AUTO`` for the cache;
    the step itself is compiled with default layouts on the cache's axes
    reordered to match, because an executable with a non-default entry
    layout comes back from JAX's persistent compilation cache with its
    outputs labelled in the default layout."""
    def step(p, c, t, i, m):
        return decode_step(cfg, p, c, t, i, memory=m)

    cache = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding), cache)
    auto = Format(Layout.AUTO)
    picked = jax.jit(
        step, in_shardings=(None, auto, None, None, None),
        out_shardings=(None, auto),
    ).lower(params, cache, token, pos, memory).compile().input_formats[0][1]
    order = {k: f.layout.major_to_minor for k, f in picked.items()}
    back = {k: tuple(o.index(i) for i in range(len(o)))
            for k, o in order.items()}

    def run(p, c, t, i, m):
        logits, c = step(p, _permute(c, back), t, i, m)
        return logits, _permute(c, order)

    enter = jax.jit(lambda c: _permute(c, order)).lower(cache).compile()
    held = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding), enter.out_info)
    return DecodeStep(enter, jax.jit(run, donate_argnums=1).lower(
        params, held, token, pos, memory).compile())


def aliased_share(step: DecodeStep, cache) -> float:
    """The share of ``cache``'s bytes that the compiled decode ``step``
    writes back into its argument's buffers (1.0: every byte)."""
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(cache))
    return step.run.memory_analysis().alias_size_in_bytes / cache_bytes


def fused_weight_share(step: DecodeStep, params) -> float:
    """The share of the stacked layers' matrix weight bytes (the leaves of
    ``params["stack"]`` with two or more dimensions a layer) that the
    compiled decode ``step`` reads as stored (1.0: every matmul casts its
    weight inside its own fusion). A weight counts as cast apart when an
    op outside a fusion's body writes an array of its shape, the whole
    stack or one layer's slice, in another dtype."""
    hlo = step.run.as_text()
    bodies = set(re.findall(r" fusion\(.*?calls=%([\w.-]+)", hlo))
    written, comp = set(), None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        op = re.match(r"\s+(?:ROOT )?%\S+ = (.+?) ([a-z][\w-]*)\(", line)
        if comp in bodies or not op or op.group(2) in _PASS_THROUGH:
            continue
        written.update(re.findall(r"(\w+)\[([\d,]*)\]", op.group(1)))
    total = cast_apart = 0
    for w in jax.tree.leaves(params["stack"]):
        if w.ndim < 3:
            continue
        n = w.size * w.dtype.itemsize
        total += n
        shapes = {",".join(map(str, dims))
                  for dims in (w.shape, w.shape[1:], (1, *w.shape[1:]))}
        stored = _HLO_TYPES.get(w.dtype.name, w.dtype.name)
        if any(dims in shapes and t != stored for t, dims in written):
            cast_apart += n
    return 1.0 - cast_apart / total if total else 1.0


#: Ops that hand a buffer on without writing one.
_PASS_THROUGH = {"parameter", "get-tuple-element", "tuple", "bitcast",
                 "while", "conditional", "call"}
#: HLO's names of the weight dtypes.
_HLO_TYPES = {"float32": "f32", "bfloat16": "bf16", "float16": "f16"}


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, max_batch: int = 8,
                 max_len: int = 1024):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        # both compiled paths close over cfg and thread the cross-attention
        # memory operand — tests assert a memory change reaches the logits
        self._prefill = jax.jit(
            lambda p, t, mem: prefill(cfg, p, t, memory=mem))
        # (batch, memory shape) -> (step, aliased share, fused share)
        self._steps: dict = {}
        self._calls = itertools.count()

    # --- shared plumbing ----------------------------------------------------
    def _check_window(self, s: int, n_tokens: int) -> None:
        if s + n_tokens > self.max_len:
            raise ValueError(
                f"decode window overflows the KV cache: prompt length {s} "
                f"+ {n_tokens} new tokens > max_len {self.max_len}; "
                f"re-create the engine with max_len >= {s + n_tokens}")

    def _rehome(self, cache0: dict, b: int, s: int) -> dict:
        """Move the prefill cache (length s) into the serving-length cache.

        ``_check_window`` has already bounded ``s`` strictly below
        ``max_len``, so the slot write below cannot clip silently.
        """
        cache = init_cache(self.cfg, b, self.max_len)
        if "k" in cache0:
            cache["k"] = cache["k"].at[:, :, :, :s].set(cache0["k"])
            cache["v"] = cache["v"].at[:, :, :, :s].set(cache0["v"])
        if "ssm" in cache0:
            cache["ssm"] = cache0["ssm"]
            cache["conv"] = cache0["conv"]
        return cache

    def _decoder(self, cache: dict, tok: jax.Array, memory):
        """The compiled decode step for this batch and memory shape (see
        :func:`compile_decode`, compiled on first use), its shares of
        aliased cache bytes and of weight bytes read as stored, and
        ``cache`` moved into the step's order."""
        key = (tok.shape, None if memory is None else memory.shape)
        if key not in self._steps:
            step = compile_decode(self.cfg, self.params, cache, tok,
                                  jnp.int32(0), memory)
            self._steps[key] = (step, aliased_share(step, cache),
                                fused_weight_share(step, self.params))
        step, aliased, fused = self._steps[key]
        return step.run, aliased, fused, step.enter(cache)

    @staticmethod
    def _sample(logits: jax.Array, temperature: float,
                rng: jax.Array | None):
        if temperature <= 0.0 or rng is None:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(rng, logits / temperature
                                      ).astype(jnp.int32)

    @staticmethod
    def _next_key(rng: jax.Array | None):
        """Per-step subkey: a fixed key every step would make 'sampling'
        draw the same categorical variate at each position."""
        if rng is None:
            return None, None
        rng, sub = jax.random.split(rng)
        return rng, sub

    # --- serving path -------------------------------------------------------
    def generate(self, prompts: jax.Array, n_tokens: int,
                 memory: jax.Array | None = None,
                 temperature: float = 0.0,
                 rng: jax.Array | None = None) -> GenerationResult:
        """prompts: (B, S) int32 (same length; pad upstream).

        Under a profiler session (``repro.tracing``) each call records the
        spans ``serve.generate`` > ``serve.prefill``, ``serve.rehome``,
        ``serve.sample``, ``serve.wait``, ``serve.decode_step`` x
        (n_tokens - 1), ``serve.wait``, ``serve.to_host``, all with one
        engine-local call id, and the counter ``serve.exposed_s``: host
        seconds from each sync's return to the return of the engine's next
        dispatch (after the last sync, to the call's return), in which the
        device holds none of its work. A call that decodes also adds the
        compiled step's :func:`aliased_share` to ``serve.cache_aliased``
        and its :func:`fused_weight_share` to ``serve.weights_fused``.
        """
        b, s = prompts.shape
        self._check_window(s, n_tokens)
        with span("serve.generate", call=next(self._calls)):
            t0 = time.perf_counter()
            with span("serve.prefill"):
                logits, cache0 = self._prefill(self.params, prompts, memory)
            with span("serve.rehome"):
                cache = self._rehome(cache0, b, s)
            with span("serve.sample"):
                rng, sub = self._next_key(rng)
                next_tok = self._sample(logits[:, -1], temperature, sub)
            with span("serve.wait"):
                jax.block_until_ready(next_tok)
            synced = time.perf_counter()
            ttft = synced - t0

            toks = [next_tok]
            for pos in range(s, s + n_tokens - 1):
                with span("serve.decode_step"):
                    if pos == s:
                        step, aliased, fused, cache = self._decoder(
                            cache, toks[-1], memory)
                        count("serve.cache_aliased", aliased)
                        count("serve.weights_fused", fused)
                    logits_i, cache = step(self.params, cache, toks[-1],
                                           jnp.int32(pos), memory)
                    if pos == s:      # the first dispatch after the first sync
                        count("serve.exposed_s", time.perf_counter() - synced)
                    rng, sub = self._next_key(rng)
                    toks.append(self._sample(logits_i, temperature, sub))
            with span("serve.wait"):
                jax.block_until_ready(toks[-1])
            end = time.perf_counter()
            dt = end - synced
            with span("serve.to_host"):
                tokens = [t.tolist() for t in toks]
            last_sync = end if n_tokens > 1 else synced
            count("serve.exposed_s", time.perf_counter() - last_sync)
        tpot = dt / max(n_tokens - 1, 1)
        return GenerationResult(
            tokens=tokens, ttft=ttft, tpot=tpot,
            tokens_per_s=b * n_tokens / (ttft + dt))

    # --- measurement path ---------------------------------------------------
    def decode_steady(self, prompts: jax.Array, n_steps: int = 16,
                      warmup: int = 2,
                      memory: jax.Array | None = None) -> SteadyTiming:
        """Steady-state greedy decode with per-step timing.

        Runs prefill, compiles the decode step on first use and moves the
        cache into its format, then ``warmup`` decode steps whose times are
        discarded (cache/allocator warmup), then ``n_steps`` steps each
        synced and timed individually.
        The decode step's cost is ``max_len``-shaped (slot attention runs
        over the whole cache regardless of position), so every steady step
        does identical work — the per-step spread is measurement noise, not
        workload drift, which is what lets the validation report quote a
        trimmed mean.
        """
        b, s = prompts.shape
        self._check_window(s, warmup + n_steps + 1)
        t0 = time.perf_counter()
        logits, cache0 = self._prefill(self.params, prompts, memory)
        cache = self._rehome(cache0, b, s)
        tok = self._sample(logits[:, -1], 0.0, None)
        jax.block_until_ready(tok)
        ttft = time.perf_counter() - t0

        step, _, _, cache = self._decoder(cache, tok, memory)
        pos = s
        for _ in range(warmup):
            logits_i, cache = step(self.params, cache, tok, jnp.int32(pos),
                                   memory)
            tok = self._sample(logits_i, 0.0, None)
            pos += 1
        jax.block_until_ready(tok)

        times: list[float] = []
        for _ in range(n_steps):
            t1 = time.perf_counter()
            logits_i, cache = step(self.params, cache, tok, jnp.int32(pos),
                                   memory)
            tok = self._sample(logits_i, 0.0, None)
            jax.block_until_ready(tok)
            times.append(time.perf_counter() - t1)
            pos += 1
        return SteadyTiming(ttft=ttft, warmup=warmup, step_times=times,
                            batch=b)

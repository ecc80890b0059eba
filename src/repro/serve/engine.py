"""Batched serving engine: prefill + decode with a slot-based KV cache
(continuous-batching-lite: fixed slots, per-slot position counters, greedy or
temperature sampling). This is the executable twin of the paper's §VIII.A
serving model — TTFT = prefill latency, TPOT = decode step latency.

Two decode drivers share the jitted step:

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
import time

import jax
import jax.numpy as jnp

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


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, max_batch: int = 8,
                 max_len: int = 1024):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        # both jitted paths close over cfg and thread the cross-attention
        # memory operand — tests assert a memory change reaches the logits
        self._decode = jax.jit(
            lambda p, c, t, pos, mem: decode_step(cfg, p, c, t, pos,
                                                  memory=mem))
        self._prefill = jax.jit(
            lambda p, t, mem: prefill(cfg, p, t, memory=mem))
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
        device holds none of its work.
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
                    logits_i, cache = self._decode(self.params, cache,
                                                   toks[-1], jnp.int32(pos),
                                                   memory)
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

        Runs prefill, then ``warmup`` decode steps whose times are discarded
        (the first step pays compilation, the next ones cache/allocator
        warmup), then ``n_steps`` steps each synced and timed individually.
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

        pos = s
        for _ in range(warmup):
            logits_i, cache = self._decode(self.params, cache, tok,
                                           jnp.int32(pos), memory)
            tok = self._sample(logits_i, 0.0, None)
            pos += 1
        jax.block_until_ready(tok)

        times: list[float] = []
        for _ in range(n_steps):
            t1 = time.perf_counter()
            logits_i, cache = self._decode(self.params, cache, tok,
                                           jnp.int32(pos), memory)
            tok = self._sample(logits_i, 0.0, None)
            jax.block_until_ready(tok)
            times.append(time.perf_counter() - t1)
            pos += 1
        return SteadyTiming(ttft=ttft, warmup=warmup, step_times=times,
                            batch=b)

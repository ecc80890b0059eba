"""The one traffic generator. A mix is a JSON file of parameters under
``bench/traffic/``; this module turns it and ``--seed`` into inputs.

Every seed gets the same sizes and the same number of units in the same
order; only the token ids differ. A unit is one batch: ``unit(mix, seed, i)``
is a pure function of its arguments, so a window may run as many units as
its time allows and the check can rebuild any of them.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent / "traffic"


def load(name: str) -> dict:
    mix = json.loads((ROOT / f"{name}.json").read_text())
    mix["name"] = name
    return mix


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one stream of one seed; any whole number >= 0."""
    return np.random.default_rng([int(seed) & (2**63 - 1), *stream])


def unit(mix: dict, seed: int, index: int, vocab: int) -> np.ndarray:
    """Prompts of unit ``index``: (batch, prompt_len) int32 token ids drawn
    uniformly from the vocabulary."""
    return rng(seed, 1, index).integers(
        0, vocab, (mix["batch"], mix["prompt_len"]), dtype=np.int32)


def check_sample(mix: dict, seed: int, finished: int) -> np.ndarray:
    """Indices of the finished requests that the check compares, drawn
    from the seed: ``mix["check_requests"]`` of them (all, where fewer
    finished), always with the last finished one in it."""
    n = min(int(mix["check_requests"]), finished)
    pick = rng(seed, 2).choice(finished - 1, size=n - 1, replace=False) \
        if n > 1 else np.zeros(0, np.int64)
    return np.sort(np.append(pick, finished - 1))

"""Sequence speculative decoding (Leviathan et al. [50]; paper §VIII.B).

Draft model proposes K tokens autoregressively; the target model scores the
whole window in ONE forward pass; tokens are accepted with probability
min(1, p_target/p_draft) (greedy variant: accept while argmax matches).
The analytical twin (expected tokens/step vs K and acceptance rate) lives in
core/serving.py; this is the executable version used by the tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..models import forward
from ..models.config import ModelConfig


def speculative_generate(target_cfg: ModelConfig, target_params,
                         draft_cfg: ModelConfig, draft_params,
                         prompt: jax.Array, n_tokens: int, window: int = 4):
    """Greedy sequence speculative decoding (KV-less reference executor:
    both models re-run on the growing sequence — correctness oracle for the
    acceptance logic, small-model scale).

    prompt: (1, S). Returns (tokens list, acceptance_rate, n_target_calls).
    """
    seq = prompt
    produced = 0
    accepted_total = 0
    proposed_total = 0
    target_calls = 0
    out: list[int] = []
    while produced < n_tokens:
        k = min(window, n_tokens - produced)
        # draft proposes k tokens greedily
        dseq = seq
        proposal = []
        for _ in range(k):
            dlogits = forward(draft_cfg, draft_params, dseq, remat=False,
                              dropless=True)
            nxt = jnp.argmax(dlogits[:, -1], -1).astype(jnp.int32)
            proposal.append(int(nxt[0]))
            dseq = jnp.concatenate([dseq, nxt[:, None]], axis=1)
        # target verifies in one pass over seq + proposal
        ver_in = jnp.concatenate(
            [seq, jnp.asarray([proposal], jnp.int32)], axis=1)
        tlogits = forward(target_cfg, target_params, ver_in, remat=False,
                          dropless=True)
        target_calls += 1
        s0 = seq.shape[1]
        greedy = jnp.argmax(tlogits[0, s0 - 1:s0 - 1 + k], -1)
        n_acc = 0
        for i in range(k):
            if int(greedy[i]) == proposal[i]:
                n_acc += 1
            else:
                break
        accepted = proposal[:n_acc]
        # bonus token from the target at the first mismatch (or window end)
        bonus = int(greedy[n_acc]) if n_acc < k else int(
            jnp.argmax(tlogits[0, s0 - 1 + k], -1))
        new_toks = accepted + [bonus]
        out.extend(new_toks)
        produced += len(new_toks)
        seq = jnp.concatenate(
            [seq, jnp.asarray([new_toks], jnp.int32)], axis=1)
        accepted_total += n_acc
        proposed_total += k
    rate = accepted_total / max(proposed_total, 1)
    return out[:n_tokens], rate, target_calls

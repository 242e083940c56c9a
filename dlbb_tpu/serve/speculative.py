"""The host side of speculative decoding, in numpy: the n-gram drafter
and the residual-sampling law the scheduler's verify units apply to the
logits a verify program returns (``docs/serving.md``, "Speculative
decoding").  Pure functions of their arguments; nothing here touches the
device.
"""

from __future__ import annotations

from typing import Optional

import numpy as np


def _ngram_propose(hist: list, gamma: int,
                   max_ngram: int = 3) -> Optional[list]:
    """Prompt-lookup / n-gram drafting (Saxena 2023): find the most
    recent earlier occurrence of the history's trailing n-gram (n from
    ``max_ngram`` down to 1) in ``hist`` (= the request's prompt token
    ids + every committed token) and propose the γ ids that followed
    it.  When the match sits d < γ positions back, the continuation
    runs off the end of the history after d tokens — but a trailing
    match at distance d means the history is locally d-periodic, so
    the proposal extends CYCLICALLY through that period rather than
    flat-padding (greedy feedback through a fixed table falls into
    short cycles, and cyclic extension is what lets a γ≫d proposal
    stay correct for the whole window).  Pure, deterministic function
    of the history — drafter determinism from trace seeds is a test
    invariant.  None = cold (no occurrence of even the last token):
    the scheduler falls back to a plain decode unit."""
    ln = len(hist)
    for n in range(min(max_ngram, ln - 1), 0, -1):
        key = hist[ln - n:]
        for start in range(ln - n - 1, -1, -1):
            if hist[start:start + n] == key:
                cont = list(hist[start + n:start + n + gamma])
                if len(cont) < gamma:
                    d = len(cont)  # == distance back to the match
                    cont += [cont[i % d] for i in range(d, gamma)]
                return cont
    return None


def softmax_np(logits: np.ndarray, temperature: float) -> np.ndarray:
    """Host-side temperature softmax (float64, max-subtracted) — the
    sampled path's target law ``p``.  The device never softmaxes: the
    verify logits come to host raw and every probability the sampler
    consumes is computed here, so the sampled law is exactly
    reproducible from the journal'd seeds."""
    z = np.asarray(logits, np.float64) / float(temperature)
    z = z - z.max(axis=-1, keepdims=True)
    e = np.exp(z)
    return e / e.sum(axis=-1, keepdims=True)


def residual_distribution(p_target: np.ndarray,
                          q_draft: np.ndarray) -> np.ndarray:
    """The rejection-correction distribution of speculative SAMPLING
    (Leviathan et al. 2023): ``norm(max(p - q, 0))``.  Degenerates to
    ``p`` when ``q`` dominates it everywhere (rejection then has zero
    probability, so the branch is never taken)."""
    resid = np.maximum(np.asarray(p_target, np.float64)
                       - np.asarray(q_draft, np.float64), 0.0)
    z = resid.sum()
    if z <= 0.0:
        return np.asarray(p_target, np.float64)
    return resid / z


def speculative_sample(p_target: np.ndarray, q_draft: np.ndarray,
                       draft_id: int,
                       rng: np.random.Generator) -> tuple[int, bool]:
    """One position of the residual-sampling correction — HOW the
    equivalence gate weakens for sampled (temperature > 0) decode:
    accept the drafted token with probability ``min(1, p/q)``; on
    rejection, sample from ``residual_distribution(p, q)``.  The
    composite law is exactly ``p`` (distribution-identity, pinned by
    ``tests/test_speculative.py``), so sampled speculative decode is
    distribution-identical — not token-identical — to the sequential
    sampler.  The engine's default serving path is greedy (argmax),
    which this correction degenerates to as temperature -> 0; with
    ``serving.temperature > 0`` the scheduler's verify units run this
    helper position-by-position over the host-side verify softmax
    (``q`` = the deterministic drafter's one-hot, so acceptance is
    ``p[draft]`` and the residual is ``p`` with the draft's mass
    removed — docs/serving.md)."""
    p = float(p_target[draft_id])
    q = float(q_draft[draft_id])
    accept_p = 1.0 if q <= 0.0 and p > 0.0 else (
        min(1.0, p / q) if q > 0.0 else 0.0)
    if rng.uniform() < accept_p:
        return int(draft_id), True
    resid = residual_distribution(p_target, q_draft)
    return int(rng.choice(len(resid), p=resid)), False

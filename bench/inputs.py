"""Inputs made from the seed: training token batches and serving requests.

``token_batch`` is the training data stream the program's ``TokenSource``
documents (a Zipf unigram mixture, one generator per row seeded from the
run seed, the step and the row), kept here so that the reference reads the
same tokens without taking them from the program.

``requests`` plays any serving traffic file. Every seed gets the same
schedule of arrivals and prompt lengths, entered at a place of its own, so
that runs with different seeds do the same amount of work in the same
bursts.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np


def token_batch(seed: int, step: int, *, vocab: int, seq_len: int, rows: int,
                zipf_a: float = 1.3) -> np.ndarray:
    """Rows ``[0, rows)`` of training step ``step``: int32 (rows, seq_len)."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    probs = ranks ** -zipf_a
    probs = probs / probs.sum()
    perm = rng.permutation(vocab)
    out = []
    for r in range(rows):
        doc = np.random.default_rng((seed * 1_000_003 + step) * 100_003 + r)
        out.append(perm[doc.choice(vocab, size=seq_len, p=probs)])
    return np.stack(out).astype(np.int32)


@dataclass
class Request:
    index: int
    due_s: float          # seconds after the window opens
    prompt: List[int]
    new_tokens: int
    session: str


def apportion(n: int, weights: List[float]) -> List[int]:
    """Split ``n`` into whole counts in proportion to ``weights``."""
    total = float(sum(weights))
    raw = [n * w / total for w in weights]
    counts = [int(math.floor(x)) for x in raw]
    order = sorted(range(len(raw)), key=lambda i: raw[i] - counts[i], reverse=True)
    for i in order[: n - sum(counts)]:
        counts[i] += 1
    return counts


def requests(traffic: Dict[str, Any], *, rate: float, seconds: float, seed: int,
             vocab: int) -> List[Request]:
    """Open-loop Poisson arrivals at ``rate`` per second over ``seconds``.

    One schedule serves every seed: the gaps are the exponential
    distribution's quantiles at evenly spaced probabilities and the prompt
    lengths are the mix's shares of the count, both shuffled once by the
    traffic's ``schedule_seed``. A seed rotates that schedule to start at a
    place of its own (bursts stay whole, only where they fall in the window
    moves) and draws the prompts' tokens. The first request is due one gap
    after the window opens, so that every seed's last request is due at the
    same time.
    """
    n = max(1, int(round(rate * seconds)))
    fixed = np.random.default_rng(int(traffic["schedule_seed"]))
    lengths: List[int] = []
    for length, count in zip(traffic["prompt_lengths"],
                             apportion(n, traffic["prompt_weights"]), strict=True):
        lengths += [int(length)] * count
    lengths = [lengths[i] for i in fixed.permutation(n)]
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = gaps[fixed.permutation(n)]
    rng = np.random.default_rng(seed)
    order = np.roll(np.arange(n), -int(rng.integers(n)))
    due = np.cumsum(gaps[order])  # every seed's schedule spans the same time
    sessions = int(traffic["sessions"])
    out = []
    for i, j in enumerate(order.tolist()):
        prompt = rng.integers(0, vocab, lengths[j]).tolist()
        out.append(Request(i, float(due[i]), prompt, int(traffic["new_tokens"]),
                           f"s{j % sessions}"))
    return out


def warmup_requests(traffic: Dict[str, Any], *, seed: int, vocab: int) -> List[Request]:
    """One request of every prompt length the mix sends (the shapes to warm)."""
    rng = np.random.default_rng(seed ^ 0x5EED)
    return [Request(-1 - i, 0.0, rng.integers(0, vocab, int(n)).tolist(),
                    int(traffic["new_tokens"]), f"warm{i}")
            for i, n in enumerate(traffic["prompt_lengths"])]

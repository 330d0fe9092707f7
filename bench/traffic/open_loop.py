"""Serving requests of an open loop: arrivals due on a schedule whatever
the server does.

Every seed gets the same work in another order.  There are
``round(rate * seconds)`` requests.  Their gaps are the ``(i + 0.5) / n``
quantiles of an exponential of mean ``1 / rate``, scaled to fill the
window, in an order the seed shuffles freely: Poisson arrivals, bursts
included, from the same set of gaps on every seed.  Their prompt and
output lengths are the same quantiles of clipped lognormals, dealt
round-robin into consecutive chunks of ``CHUNK`` requests and shuffled by
the seed inside each chunk, so that every stretch of the window (its end
too, whose requests finish after it) carries the same mix of lengths.
Prompt tokens are uniform over the vocabulary.  Every request decodes
greedily.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import List

import numpy as np

#: requests a chunk; the seed orders lengths only inside a chunk
CHUNK = 4


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``(i + 0.5) / n`` quantiles of the clipped lognormal ``spec``."""
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    x = [spec["median"] * math.exp(spec["sigma"] * v) for v in z]
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def deal(values: np.ndarray, rng) -> np.ndarray:
    """``values`` laid out so that each chunk of ``CHUNK`` consecutive
    slots holds ranks spread over the whole distribution (slot ``j`` of a
    chunk of ``m`` near quantile ``(j + 0.5) / m``), shuffled by the seed
    inside each chunk."""
    n = len(values)
    sizes = [min(CHUNK, n - s) for s in range(0, n, CHUNK)]
    key = np.concatenate([(np.arange(m) + 0.5) / m for m in sizes])
    out = np.empty(n, values.dtype)
    out[np.argsort(key, kind="stable")] = np.sort(values)
    for s in range(0, n, CHUNK):
        out[s:s + CHUNK] = rng.permutation(out[s:s + CHUNK])
    return out


def make(mix: dict, seed: int, seconds: float, vocab: int) -> List[dict]:
    """Requests ``{"due", "prompt", "max_new"}``, in due order."""
    n = max(1, round(mix["rate_per_s"] * seconds))
    rng = np.random.default_rng(seed)
    gaps = rng.permutation(
        [-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    due = (np.cumsum(gaps) - gaps[0]) * (seconds / gaps.sum())
    prompt = deal(quantiles(mix["prompt_len"], n), rng)
    out = deal(quantiles(mix["output_len"], n), rng)
    return [{"due": float(due[i]),
             "prompt": rng.integers(0, vocab, int(prompt[i]), dtype=np.int32),
             "max_new": int(out[i])} for i in range(n)]

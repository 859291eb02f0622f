"""Seeded traffic, from the parameters in ``traffic/<mix>.json``. One general
generator reads every mix; the program receives only what is generated here.

Sizes and inter-arrival gaps are taken at evenly spaced quantiles of the
mix's distributions, so every seed offers the same set of prompt lengths,
answer lengths and gaps: the same work, no sampling noise in how much a run
holds. ``--seed`` decides their order, how prompt and answer lengths pair
up, and the token ids (and the weights). A tail such as a 95th percentile
of time to first token then depends on the order only as far as the mix's
rate lets requests queue for a slot (PERF.md, PR 25: at 3.6 requests/s a
third of the orders doubled it, which is why ``chat-open`` offers 2.7).
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, List, Tuple

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    due_s: float              # seconds after the window opens (open loop)
    prompt: np.ndarray        # int32 token ids
    new_tokens: int
    temperature: float


def quantile_values(dist: Dict, n: int) -> np.ndarray:
    """n whole numbers at the quantiles (i + 0.5) / n of a distribution
    {"dist": "lognormal", "median", "sigma", "min", "max"} or
    {"dist": "uniform", "min", "max"}, clipped to [min, max]."""
    qs = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "uniform":
        vals = lo + qs * (hi - lo + 1) - 0.5
    elif dist["dist"] == "lognormal":
        nd = NormalDist()
        z = np.asarray([nd.inv_cdf(float(q)) for q in qs])
        vals = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def exponential_gaps(rate_per_s: float, n: int) -> np.ndarray:
    """n inter-arrival gaps at the quantiles of Exp(rate): a Poisson
    process's gaps, every run the same set."""
    qs = (np.arange(n) + 0.5) / n
    return -np.log1p(-qs) / float(rate_per_s)


def _order_rng(seed: int) -> np.random.Generator:
    """The stream that orders a schedule: of the run's seed, and apart from
    the stream that draws its tokens."""
    return np.random.default_rng([int(seed), 0x0DE5])


def _requests(traffic: Dict, vocab: int, order: np.random.Generator,
              tokens: np.random.Generator, n: int) -> List[Request]:
    prompts = order.permutation(quantile_values(traffic["prompt_tokens"], n))
    news = order.permutation(quantile_values(traffic["new_tokens"], n))
    temp = float(traffic.get("temperature", 0.0))
    return [Request(i, 0.0,
                    tokens.integers(0, vocab, int(p)).astype(np.int32),
                    int(g), temp)
            for i, (p, g) in enumerate(zip(prompts, news))]


def open_loop_schedule(traffic: Dict, vocab: int, seed: int, seconds: float
                       ) -> List[Request]:
    """Every request due inside a window of ``seconds``: round(rate x
    seconds) of them, arrival gaps from Exp(rate) in the seed's order."""
    n = max(1, int(round(float(traffic["rate_per_s"]) * seconds)))
    order = _order_rng(seed)
    reqs = _requests(traffic, vocab, order,
                     np.random.default_rng(int(seed)), n)
    gaps = order.permutation(exponential_gaps(traffic["rate_per_s"], n))
    due = np.cumsum(gaps)
    # the n-th arrival of a rate-r process lands near n / r = seconds; keep
    # every arrival inside the window whatever the order of the gaps
    due *= min(1.0, (seconds * (1.0 - 0.5 / n)) / float(due[-1]))
    for r, t in zip(reqs, due):
        r.due_s = float(t)
    return reqs


def train_batches(traffic: Dict, vocab: int, seed: int, count: int
                  ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """``count`` distinct (inputs, next-token labels) batches of
    ``batch_rows`` x ``seq_len`` tokens; every row differs."""
    rng = np.random.default_rng(int(seed))
    rows, t = int(traffic["batch_rows"]), int(traffic["seq_len"])
    out = []
    for _ in range(count):
        ids = rng.integers(0, vocab, (rows, t + 1)).astype(np.int32)
        out.append((ids[:, :-1].copy(), ids[:, 1:].copy()))
    return out


def lateness_ms(sent_s: List[float], due_s: List[float]) -> List[float]:
    """How late the generator sent each request (never negative: a request
    is not sent before it is due)."""
    return [max(0.0, (s - d) * 1e3) for s, d in zip(sent_s, due_s)]


def count_buckets(num_slots: int) -> List[int]:
    """The admission-count buckets a slot engine can reach: powers of two
    capped at the slot count (the rule of ``SlotGenerationEngine.
    _count_bucket``, restated here so warm-up needs nothing private)."""
    out, b = [], 1
    while b < num_slots:
        out.append(b)
        b *= 2
    return out + [num_slots]


def length_bucket(n: int, t_max: int, floor: int = 16) -> int:
    """The padded prompt length of a prompt of n tokens: the next power of
    two from ``floor``, capped at the context (``_round_up_pow2``)."""
    p = floor
    while p < n:
        p *= 2
    return min(p, t_max)


def length_buckets(dist: Dict, t_max: int) -> List[int]:
    """Every padded prompt length the mix can reach."""
    lo = length_bucket(int(dist["min"]), t_max)
    hi = length_bucket(int(dist["max"]), t_max)
    out, b = [], lo
    while b <= hi:
        out.append(b)
        b *= 2
    return out

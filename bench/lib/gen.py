"""The one traffic generator: reads a mix's parameters, draws its requests.

Every seed gets the same multiset of sizes, gaps and tenants: each
attribute is a set of evenly spaced quantiles of its declared distribution.
An open loop shuffles them by the seed; a closed loop deals them alike for
every seed (see ``closed_round``). So two seeds differ in order and in token
contents, never in the amount of work, and a spread between runs is a
spread of the system, not of the draw.

Mix keys (``bench/traffic/<mix>.json``):
  loop: "open" (Poisson arrivals at ``rate`` per second, timed from when each
        request was due) or "closed" (``clients`` callers, each sending its
        next request when the last one finished);
  prompt, output: {"dist": "uniform", "lo", "hi"} or
        {"dist": "lognormal", "median", "sigma", "lo", "hi"} (clipped);
  tasks: {"dist": "zipf", "s", "n"} over tenant ids 0..n-1 (id 0 the most
        frequent), or absent for one tenant;
  closed loops only: "prime": fraction of each client's first output already
        served, spread evenly over the clients, so the window opens on
        clients at every phase of a request.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request as the traffic defines it."""
    uid: int
    prompt: np.ndarray  # (S,) int32 token ids
    max_new: int
    task: int
    due: float | None  # seconds after the window opens; None: closed loop


def quantiles(n: int, offset: float = 0.5) -> np.ndarray:
    """n evenly spaced probabilities in (0, 1)."""
    return (np.arange(n) + offset) / n


def lengths(spec: dict, probs: np.ndarray) -> np.ndarray:
    """Token counts at the given quantiles of a length distribution."""
    lo, hi = spec["lo"], spec["hi"]
    if spec["dist"] == "uniform":
        vals = lo + probs * (hi - lo)
    elif spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in probs])
        vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def tenants(spec: dict | None, probs: np.ndarray) -> np.ndarray:
    if spec is None:
        return np.zeros(len(probs), np.int64)
    if spec["dist"] != "zipf":
        raise ValueError(f"unknown tenant distribution {spec['dist']!r}")
    ranks = np.arange(1, spec["n"] + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -spec["s"])
    cdf /= cdf[-1]
    return np.minimum(np.searchsorted(cdf, probs), spec["n"] - 1)


def _tokens(rng, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, n).astype(np.int32)


def open_loop(mix: dict, seconds: float, seed: int, vocab: int) -> list:
    """The requests due in a window of ``seconds``: round(rate * seconds)
    of them, exponential gaps at evenly spaced quantiles, shuffled."""
    n = max(1, round(mix["rate"] * seconds))
    rng = np.random.default_rng(seed)
    p = quantiles(n)
    gaps = rng.permutation(-np.log1p(-p) / mix["rate"])
    # scale so the n arrivals fill the window at exactly n / seconds
    due = np.cumsum(gaps) * seconds / (gaps.sum() + gaps.mean())
    prompt = rng.permutation(lengths(mix["prompt"], p))
    out = rng.permutation(lengths(mix["output"], p))
    task = rng.permutation(tenants(mix.get("tasks"), p))
    return [
        Planned(i, _tokens(rng, int(prompt[i]), vocab), int(out[i]),
                int(task[i]), float(due[i]))
        for i in range(n)
    ]


def closed_round(mix: dict, seed: int, k: int, vocab: int) -> list[Planned]:
    """Round k of a closed loop: the k-th request of every client. Each
    round is one full set of quantiles (a different set per round), dealt
    to the clients alike for every seed: where the KV pool is full, which
    sizes run side by side sets the work, so the seed changes only the
    token ids. In round 0 each client's output is cut to its priming
    share."""
    c = mix["clients"]
    deal = np.random.default_rng([k, 0])
    p = quantiles(c, offset=(0.5 + 0.618 * k) % 1.0)
    prompt = deal.permutation(lengths(mix["prompt"], p))
    out = deal.permutation(lengths(mix["output"], p))
    task = deal.permutation(tenants(mix.get("tasks"), p))
    if k == 0 and mix.get("prime"):
        served = np.random.default_rng([0, 1]).permutation(quantiles(c))
        out = np.maximum(1, np.ceil(out * (1.0 - served))).astype(np.int64)
    rng = np.random.default_rng([seed, k, 0])
    return [Planned(k * c + j, _tokens(rng, int(prompt[j]), vocab),
                    int(out[j]), int(task[j]), None) for j in range(c)]

"""The one request generator: a traffic file's parameters -> a schedule.

Every seed gets the same multiset of prompt lengths, output lengths and
inter-arrival gaps (stratified quantiles of the stated distributions), in an
order and with prompt tokens drawn from the seed. So two seeds differ in
which request comes when and what it says, not in how much work a run holds.
The idea follows the repository's seeded request stream
(``repro.launch.serve.synth_requests``), made open-loop and stratified here.

Traffic file keys (serving): ``arrivals`` = ``{"process": "poisson",
"rate_per_s": r}`` (``round(r * seconds)`` requests spread over the window)
or ``{"process": "batch", "requests": n, "block": b}`` (all due at t=0, and
every ``b`` consecutive requests hold the same multiset of lengths, so that
the part of the queue a window reaches holds the same work on every seed);
``prompt_tokens`` / ``output_tokens`` = ``{"dist": "log_uniform"|"uniform",
"min": a, "max": b}``, ``{"dist": "log_normal", "median": m, "sigma": s,
"min": a, "max": b}`` (the log-normal taken on [a, b]) or ``{"dist":
"fixed", "value": v}``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

_STREAM_ORDER, _STREAM_GAPS, _STREAM_OUT, _STREAM_TOKENS, _STREAM_CHECK = \
    range(5)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any whole seed >= 0."""
    return np.random.default_rng([int(seed), stream])


@dataclass
class Arrival:
    uid: int
    due_s: float
    tokens: np.ndarray
    max_new: int


def quantile_lengths(dist: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles (i + 0.5) / n of ``dist``."""
    kind = dist["dist"]
    if kind == "fixed":
        return np.full(n, int(dist["value"]), np.int64)
    q = (np.arange(n) + 0.5) / n
    lo, hi = float(dist["min"]), float(dist["max"])
    if kind == "log_uniform":
        x = np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
    elif kind == "uniform":
        x = lo + q * (hi - lo)
    elif kind == "log_normal":
        z = NormalDist()
        mu, sig = math.log(float(dist["median"])), float(dist["sigma"])
        f_lo, f_hi = (z.cdf((math.log(v) - mu) / sig) for v in (lo, hi))
        x = np.exp(mu + sig * np.array([z.inv_cdf(f_lo + u * (f_hi - f_lo))
                                        for u in q]))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _lengths(dist: Dict[str, Any], n: int, block: int,
             rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths, each block of ``block`` a permutation of the same
    quantiles of ``dist``."""
    q = quantile_lengths(dist, block)
    return np.concatenate([rng.permutation(q)
                           for _ in range(-(-n // block))])[:n]


def arrival_times(arrivals: Dict[str, Any], seconds: float,
                  seed: int) -> np.ndarray:
    """Due times in seconds from the window's start, ascending."""
    proc = arrivals["process"]
    if proc == "batch":
        return np.zeros(int(arrivals["requests"]))
    if proc != "poisson":
        raise ValueError(f"unknown arrival process {proc!r}")
    rate = float(arrivals["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate                 # exponential mid-quantiles
    gaps = rng_for(seed, _STREAM_GAPS).permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due * (seconds / gaps.sum())         # n arrivals in [0, seconds)


def schedule(traffic: Dict[str, Any], *, seed: int, seconds: float,
             vocab: int) -> List[Arrival]:
    """The cell's requests for one run, ordered by due time."""
    due = arrival_times(traffic["arrivals"], seconds, seed)
    n = len(due)
    block = int(traffic["arrivals"].get("block", n))
    prompt = _lengths(traffic["prompt_tokens"], n, block,
                      rng_for(seed, _STREAM_ORDER))
    out = _lengths(traffic["output_tokens"], n, block,
                   rng_for(seed, _STREAM_OUT))
    tok_rng = rng_for(seed, _STREAM_TOKENS)
    return [Arrival(uid=i, due_s=float(due[i]),
                    tokens=tok_rng.integers(1, vocab, int(prompt[i]),
                                            dtype=np.int64).astype(np.int32),
                    max_new=int(out[i]))
            for i in range(n)]


def check_sample(finished: Dict[int, int], k: int, seed: int) -> List[int]:
    """Uids of the requests whose outputs are compared: the one with the most
    served tokens, and ``k - 1`` more drawn from the seed. ``finished`` maps
    each finished uid to its served token count."""
    if not finished:
        return []
    uids = sorted(finished)
    longest = max(uids, key=lambda u: (finished[u], -u))
    rest = [u for u in uids if u != longest]
    pick = rng_for(seed, _STREAM_CHECK).permutation(len(rest))[:max(k - 1, 0)]
    return [longest] + sorted(rest[i] for i in pick)

"""The one general traffic generator: every mix is a data file it reads.

A traffic file under ``benchmark/traffic/`` says what arrives — lengths,
rates, loop type, data and optimizer — and this module turns it and the
``--seed`` into inputs. The program receives only the inputs. A later
mix of an existing kind is a new data file and no new code.

Length distributions (``prompt_len`` / ``output_len``):
``{"dist": "lognormal", "median", "sigma", "min", "max"}``,
``{"dist": "uniform", "min", "max"}``, ``{"dist": "fixed", "value"}``,
``{"dist": "mixture", "parts": [{"weight", ...a distribution...}]}``.
A uniform or lognormal distribution may carry ``"strata": k``: every run
of ``k`` consecutive draws then holds one draw from each of ``k`` equal
slices of the distribution, in seeded order, so any stretch of the
stream carries the same amount of work whatever the seed (a closed loop
judged on throughput would otherwise measure the luck of the draw).
Without the key draws are independent.
Arrivals (open loop): ``{"process": "poisson", "rate_per_s"}`` or
``{"process": "gamma", "rate_per_s", "cv"}`` (inter-arrival times of
mean ``1/rate`` and coefficient of variation ``cv``; ``cv = 1`` is
Poisson).
"""

from __future__ import annotations

from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def stratified(rng: np.random.Generator, n: int, k: int) -> np.ndarray:
    """``n`` numbers in [0, 1), every run of ``k`` consecutive ones
    holding one from each slice ``[i/k, (i+1)/k)`` in seeded order."""
    blocks = -(-n // k)
    u = (np.concatenate([rng.permutation(k) for _ in range(blocks)])
         + rng.random(blocks * k)) / k
    return u[:n]


def lengths(spec: Dict[str, Any], rng: np.random.Generator, n: int
            ) -> np.ndarray:
    """``n`` integer lengths drawn from ``spec``."""
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if "strata" in spec:
        # through the inverse distribution function, slice by slice
        u = stratified(rng, n, int(spec["strata"]))
        lo, hi = int(spec["min"]), int(spec["max"])
        if dist == "uniform":
            return lo + np.floor(u * (hi - lo + 1)).astype(np.int64)
        if dist == "lognormal":
            z = np.asarray([NormalDist().inv_cdf(float(x))
                            for x in np.clip(u, 1e-12, 1 - 1e-12)])
            x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
            return np.clip(np.rint(x), lo, hi).astype(np.int64)
        raise ValueError(f"no strata for the distribution {dist!r}")
    if dist == "uniform":
        return rng.integers(spec["min"], spec["max"] + 1, n)
    if dist == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    if dist == "mixture":
        w = np.asarray([p["weight"] for p in spec["parts"]], float)
        which = rng.choice(len(w), size=n, p=w / w.sum())
        out = np.zeros(n, np.int64)
        for i, part in enumerate(spec["parts"]):
            idx = np.flatnonzero(which == i)
            out[idx] = lengths(part, rng, idx.size)
        return out
    raise ValueError(f"unknown length distribution {dist!r}")


def arrival_times(spec: Dict[str, Any], rng: np.random.Generator,
                  horizon_s: float) -> np.ndarray:
    """Due times in ``[0, horizon_s)`` of an open loop at the fixed
    rate the file states."""
    rate = float(spec["rate_per_s"])
    n = int(rate * horizon_s * 1.5) + 64
    if spec["process"] == "poisson":
        gaps = rng.exponential(1.0 / rate, n)
    elif spec["process"] == "gamma":
        k = 1.0 / float(spec["cv"]) ** 2
        gaps = rng.gamma(k, 1.0 / (rate * k), n)
    else:
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    t = np.cumsum(gaps)
    if t[-1] < horizon_s:   # cannot happen at 1.5x the mean count
        raise ValueError("arrival draw too short for the horizon")
    return t[t < horizon_s]


def requests(traffic: Dict[str, Any], rng: np.random.Generator, n: int,
             vocab: int, horizon: int) -> List[Dict[str, Any]]:
    """``n`` requests of the mix: prompt tokens uniform over the
    published vocabulary (no shared prefixes), an output length the
    request ends at (no eos token, so random weights cannot shorten
    the traffic), greedy. A prompt and its output never exceed the
    deployment's ``horizon``."""
    p_len = lengths(traffic["prompt_len"], rng, n)
    o_len = lengths(traffic["output_len"], rng, n)
    o_len = np.minimum(o_len, horizon - p_len)
    if (o_len < 1).any():
        raise ValueError("a prompt leaves no room for one output token")
    return [{"prompt": rng.integers(0, vocab, int(p)).tolist(),
             "max_tokens": int(o)} for p, o in zip(p_len, o_len)]


def zipf_tokens(spec: Dict[str, Any], rng: np.random.Generator, n: int,
                vocab: int) -> np.ndarray:
    """A unigram token stream with Zipf(``exponent``) frequencies over
    the published vocabulary, ranks scattered over the ids by a seeded
    permutation — something a model can learn, so the loss falls."""
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = ranks ** -float(spec["exponent"])
    ids = rng.permutation(vocab)
    return ids[rng.choice(vocab, size=n, p=p / p.sum())].astype(np.int32)

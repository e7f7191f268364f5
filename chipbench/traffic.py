"""The one traffic generator: reads a mix's data file and makes its requests.

A mix file (``traffic/<mix>.json``) holds only parameters:

``loop``
    ``"open"``: requests fall due on a schedule, at ``rate_rps`` requests
    per second, whatever the server does.  ``"backlog"``: the harness keeps
    at least ``backlog_factor`` x ``nslots`` requests waiting.
``arrivals``
    ``{"kind": "poisson"}`` or ``{"kind": "gamma", "cv": c}`` (gaps with
    coefficient of variation ``c``; ``c > 1`` is burstier than Poisson).
``prompt``
    ``{"kind": "unshared", "len": <dist>}``, or
    ``{"kind": "templates", "count": n, "len": <dist>, "zipf_alpha": a,
    "suffix_len": <dist>}``: a template chosen with probability
    proportional to ``rank ** -a``, then a suffix of its own.
``output_len``
    ``<dist>``.
``base_seed``
    Fixes the arrival times, the template lengths, and each request's
    template choice and lengths, in order.  ``--seed`` draws only the
    token ids, so every seed offers the same work: with a few tens of
    requests in a window, reordering the sizes moved the tails far more
    than two runs of one seed differ.
``prime_templates``
    With templates: the set-up serves each template once (one output
    token, the rarest first) before the window, so the window opens on a
    prefix cache in its steady state and not on a cold one.
``preempts``
    ``false`` where the mix is sized so that its live rows stay inside
    the pool and no request is preempted: the set-up then warms none of
    the page runs that suspends and restores gather and scatter.  Each
    run prints the window's preemptions.  Default ``true``.
``engine``
    Optional overrides of the configuration's engine settings, for a mix
    that needs another deployment (say more slots for a backlog).

A ``<dist>`` is ``{"uniform": [lo, hi]}`` (both ends included) or
``{"lognormal": {"mean": m, "sigma": s, "min": lo, "max": hi}}`` (the
mean is before clipping).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass
class Req:
    rid: int
    due: float              # seconds after the window opens (open loop)
    prompt: List[int]
    output_len: int
    template: int = -1      # template index, -1 when unshared


def draw(dist: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    if "uniform" in dist:
        lo, hi = dist["uniform"]
        return rng.integers(lo, hi + 1, size=n)
    if "lognormal" in dist:
        p = dist["lognormal"]
        sigma = p["sigma"]
        mu = math.log(p["mean"]) - sigma ** 2 / 2
        x = rng.lognormal(mu, sigma, size=n)
        return np.clip(np.rint(x), p["min"], p["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist!r}")


def gaps(arr: dict, rate: float, rng: np.random.Generator, n: int
         ) -> np.ndarray:
    mean = 1.0 / rate
    if arr["kind"] == "poisson":
        return rng.exponential(mean, size=n)
    if arr["kind"] == "gamma":
        shape = 1.0 / arr["cv"] ** 2
        return rng.gamma(shape, mean / shape, size=n)
    raise ValueError(f"unknown arrivals {arr!r}")


def zipf_probs(count: int, alpha: float) -> np.ndarray:
    p = 1.0 / np.arange(1, count + 1) ** alpha
    return p / p.sum()


def count_for(mix: dict, seconds: float, nslots: int) -> int:
    """How many requests a run draws: enough for the window, and the same
    for every seed."""
    if mix["loop"] == "open":
        return int(math.ceil(mix["rate_rps"] * seconds * 1.5)) + 32
    return 64 * nslots + int(seconds) * 8


def _base(mix: dict, k: int) -> np.random.Generator:
    """The k-th fixed stream of the mix: the same for every seed."""
    return np.random.default_rng([mix["base_seed"], k])


def template_prompts(mix: dict, seed: int, vocab: int) -> List[List[int]]:
    """The token ids of the mix's templates for ``seed``, by rank."""
    pr = mix["prompt"]
    tlen = draw(pr["len"], _base(mix, 3), pr["count"])
    rng = np.random.default_rng([seed % (1 << 63), 1])
    return [rng.integers(0, vocab, size=int(t)).tolist() for t in tlen]


def generate(mix: dict, seed: int, n: int, vocab: int) -> List[Req]:
    """``n`` requests of the mix for ``seed``, ordered by due time."""
    rng = np.random.default_rng(seed)
    pr = mix["prompt"]
    out_len = draw(mix["output_len"], _base(mix, 0), n)
    if mix["loop"] == "open":
        due = np.cumsum(gaps(mix["arrivals"], mix["rate_rps"],
                             _base(mix, 1), n))
        due -= due[0]                        # the first request opens the run
    else:
        due = np.zeros(n)
    if pr["kind"] == "templates":
        choice = _base(mix, 2).choice(
            pr["count"], size=n, p=zipf_probs(pr["count"], pr["zipf_alpha"]))
        suffix = draw(pr["suffix_len"], _base(mix, 4), n)
        temps = template_prompts(mix, seed, vocab)
        return [Req(i, float(due[i]),
                    temps[int(choice[i])] + rng.integers(
                        0, vocab, size=int(suffix[i])).tolist(),
                    int(out_len[i]), int(choice[i]))
                for i in range(n)]
    if pr["kind"] == "unshared":
        in_len = draw(pr["len"], _base(mix, 2), n)
        return [Req(i, float(due[i]),
                    rng.integers(0, vocab, size=int(in_len[i])).tolist(),
                    int(out_len[i]))
                for i in range(n)]
    raise ValueError(f"unknown prompt kind {pr['kind']!r}")

"""The one traffic generator: reads a mix file's parameters, makes an
open-loop schedule from the seed.

A run's traffic has three phases: ``warm_s`` seconds that bring the engines
to a steady load before the measured window, the window itself, and up to
``drain_s`` seconds after it, in which arrivals go on while the requests
due in the window wait for their first token.

Every seed gets the same work.  Within each phase the inter-arrival gaps
and the prompt and output lengths are fixed sets, one value per stratum of
equal probability (the distribution's mean over the stratum), so their sums
equal the distribution's means times the count.  The seed only orders them,
pairs prompts with outputs and draws the token ids: two seeds differ in
where the long requests fall, not in how much there is to do.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List

import numpy as np
from scipy.special import ndtri

_QUAD = 64          # quadrature points per stratum


@dataclasses.dataclass(frozen=True)
class Arrival:
    req_id: int
    due: float              # seconds after the traffic starts
    prompt: List[int]
    max_new: int
    phase: str              # "warm" | "window" | "drain"


def _strata_means(n: int, inverse_cdf) -> np.ndarray:
    """Mean of ``inverse_cdf(u)`` over each of ``n`` equal strata of u."""
    u = (np.arange(n)[:, None] + (np.arange(_QUAD)[None, :] + 0.5) / _QUAD) / n
    return inverse_cdf(u).mean(axis=1)


def _lognormal(spec: Dict):
    """Inverse CDF of a lognormal clipped to [min, max], given by its
    unclipped mean and sigma."""
    if spec["kind"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['kind']!r}")
    sigma = float(spec["sigma"])
    mu = math.log(float(spec["mean"])) - sigma ** 2 / 2
    lo, hi = int(spec["min"]), int(spec["max"])
    return lambda u: np.clip(np.exp(mu + sigma * ndtri(u)), lo, hi)


def lengths(spec: Dict, n: int) -> np.ndarray:
    """``n`` lengths: ``{"kind": "lognormal", "mean", "sigma", "min",
    "max"}``."""
    vals = _strata_means(n, _lognormal(spec))
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def length_mean(spec: Dict, points: int = 200_000) -> float:
    """The mean that ``lengths`` aims at, by fine quadrature."""
    return float(_lognormal(spec)((np.arange(points) + 0.5) / points).mean())


def gaps(spec: Dict, rate: float, n: int, span: float) -> np.ndarray:
    """``n`` inter-arrival gaps summing to ``span`` seconds."""
    if spec["kind"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['kind']!r}")
    g = _strata_means(n, lambda u: -np.log1p(-u) / rate)
    return g * (span / g.sum())


def schedule(mix: Dict, rate: float, seconds: float, seed: int,
             vocab: int) -> List[Arrival]:
    """Every arrival of one run, in due order."""
    if rate <= 0:
        raise ValueError(f"rate must be positive, not {rate}")
    phases = (("warm", 0.0, float(mix["warm_s"])),
              ("window", float(mix["warm_s"]), float(seconds)),
              ("drain", float(mix["warm_s"]) + seconds, float(mix["drain_s"])))
    out: List[Arrival] = []
    for index, (phase, start, span) in enumerate(phases):
        n = int(round(rate * span))
        if n == 0:
            continue
        rng = np.random.default_rng([seed, index])
        g = rng.permutation(gaps(mix["arrivals"], rate, n, span))
        due = start + np.concatenate([[0.0], np.cumsum(g)[:-1]])
        prompt_len = rng.permutation(lengths(mix["prompt_len"], n))
        output_len = rng.permutation(lengths(mix["output_len"], n))
        for t, p, o in zip(due, prompt_len, output_len):
            prompt = rng.integers(0, vocab, int(p)).tolist()
            out.append(Arrival(len(out), float(t), prompt, int(o), phase))
    return out

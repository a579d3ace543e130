"""The one traffic generator: an open-loop arrival schedule from a mix file.

A mix (``traffic/<name>.json``) holds only parameters:

    {"arrivals": {"process": "poisson", "rate_per_s": 1.5},
     "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                "min": 32, "max": 3072},
     "output": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                "min": 16, "max": 1024},
     "preroll_s": 15}

Distributions: ``lognormal`` (median, sigma, clipped to [min, max]),
``loguniform`` and ``uniform`` (over [min, max]).  Arrivals: ``poisson``
(exponential gaps at ``rate_per_s``).

The schedule has two parts, the pre-roll (``preroll_s``) and the
window (``--seconds``), each of round(rate x span) requests, and each a
pure function of the mix: its inter-arrival gaps, prompt lengths and
output lengths are the distribution's inverse CDF at the stratified
quantiles ``(i + 0.5) / n`` (the gaps scaled to fill the span), put in
one low-discrepancy order (``_spread``), so that long and short values,
and their pairings, are spread evenly over every part of the span.
The seed draws the prompts' token ids (and the harness the weights):
every seed serves the same arrivals and sizes.  A window holds a few
tens of requests, and with an order drawn from the seed its median
TTFT followed the order: 15-19% between seeds against 0-2% between two
runs of one seed, with a blocked permutation.  The arithmetic is that
of ``repro.data.traces.poisson_trace`` and the clipped lognormal of
``repro.data.synthetic``, made stratified.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

__all__ = ["Request", "schedule", "segments"]

_STD_NORMAL = NormalDist()
# the steps of the order (``_spread``) of gaps, output lengths and prompt
# lengths: those of the three-dimensional R-sequence, 1 / g**k with g the
# real root of x**4 = x + 1, whose points are even in all three at once;
# prompts take the step whose runs of 10 stray least from the mean
_G3 = 1.2207440846057594
ALPHA_GAP, ALPHA_OUTPUT, ALPHA_PROMPT = (_G3 ** -k for k in (1, 2, 3))


@dataclasses.dataclass(frozen=True)
class Request:
    rid: int
    due_s: float            # seconds after the schedule opens
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int
    window: bool            # due in the measured window


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths at the stratified quantiles of ``spec``'s distribution."""
    u = _quantiles(n)
    lo, hi = int(spec["min"]), int(spec["max"])
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.array([_STD_NORMAL.inv_cdf(x) for x in u])
        v = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    elif dist == "loguniform":
        v = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif dist == "uniform":
        v = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.round(v), lo, hi).astype(np.int64)


def _gaps(spec: dict, n: int, span: float) -> np.ndarray:
    """n exponential gaps at stratified quantiles, scaled to sum to
    ``span`` (the mean rate differs from ``rate_per_s`` by the rounding
    of n only)."""
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    g = -np.log1p(-_quantiles(n))
    return g * (span / g.sum())


def _spread(values: np.ndarray, alpha: float) -> np.ndarray:
    """``values`` in a low-discrepancy order: position i gets the value
    whose rank among ``values`` is the rank of ``frac((i + 1) x alpha)``
    among the n such points."""
    x = np.mod(np.arange(1, values.size + 1) * alpha, 1.0)
    out = np.empty_like(values)
    out[np.argsort(x, kind="stable")] = np.sort(values)
    return out


def segments(mix: dict, seconds: float) -> list:
    """(start_s, span_s, n_requests) of the pre-roll and of the window."""
    rate = float(mix["arrivals"]["rate_per_s"])
    pre = float(mix["preroll_s"])
    return [(0.0, pre, max(1, round(rate * pre))),
            (pre, float(seconds), max(1, round(rate * float(seconds))))]


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The open-loop schedule of one run, in due order: the pre-roll's
    requests, then the window's.  Only the token ids follow the seed."""
    rng = np.random.default_rng(seed)
    out = []
    for k, (start, span, n) in enumerate(segments(mix, seconds)):
        due = start + np.minimum(np.cumsum(_spread(
            _gaps(mix["arrivals"], n, span), ALPHA_GAP)), span)
        prompt_len = _spread(_lengths(mix["prompt"], n), ALPHA_PROMPT)
        out_len = _spread(_lengths(mix["output"], n), ALPHA_OUTPUT)
        for i in range(n):
            out.append(Request(
                rid=len(out), due_s=float(due[i]),
                prompt=rng.integers(1, vocab, size=int(prompt_len[i]),
                                    dtype=np.int32),
                max_new_tokens=int(out_len[i]), window=k == 1))
    return out

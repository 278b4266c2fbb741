"""The one traffic generator: reads a mix's data file, makes requests.

A mix (``bench/traffic/<name>.json``) gives length distributions, an arrival
process and its rate, and optionally what makes the window open at steady
state:

* ``decode_s``, the seconds a request spends decoding: set-up admits a
  starting population of ``rate x decode_s`` requests (Little's law), each
  part-way through its answer, so the decoding slots are as full as the rate
  keeps them;
* ``preroll_s``: arrivals at the same rate start that many seconds before
  the window opens, so the requests in prefill are too.

After the window the loop serves on until every request due in it has had
its first token, for at most ``FOLLOW_S`` seconds: a request that has none
by then is an answer that never came.

A mix may name another in ``extends`` and replace some of its top-level
keys, so that one shape of traffic can be offered at several rates.

Every seed gets the same work in the same order: lengths and inter-arrival
gaps are the stratified quantiles ``(i + 0.5) / n`` of their distributions,
put in one order drawn from ``ORDER_SEED``; the seed draws the token ids
(and ``bench/weights.py`` the weights).  A 95th percentile over the dozen
or so requests a window holds swings by some 15-20% with the order alone,
more than a bound may allow, so the order is held fixed.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os

import numpy as np

_QUANTILE_SAMPLE = 200_000  # draws from a fixed stream that quantiles read
FOLLOW_S = 60.0
ORDER_SEED = 0  # the one order of lengths and arrivals


@dataclasses.dataclass
class Planned:
    rid: int
    due_s: float | None  # offset from the window's opening; None = set-up
    prompt: list[int]
    max_new: int


@dataclasses.dataclass
class Traffic:
    requests: list[Planned]  # due inside the window, in due order
    population: list[Planned]  # admitted during set-up
    preroll: list[Planned] = dataclasses.field(
        default_factory=list)  # due before the window opens, in due order
    preroll_s: float = 0.0
    follow_s: float = FOLLOW_S


def load_mix(root: str, name: str) -> dict:
    with open(os.path.join(root, "bench", "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    base = mix.pop("extends", None)
    if base is not None:
        mix = {**load_mix(root, base), **mix}
    return mix


@functools.lru_cache(maxsize=None)
def _sample(dist_json: str) -> np.ndarray:
    d = json.loads(dist_json)
    rng = np.random.default_rng(0)
    kind = d["dist"]
    if kind == "lognormal":
        x = d["median"] * np.exp(d["sigma"] * rng.standard_normal(
            _QUANTILE_SAMPLE))
    elif kind == "uniform":
        x = rng.uniform(d["min"], d["max"], _QUANTILE_SAMPLE)
    elif kind == "gamma":  # mean 1, coefficient of variation cv
        shape = 1.0 / d["cv"] ** 2
        x = rng.gamma(shape, 1.0 / shape, _QUANTILE_SAMPLE)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return np.sort(x)


def quantiles(dist: dict, n: int, size_biased: bool = False) -> np.ndarray:
    """The n stratified quantiles of ``dist``, clipped to its bounds.
    ``size_biased`` weights each value by its size: the lengths of the
    requests that a random moment finds in service."""
    x = _sample(json.dumps(dist, sort_keys=True))
    if "min" in dist:
        x = np.clip(x, dist["min"], dist["max"])
    u = (np.arange(n) + 0.5) / n
    if not size_biased:
        return np.quantile(x, u)
    w = np.cumsum(x) / x.sum()
    return x[np.minimum(np.searchsorted(w, u), x.size - 1)]


def _lengths(dist: dict, n: int, rng: np.random.Generator,
             size_biased: bool = False) -> np.ndarray:
    return rng.permutation(
        np.rint(quantiles(dist, n, size_biased)).astype(np.int64))


def _dues(cv: float, n: int, seconds: float,
          rng: np.random.Generator) -> np.ndarray:
    """n arrival offsets in (0, seconds]: stratified gamma gaps in the
    order ``rng`` draws, scaled so that the last arrival is at ``seconds``."""
    if n == 0:
        return np.zeros(0)
    gaps = rng.permutation(quantiles({"dist": "gamma", "cv": cv}, n))
    return np.cumsum(gaps) * seconds / gaps.sum()


def make_traffic(mix: dict, seconds: float, seed: int,
                 vocab: int) -> Traffic:
    order = np.random.default_rng(ORDER_SEED)
    rng = np.random.default_rng(seed)
    arr = mix["arrivals"]
    rate = arr["rate_per_s"]
    n = max(1, int(round(rate * seconds)))
    due = _dues(arr["cv"], n, n / rate, order)

    def planned(rid, due_s, plen, max_new):
        return Planned(rid, due_s, rng.integers(0, vocab, int(plen)).tolist(),
                       int(max_new))

    prompts = _lengths(mix["prompt"], n, order)
    outs = _lengths(mix["output"], n, order)
    requests = [planned(i, float(due[i]), prompts[i], outs[i])
                for i in range(n)]

    population = []
    p = int(round(rate * mix.get("decode_s", 0)))
    if p:
        # a request in service at a random moment is drawn by its length
        # and is a uniform share of the way through its answer
        left = order.permutation((np.arange(p) + 0.5) / p)
        p_prompts = _lengths(mix["prompt"], p, order)
        p_outs = _lengths(mix["output"], p, order, size_biased=True)
        population = [planned(n + j, None, p_prompts[j],
                              max(1, round(p_outs[j] * left[j])))
                      for j in range(p)]

    preroll_s = float(mix.get("preroll_s", 0))
    k = int(round(rate * preroll_s))
    pre_due = _dues(arr["cv"], k, preroll_s, order) - preroll_s
    pre_prompts = _lengths(mix["prompt"], k, order)
    pre_outs = _lengths(mix["output"], k, order)
    preroll = [planned(n + p + j, float(pre_due[j]), pre_prompts[j],
                       pre_outs[j]) for j in range(k)]
    return Traffic(requests, population, preroll, preroll_s, FOLLOW_S)

"""One general traffic generator, driven by a mix's data file.

A mix (``benchmark/traffic/<mix>.json``) names a ``driver``, a module
``benchmark/drivers/<driver>.py``, and gives its parameters. Every seed
draws the SAME multiset of sizes and arrival gaps (stratified quantiles of
the stated distributions) in another order, with other token ids: runs
differ by order and content, never by the amount of work, so a spread
between seeds is the system's and not the sample's.
"""
import json
import math
import os
from statistics import NormalDist

import numpy as np

from . import manifest


def load_mix(name: str) -> dict:
    with open(os.path.join(manifest.DATA, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    drivers = manifest.module_names("drivers")
    if mix.get("driver") not in drivers:
        raise ValueError(f"traffic mix {name}: no driver "
                         f"{mix.get('driver')!r}; there are: "
                         f"{', '.join(drivers)}")
    return mix


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent stream per (seed, purpose); seeds above 2**32 are
    fine."""
    tag = sum(ord(c) * 131 ** i for i, c in enumerate(stream)) % (1 << 32)
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  tag])


def stratified(spec: dict, n: int) -> np.ndarray:
    """n whole numbers at the (i + 0.5) / n quantiles of ``spec``:
    {"dist": "lognormal", "median", "sigma", "min", "max"},
    {"dist": "uniform", "min", "max"} or {"dist": "constant", "value"}."""
    q = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "constant":
        return np.full(n, int(spec["value"]), np.int64)
    if dist == "uniform":
        vals = spec["min"] + q * (spec["max"] - spec["min"])
    elif dist == "lognormal":
        nd = NormalDist()
        z = np.array([nd.inv_cdf(float(x)) for x in q])
        vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def request_sizes(mix: dict, n: int, seed: int, stream: str = ""):
    """(prompt lengths, output lengths) of n requests: each a seeded
    permutation of the stratified sample, permuted independently."""
    rng = rng_for(seed, "sizes" + stream)
    return (rng.permutation(stratified(mix["prompt_len"], n)),
            rng.permutation(stratified(mix["output_len"], n)))


def poisson_due_times(rate_rps: float, n: int, seed: int,
                      stream: str = "") -> np.ndarray:
    """Due times of n arrivals of a Poisson process inside a span of
    n / rate seconds: the stratified exponential gaps, scaled to fill the
    span and permuted; the span starts half of the first gap before the
    first arrival, so that every seed has all n inside it."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / float(rate_rps)
    gaps *= (n / float(rate_rps)) / gaps.sum()
    gaps = rng_for(seed, "arrivals" + stream).permutation(gaps)
    return np.cumsum(gaps) - gaps[0] / 2.0


def prompt_tokens(vocab: int, length: int, seed: int, index: int):
    """Random token ids of one prompt; distinct requests share nothing."""
    return rng_for(seed, f"prompt{index}").integers(
        0, vocab, int(length), dtype=np.int32)


def train_batch(vocab: int, batch: int, seq: int, seed: int, step: int):
    """The [batch, seq] token ids of one training step; rows all differ."""
    return rng_for(seed, f"batch{step}").integers(
        0, vocab, (int(batch), int(seq)), dtype=np.int32)

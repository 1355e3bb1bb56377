"""The one traffic generator. A mix is a JSON file of parameters
(``traffic/<mix>.json``); nothing about a mix lives in code.

Keys of a mix:

  loop          "open" (arrivals on a schedule, whatever the system does)
                or "closed" (one client per engine lane; each sends its
                next request when the last one finishes)
  batch         engine lanes (decode batch)
  rate_per_s    open loop: mean arrival rate, fixed in the mix
  prompt, output  length distributions: {"dist": "lognormal", "median",
                "sigma", "min", "max"} or {"dist": "uniform", "min", "max"}
  ramp_s        seconds the schedule runs before the measured window
  drain_cap_s   open loop: how long after the window the run waits for the
                window's requests (a request past it is a miss)
  deadline_ms   optional per-request deadline (the engine then caps each
                decode burst at its policy's ``deadline_burst_cap``)
  warm_bursts   longest decode burst warmed up in set-up (default: the
                longest output)
  why           one line for the reader

Every seed gets the same sizes and arrival gaps, in another order, so
that seeds change which request comes when and the token ids, not how
much work a run holds. Lengths come in blocks: the closed loop's of
``batch`` requests (the first block fills every lane, each later one
refills them), the open loop's one block of all its arrivals. A block
holds the distribution's quantiles at (i + 1/2) / n, in a stratified
order: sorted index bitrev(i) XOR a mask drawn from the seed, so that any
2^k requests in a row from an aligned start hold one length from each of
2^k equal strata. A run that uses only part of a block (the closed loop's
first refills) then still gets the same spread of lengths on every seed.
Arrival gaps are the exponential's quantiles, shuffled by the seed.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import List, Optional

import numpy as np

SET_SIZE = 4096          # closed loop: requests planned (whole blocks)


@dataclasses.dataclass
class Planned:
    index: int
    prompt: np.ndarray          # (S,) int32
    max_new: int
    due: Optional[float] = None  # open loop: seconds after the origin


def _quantiles(spec: dict, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        nd = statistics.NormalDist()
        z = np.array([nd.inv_cdf(float(p)) for p in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + u * (spec["max"] - spec["min"] + 1)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(v), spec["min"], spec["max"]).astype(np.int64)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def stratified_order(n: int, mask: int) -> np.ndarray:
    """A permutation of range(n): bit-reversed i XOR ``mask`` over the
    next power of two, the values under n kept in turn."""
    bits = max(1, (n - 1).bit_length())
    i = np.arange(1 << bits)
    rev = np.zeros_like(i)
    for b in range(bits):
        rev |= ((i >> b) & 1) << (bits - 1 - b)
    out = rev ^ (mask % (1 << bits))
    return out[out < n]


def _lengths(spec: dict, n: int, block: int, rng) -> np.ndarray:
    """n lengths: blocks of the distribution's ``block`` quantiles, each
    in a stratified order of its own."""
    q = _quantiles(spec, block)
    hi = 1 << max(1, (block - 1).bit_length())
    return np.concatenate([q[stratified_order(block, int(rng.integers(hi)))]
                           for _ in range(-(-n // block))])[:n]


def max_len(mix: dict) -> int:
    return mix["prompt"]["max"] + mix["output"]["max"]


def n_open(mix: dict, seconds: float) -> int:
    """Open loop: arrivals in the ramp and the window together."""
    return max(1, round(mix["rate_per_s"] * (mix["ramp_s"] + seconds)))


def open_schedule(mix: dict, seed: int, seconds: float,
                  vocab: int) -> List[Planned]:
    """Arrivals over [0, ramp + seconds): exponential gaps (a Poisson
    process' quantiles, shuffled) scaled to fill the horizon exactly."""
    n = n_open(mix, seconds)
    horizon = mix["ramp_s"] + seconds
    u = (np.arange(n) + 0.5) / n
    gaps = _rng(seed, 0).permutation(-np.log1p(-u))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * horizon / gaps.sum()
    return _requests(mix, seed, n, n, vocab, list(due))


def closed_requests(mix: dict, seed: int, vocab: int) -> List[Planned]:
    n = SET_SIZE // mix["batch"] * mix["batch"]
    return _requests(mix, seed, n, mix["batch"], vocab, [None] * n)


def _requests(mix, seed, n, block, vocab, due) -> List[Planned]:
    plen = _lengths(mix["prompt"], n, block, _rng(seed, 1))
    olen = _lengths(mix["output"], n, block, _rng(seed, 2))
    toks = _rng(seed, 3)
    return [Planned(i, toks.integers(0, vocab, int(plen[i])).astype(np.int32),
                    int(olen[i]), due[i]) for i in range(n)]


def describe(mix: dict, seconds: float) -> dict:
    """Mean lengths and the window's expected arrivals (for logs)."""
    n = 4096
    out = {"prompt_mean": float(_quantiles(mix["prompt"], n).mean()),
           "output_mean": float(_quantiles(mix["output"], n).mean())}
    if mix["loop"] == "open":
        out["window_arrivals"] = mix["rate_per_s"] * seconds
    return out

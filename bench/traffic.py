"""The one traffic generator: reads a mix file and a seed, returns the
open-loop schedule of one run.

A mix file (``bench/traffic/<name>.json``) holds only parameters:

  rate_rps          mean arrival rate, requests per second
  arrivals          "poisson": exponential gaps between arrivals
  prompt_tokens     {"median", "sigma", "min", "max"}: clamped lognormal
  output_tokens     the same, for the tokens each request asks for
  tenants           {"popularity": "equal"} or {"zipf_s": s}
  burst             optional {"factor", "on_s", "off_s"}: the rate is
                    ``factor`` times higher during ON than during OFF,
                    with the same mean
  prefix_tokens     optional: every prompt of a tenant starts with that
                    tenant's own prefix of this many tokens
  order             optional: "seeded" (the default) or "fixed"
  assumed           optional: the basis of each parameter; not read

Every seed gets the same work: the gaps, the prompt and output lengths
and the tenant shares are the quantiles of their distributions over the
window's request count. The seed draws the token ids and, unless the
order is "fixed", the order of the gaps, lengths and tenants. A run's
load therefore does not move with its seed; under a seeded order two
seeds differ as two orders of one day's requests do, under a fixed one
only in the tokens.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    due_s: float            # seconds after the window opens
    tenant: int             # index into the deployment's tenants
    prompt: np.ndarray      # (S,) int32 token ids
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lengths(spec: dict, n: int) -> np.ndarray:
    """Clamped lognormal lengths at the n mid-quantiles."""
    from statistics import NormalDist
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _gaps(rate: float, n: int) -> np.ndarray:
    """Exponential gaps at the n mid-quantiles, scaled to mean 1/rate
    exactly: the n arrivals end at n / rate for every seed."""
    g = -np.log1p(-_quantiles(n))
    return g / g.mean() / rate


def _tenant_counts(spec: dict, n_tenants: int, n: int) -> np.ndarray:
    if spec.get("popularity") == "equal":
        w = np.ones(n_tenants)
    elif "zipf_s" in spec:
        w = 1.0 / np.arange(1, n_tenants + 1) ** spec["zipf_s"]
    else:
        raise ValueError(f"unknown tenant popularity {spec!r}")
    share = w / w.sum() * n
    counts = np.floor(share).astype(np.int64)
    # largest remainders take the requests the floors left over
    rest = np.argsort(-(share - counts), kind="stable")[:n - counts.sum()]
    counts[rest] += 1
    return counts


def _burst_time(t: np.ndarray, spec: dict) -> np.ndarray:
    """Map arrival times of a steady process onto a two-state process with
    the same mean rate: ON periods run ``factor`` times faster."""
    f, on, off = spec["factor"], spec["on_s"], spec["off_s"]
    period = on + off
    r_off = period / (f * on + off)          # relative rates, mean 1
    r_on = f * r_off
    work_per_period = r_on * on + r_off * off  # == period
    k, w = np.divmod(t, work_per_period)
    in_on = w < r_on * on
    return k * period + np.where(in_on, w / r_on,
                                 on + (w - r_on * on) / r_off)


def schedule(mix: dict, seed: int, seconds: float, n_tenants: int,
             vocab: int) -> List[Arrival]:
    """The arrivals due in a window of ``seconds``: ``floor(rate *
    seconds)`` requests, all due inside it."""
    if mix.get("arrivals") != "poisson":
        raise ValueError(f"unknown arrival process {mix.get('arrivals')!r}")
    n = int(math.floor(mix["rate_rps"] * seconds))
    if n < 1:
        raise ValueError(f"{seconds} s at {mix['rate_rps']} req/s is no "
                         "request")
    rng = np.random.default_rng([seed, 0x7A1])
    order = {"seeded": rng,
             "fixed": np.random.default_rng(0x7A1)}[mix.get("order", "seeded")]
    due = np.cumsum(order.permutation(_gaps(mix["rate_rps"], n)))
    due = np.minimum(due, n / mix["rate_rps"])   # float sum round-off
    if "burst" in mix:
        due = _burst_time(due, mix["burst"])
    prompt_len = order.permutation(_lengths(mix["prompt_tokens"], n))
    out_len = order.permutation(_lengths(mix["output_tokens"], n))
    tenants = order.permutation(np.repeat(
        np.arange(n_tenants), _tenant_counts(mix["tenants"], n_tenants, n)))
    prefix_len = int(mix.get("prefix_tokens", 0))
    if prefix_len and prefix_len >= mix["prompt_tokens"]["min"]:
        raise ValueError("prefix_tokens must be shorter than every prompt")
    prefixes = rng.integers(0, vocab, size=(n_tenants, prefix_len),
                            dtype=np.int32)
    out = []
    for i in range(n):
        body = rng.integers(0, vocab, size=int(prompt_len[i]) - prefix_len,
                            dtype=np.int32)
        prompt = np.concatenate([prefixes[tenants[i]], body])
        out.append(Arrival(float(due[i]), int(tenants[i]), prompt,
                           int(out_len[i])))
    return out

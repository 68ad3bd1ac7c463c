"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests the fleet finished
is drawn from the seed: the longest (prompt plus served tokens), one of
every tenant (so every chip of a multi-chip cell), and more at random
until the sample holds ``sample_tokens`` served tokens. The reference
runs once over each prompt with its served tokens. At every served
position it reads how far the served token's logit lies below the
reference's best; the mean of these gaps over the sample is compared
with the limit the configuration file states. Decoding is greedy, so a
sound program serves the reference's best token up to rounding, and a
gap opens only where rounding flips a near tie.
"""
from __future__ import annotations

from types import ModuleType
from typing import Dict, List

import numpy as np

import reference
from loop import Record, Sent


def sample(rec: Record, seed: int, min_tokens: int) -> List[Sent]:
    done = [s for s in rec.sent if s.req is not None and s.req.done.is_set()
            and s.req.finish_reason == "length"]
    if not done:
        return []
    rng = np.random.default_rng([seed, 0xC4EC])
    size = lambda s: len(s.arrival.prompt) + len(s.req.out_tokens)  # noqa
    chosen = {id(max(done, key=size)): max(done, key=size)}
    by_tenant: Dict[str, List[Sent]] = {}
    for s in done:
        by_tenant.setdefault(s.tenant, []).append(s)
    for t in sorted(by_tenant):
        s = by_tenant[t][rng.integers(len(by_tenant[t]))]
        chosen.setdefault(id(s), s)
    rest = [done[i] for i in rng.permutation(len(done))]
    for s in rest:
        if sum(len(c.req.out_tokens) for c in chosen.values()) >= min_tokens:
            break
        chosen.setdefault(id(s), s)
    return list(chosen.values())


def served(chosen: List[Sent]):
    """(prompt, served tokens) pairs, copied off the requests."""
    return [(np.asarray(s.arrival.prompt, np.int32),
             [int(t) for t in s.req.out_tokens]) for s in chosen]


def _numbers(mean: float, tokens: int, tenants_seen: int, limits: dict,
             n_tenants: int) -> dict:
    return {
        "mean_gap": {"value": mean, "limit": limits["max_mean_gap"]},
        "sampled_tokens": {"value": tokens, "limit": limits["sample_tokens"]},
        "tenants_sampled": {"value": tenants_seen, "limit": n_tenants},
    }


def judge(arch: ModuleType, m, seed: int, seqs, limits: dict,
          n_tenants: int, tenants_seen: int, control: bool = False):
    """(the numbers compared, each with its limit; readings beside them
    that are not compared: the widest gap and, with ``control``, the
    control's own; with ``control``, the control's numbers under the same
    limits, else None). ``arch`` and ``m``: the architecture module and
    its sizes."""
    tokens = sum(len(out) for _, out in seqs)
    bad = any(t < 0 or t >= m.vocab for _, out in seqs for t in out)
    mean = widest = ctl_mean = float("inf")
    readings = {}
    if seqs and not bad:
        gaps, ctl_gaps = reference.served_gaps(arch, m, seed, seqs, control)
        flat = np.concatenate(gaps)
        mean, widest = float(flat.mean()), float(flat.max())
        readings["flip_share"] = float(np.mean(flat > 0))
        if control:
            ctl = np.concatenate(ctl_gaps)
            ctl_mean = float(ctl.mean())
            readings.update(control_mean_gap=ctl_mean,
                            control_widest_gap=float(ctl.max()),
                            control_flip_share=float(np.mean(ctl > 0)))
    readings["widest_gap"] = widest
    numbers = _numbers(mean, tokens, tenants_seen, limits, n_tenants)
    ctl_numbers = (_numbers(ctl_mean, tokens, tenants_seen, limits,
                            n_tenants) if control else None)
    return numbers, readings, ctl_numbers


def passes(numbers: dict) -> bool:
    return (numbers["mean_gap"]["value"] <= numbers["mean_gap"]["limit"]
            and numbers["sampled_tokens"]["value"]
            >= numbers["sampled_tokens"]["limit"]
            and numbers["tenants_sampled"]["value"]
            >= numbers["tenants_sampled"]["limit"])

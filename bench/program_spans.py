"""The program's own host spans (``repro.core.spans``) in a traced run, on
the trace's clock.

The program records its spans in memory on ``time.monotonic()`` while the
profiler is on. Only the traced window's spans are read (from
``rec.trace_t0`` to the end of the window: one process may serve several
windows). They are mapped onto the clock of the device planes by pairing
each program ``rc3e.fleet.round`` span with the ``bench.fleet_step`` span
of the trace it ran in, one to one and in order: the offset is the median
of the start differences. If the counts differ, a round falls outside its
step, or the differences spread by more than ``MAX_SPREAD_S``, nothing is
mapped and the metrics that need the trace's clock read nothing.

A program without ``repro.core.spans`` records nothing: every function
here then returns None.
"""
from __future__ import annotations

import statistics
import time
from typing import Dict, List, Optional

import numpy as np

from loop import clock
from measure import percentile
from trace_reduce import busy_intervals

MAX_SPREAD_S = 50e-6
ROUND = "rc3e.fleet.round"
ENGINE_STEP = "rc3e.fleet.engine_step"
READBACK = "rc3e.engine.readback"
PREFILL = "rc3e.engine.prefill"
FLEET_STEP = "bench.fleet_step"
OTHER_CHIPS = "other chips' engine steps"
OUTSIDE = "outside rc3e.fleet.round"


def recorded() -> Optional[list]:
    """Every span the program holds, or None where it records none."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    return spans.recorded()


def _subset(spans: list, keep: List[int]) -> list:
    """``spans[keep]`` with each parent index renumbered (None where the
    parent is not kept)."""
    new = {old: i for i, old in enumerate(keep)}
    return [spans[i]._replace(parent=new.get(spans[i].parent)) for i in keep]


def in_window(run) -> Optional[list]:
    """The program's spans that began in the traced window, on
    ``time.monotonic()``."""
    rec = run.rec
    spans = recorded()
    if spans is None or rec.trace_t0 is None:
        return None
    shift = time.monotonic() - clock()          # loop clock -> monotonic
    lo = rec.trace_t0 + shift
    hi = max([rec.t1] + [s.end for s in rec.steps]) + shift
    return _subset(spans, [i for i, s in enumerate(spans)
                           if lo <= s.t0 <= hi])


def offset(rounds: list, steps: list) -> Optional[float]:
    """Seconds to add to a program span to place it on the trace's clock,
    from the rounds and the trace's fleet steps (name, start, end)."""
    if not rounds or len(rounds) != len(steps):
        return None
    d = [st[1] - r.t0 for r, st in zip(rounds, steps)]
    q = statistics.quantiles(d, n=4) if len(d) > 1 else [d[0]] * 3
    if q[2] - q[0] > MAX_SPREAD_S:
        return None
    off = statistics.median(d)
    tol = MAX_SPREAD_S
    if any(r.t0 + off < st[1] - tol or r.t1 + off > st[2] + tol
           for r, st in zip(rounds, steps)):
        return None
    return off


def on_trace(run) -> Optional[list]:
    """The traced window's program spans on the trace's clock."""
    if run.trace is None:
        return None
    spans = in_window(run)
    if spans is None:
        return None
    off = offset([s for s in spans if s.name == ROUND],
                 [s for s in run.trace.spans if s[0] == FLEET_STEP])
    if off is None:
        return None
    return [s._replace(t0=s.t0 + off, t1=s.t1 + off) for s in spans]


# ---- interval arithmetic on sorted, disjoint (start, end) rows ----
def _union(iv) -> np.ndarray:
    iv = np.asarray(iv, np.float64).reshape(-1, 2)
    if len(iv) == 0:
        return iv
    return busy_intervals(iv, float(iv[:, 0].min()), float(iv[:, 1].max()))


def _below(iv: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Length of ``iv`` below each point of ``x``."""
    if len(iv) == 0:
        return np.zeros_like(x)
    lens = iv[:, 1] - iv[:, 0]
    before = np.concatenate([[0.0], np.cumsum(lens)])
    i = np.searchsorted(iv[:, 0], x, side="right")
    j = np.maximum(i - 1, 0)
    part = np.clip(x - iv[j, 0], 0.0, lens[j])
    return np.where(i > 0, before[j] + part, 0.0)


def _overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Length of the intersection of two unions."""
    if len(a) == 0 or len(b) == 0:
        return 0.0
    return float(np.sum(_below(b, a[:, 1]) - _below(b, a[:, 0])))


def _idle(dev, t0: float, t1: float) -> np.ndarray:
    busy = busy_intervals(dev.ops, t0, t1)
    edges = np.concatenate([[t0], busy.ravel(), [t1]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def _chip(plane: str) -> int:
    return int(plane.rsplit(":", 1)[1])


def _iv(spans: list) -> np.ndarray:
    return _union([(s.t0, s.t1) for s in spans])


def idle_split(run) -> Optional[Dict[str, float]]:
    """Shares of the traced window, in percent and mean over the cell's
    chips, in which a chip runs no operation while: its own engine step
    is open (``own``); another chip's engine step is (``other``); the
    fleet round is, outside every engine step (``round``)."""
    spans = on_trace(run)
    if spans is None:
        return None
    t0, t1 = run.trace.window
    steps = [s for s in spans if s.name == ENGINE_STEP]
    all_steps = _iv(steps)
    rounds = _union(np.concatenate([_iv([s for s in spans
                                         if s.name == ROUND]), all_steps]))
    out = {"own": [], "other": [], "round": []}
    for plane, dev in run.trace.devices.items():
        idle = _idle(dev, t0, t1)
        chip = _chip(plane)
        own = _overlap(idle, _iv([s for s in steps
                                  if s.attrs.get("chip") == chip]))
        in_steps = _overlap(idle, all_steps)
        out["own"].append(own)
        out["other"].append(in_steps - own)
        out["round"].append(_overlap(idle, rounds) - in_steps)
    return {k: 100.0 * float(np.mean(v)) / (t1 - t0) for k, v in out.items()}


def idle_phases(run) -> Optional[Dict[str, float]]:
    """Device idle seconds in the traced window, mean over chips, split by
    the innermost program span open at the time: the chip's own engine
    phases by span name, other chips' engine steps as one, the fleet's
    round phases by name, and the time outside every round."""
    spans = on_trace(run)
    if spans is None:
        return None
    t0, t1 = run.trace.window
    children: Dict[Optional[int], List[int]] = {}
    for i, s in enumerate(spans):
        children.setdefault(s.parent, []).append(i)
    chip_of: List[Optional[int]] = []
    for s in spans:          # a parent is recorded before its children
        own = s.attrs.get("chip") if s.name == ENGINE_STEP else None
        chip_of.append(own if own is not None or s.parent is None
                       else chip_of[s.parent])
    tree = [i for i, s in enumerate(spans)
            if s.name.startswith(("rc3e.fleet.", "rc3e.engine."))]
    total: Dict[str, float] = {}
    for plane, dev in run.trace.devices.items():
        idle = _idle(dev, t0, t1)
        chip = _chip(plane)
        rounds = _iv([spans[i] for i in tree if spans[i].name == ROUND])
        total[OUTSIDE] = total.get(OUTSIDE, 0.0) + float(
            np.sum(idle[:, 1] - idle[:, 0])) - _overlap(idle, rounds)
        for i in tree:
            s = spans[i]
            mine = chip_of[i] is None or chip_of[i] == chip
            if not mine and spans[i].name != ENGINE_STEP:
                continue             # inside another chip's engine step
            label = s.name if mine else OTHER_CHIPS
            kids = [] if not mine else [spans[k] for k in
                                        children.get(i, [])]
            part = _overlap(idle, _iv([s])) - _overlap(idle, _iv(kids))
            total[label] = total.get(label, 0.0) + part
    n = len(run.trace.devices)
    return {k: v / n for k, v in sorted(total.items(), key=lambda kv: -kv[1])}


def engine_host_ms(run) -> Optional[float]:
    """Median, over the traced engine steps that decoded, of the engine
    step's time less its readback: the host time of a step outside
    waiting for its chip."""
    spans = in_window(run)
    if spans is None:
        return None
    back: Dict[int, float] = {}
    for s in spans:
        if s.name == READBACK and s.parent is not None:
            back[s.parent] = back.get(s.parent, 0.0) + s.t1 - s.t0
    host = [(s.t1 - s.t0 - back[i]) * 1e3 for i, s in enumerate(spans)
            if s.name == ENGINE_STEP and i in back]
    return percentile(host, 50)


def prefill_fill_share(run) -> Optional[float]:
    """Real context tokens over padded tokens of the traced prefills, in
    percent."""
    spans = in_window(run)
    if spans is None:
        return None
    pre = [s.attrs for s in spans if s.name == PREFILL]
    padded = sum(a["padded"] for a in pre)
    return 100.0 * sum(a["tokens"] for a in pre) / padded if padded else None

"""The open loop: one thread submits every request when it falls due and
drives the lockstep fleet while work is pending.

Each iteration submits what is due, then calls ``fleet.step()`` if any
request is unfinished, or sleeps until the next due time. A client of the
lockstep fleet sees a token when the step that made it returns, so token
times are read from ``len(req.out_tokens)`` after every step. Host spans
(``jax.profiler.TraceAnnotation``) mark the loop's own calls: submit,
fleet step, the generator's wait and its bookkeeping.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional

from jax.profiler import TraceAnnotation

from traffic import Arrival

clock = time.perf_counter


@dataclasses.dataclass
class Sent:
    arrival: Arrival
    due: float                          # absolute, on ``clock``
    tenant: str
    submitted: Optional[float] = None
    req: object = None
    failed: Optional[str] = None        # why the fleet refused it
    token_times: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Step:
    start: float
    end: float
    decoded: int                        # slots decoded, all engines
    tokens: List[tuple]                 # (Sent index, token index) made
    pool_share: float                   # mean used/total pages over devices


@dataclasses.dataclass
class Record:
    t0: float
    t1: float
    sent: List[Sent]
    steps: List[Step]
    trace_t0: Optional[float] = None    # when the profiler was started


def run(fleet, tenants: List[str], arrivals: List[Arrival], seconds: float,
        trace_from: Optional[float] = None,
        start_trace: Optional[Callable[[], None]] = None) -> Record:
    """Serve ``arrivals`` for ``seconds``. With ``start_trace``, the
    profiler is started between two steps once ``trace_from`` seconds of
    the window have passed; the caller stops it after the window."""
    monitor = fleet.hv.monitor
    t0 = clock()
    end = t0 + seconds
    sent = [Sent(a, t0 + a.due_s, tenants[a.tenant]) for a in arrivals]
    rec = Record(t0, end, sent, [])
    pending: List[int] = []             # indices of unfinished Sents
    nxt = 0
    while True:
        now = clock()
        if now >= end:
            break
        if start_trace is not None and rec.trace_t0 is None \
                and now >= t0 + trace_from:
            start_trace()
            rec.trace_t0 = clock()
        with TraceAnnotation("bench.submit"):
            while nxt < len(sent) and sent[nxt].due <= now:
                s = sent[nxt]
                s.submitted = clock()
                try:
                    s.req = fleet.submit(s.tenant, s.arrival.prompt,
                                         max_new_tokens=s.arrival.max_new)
                    pending.append(nxt)
                except Exception as e:       # refused: counts as failed
                    s.failed = f"{type(e).__name__}: {e}"
                nxt += 1
        if pending:
            with TraceAnnotation("bench.fleet_step"):
                a = clock()
                decoded = fleet.step()
                b = clock()
            with TraceAnnotation("bench.bookkeeping"):
                got, still = [], []
                for i in pending:
                    s = sent[i]
                    n = len(s.req.out_tokens)
                    if n > len(s.token_times):
                        s.token_times.extend([b] * (n - len(s.token_times)))
                        got.append((i, n - 1))
                    if not s.req.done.is_set():
                        still.append(i)
                pending = still
                occ = monitor.page_occupancy()
                rec.steps.append(Step(a, b, decoded, got,
                                      sum(occ.values()) / max(1, len(occ))))
        else:
            with TraceAnnotation("bench.wait"):
                wake = sent[nxt].due if nxt < len(sent) else end
                time.sleep(max(0.0, min(wake, end) - clock()))
    return rec

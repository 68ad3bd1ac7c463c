"""Finds a cell's knee once, on the chip: the highest arrival rate at
which the backlog does not grow over the window.

  python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
      --rates 1.0,1.5,2.0

One process stands the cell up once and serves the cell's mix at each
rate in turn, draining the fleet between rates. For each rate it prints
one JSON line: tokens/s per chip, TTFT median and 95th percentile, the
requests unfinished at the middle and at the close of the window, and
whether the backlog held. The last line names the knee: the highest rate
below which every rate swept held.

A rate holds when the requests unfinished at the close are at most
``GROWTH`` times those at mid-window plus ``SLACK``: a queue that only
fluctuates passes, one that grows all through the window does not.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Optional

import run
import spec


def backlog(rec, t: float) -> int:
    """Requests submitted by ``t`` and not finished at ``t``."""
    n = 0
    for s in rec.sent:
        if s.submitted is None or s.submitted > t or s.failed:
            continue
        done = s.req.done.is_set() and s.token_times \
            and s.token_times[-1] <= t
        n += not done
    return n


GROWTH = 1.25
SLACK = 2


def holds(mid: int, end: int) -> bool:
    return end <= GROWTH * mid + SLACK


def knee(rows) -> Optional[float]:
    """The highest rate of ``rows`` (dicts with ``rate_rps`` and ``held``)
    with every rate up to it held; None if the lowest did not hold."""
    best = None
    for r in sorted(rows, key=lambda r: r["rate_rps"]):
        if not r["held"]:
            break
        best = r["rate_rps"]
    return best


def main(argv=None) -> int:
    import loop
    import traffic
    from measure import percentile
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    devices = run.chips(spec.cell(bench, args.workload)["chips"])
    run.use_compile_cache()
    st = run.Stand(bench, args.workload, args.seed, devices)
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(st.mix, rate_rps=rate)
        arrivals = traffic.schedule(mix, args.seed, args.seconds,
                                    len(st.tenants), st.dims.vocab)
        rec = loop.run(st.fleet, st.tenants, arrivals, args.seconds)
        toks = sum(len(s.token_times) for s in rec.sent)
        ttft = [(s.token_times[0] if s.token_times else rec.t1) - s.due
                for s in rec.sent]
        mid, end = backlog(rec, rec.t0 + args.seconds / 2), \
            backlog(rec, rec.t1)
        rows.append({
            "rate_rps": rate,
            "tok_s_per_chip": toks / args.seconds / st.cell["chips"],
            "ttft_p50_s": percentile(ttft, 50),
            "ttft_p95_s": percentile(ttft, 95),
            "backlog_mid": mid, "backlog_end": end, "held": holds(mid, end),
            "failed": sum(bool(s.failed) for s in rec.sent)})
        print(json.dumps(rows[-1]), flush=True)
        st.fleet.run_until_idle(max_steps=100000)
    st.close()
    print(json.dumps({"knee_rps": knee(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

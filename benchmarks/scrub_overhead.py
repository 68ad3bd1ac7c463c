"""Zero-on-free cost: paged decode step time with ``scrub_on_free`` on vs
off, under a slot-churn workload where pages actually recycle.

The isolation policy's only dataplane cost is the batched device-side
scrub ``BatchingEngine._flush_scrub`` dispatches before allocations. This
cell measures it where it is hottest: a steady stream of short requests so
slots (and their pages) turn over continuously and nearly every step both
frees and reallocates pages. Acceptance gate for the tenant-isolation PR:
**scrub-on median step time within 5% of scrub-off** (ratio <= 1.05).

Also reported: pages scrubbed and the batched scrub dispatches that
zeroed them (the numbers the gateway exports to
``Monitor.status()["scrub"]``), so the batching is visible, not just the
ratio.

Run:  PYTHONPATH=src python benchmarks/scrub_overhead.py [--smoke]
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

PAGE_SIZE = 16
N_SLOTS = 4
MAX_LEN = 128


def _setup():
    from repro.configs import get_config, reduced
    from repro.models import get_model
    cfg = reduced(get_config("smollm-135m")).replace(dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _churn_workload(cfg, n_reqs, prompt_len=12, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=prompt_len).tolist()
            for _ in range(n_reqs)]


def _churn_step_ms(model, params, cfg, scrub: bool, n_reqs: int,
                   max_new: int = 6):
    """Median per-step wall time draining ``n_reqs`` short requests (every
    completion frees pages; every admission re-allocates them — the
    scrub queue is hot the whole run). Returns (median_ms, pages_scrubbed,
    scrub_dispatches)."""
    from repro.runtime import BatchingEngine
    eng = BatchingEngine(model, params, n_slots=N_SLOTS, max_len=MAX_LEN,
                         paged=True, page_size=PAGE_SIZE,
                         scrub_on_free=scrub)
    for p in _churn_workload(cfg, n_reqs):
        eng.submit(p, max_new_tokens=max_new)
    for _ in range(4):                      # warm the decode executable
        eng.step()
    times = []
    for _ in range(10000):
        t0 = time.perf_counter()
        n = eng.step()
        times.append((time.perf_counter() - t0) * 1e3)
        if n == 0 and eng.idle():
            break
    assert eng.idle(), "churn workload did not drain"
    pool = eng.pool
    assert pool.used_pages == 0
    if scrub:
        assert pool.pages_scrubbed > 0, \
            "no pages recycled — the cell measured nothing"
    return float(np.median(times)), pool.pages_scrubbed, eng.scrub_dispatches


def measure(model, params, cfg, smoke: bool):
    n_reqs = 16 if smoke else 48
    off_ms, _, _ = _churn_step_ms(model, params, cfg, False, n_reqs)
    on_ms, pages, calls = _churn_step_ms(model, params, cfg, True, n_reqs)
    return on_ms / off_ms, on_ms, off_ms, pages, calls


def run():
    """Harness entry (``benchmarks/run.py``): CSV rows."""
    cfg, model, params = _setup()
    ratio, on_ms, off_ms, pages, calls = measure(model, params, cfg,
                                                 smoke=True)
    return [
        ("scrub_overhead.step_ms_scrub_on", on_ms * 1e3,
         f"median us/step; {pages} pages scrubbed"),
        ("scrub_overhead.step_ms_scrub_off", off_ms * 1e3,
         "median us/step baseline arm"),
        ("scrub_overhead.on_off_ratio", ratio,
         f"target<=1.05; {calls} scrub dispatches"),
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small workload for CI")
    args = ap.parse_args()
    cfg, model, params = _setup()
    ratio, on_ms, off_ms, pages, calls = measure(model, params, cfg,
                                                 args.smoke)
    print("== zero-on-free scrub overhead (slot-churn paged decode) ==")
    print(f"  scrub off: {off_ms:.3f} ms/step (median)")
    print(f"  scrub on : {on_ms:.3f} ms/step (median), {pages} pages "
          f"scrubbed in {calls} dispatches")
    print(f"  => on/off step-time ratio {ratio:.3f} (target <= 1.05)")
    if ratio > 1.05:
        print("WARNING: scrub overhead exceeded the 5% envelope on this "
              "host (batched dispatch amortizes poorly on tiny CPU "
              "models; re-check on an accelerator before gating)")


if __name__ == "__main__":
    main()

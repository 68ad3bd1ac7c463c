"""Warms up every program shape a cell's traffic can reach, so that
nothing compiles inside the measured window.

The paged engine compiles, per device: the decode step (bound at fleet
start), one prefill per power-of-two bucket of the context length, one
page splice per count of pages written at admission, one zero-on-free
scrub per count of pages freed before a step, and the one-page
invalidation a decode step that grows into a new page runs. The ranges
follow from the mix's length bounds and the deployment: a scrub can free
every page all slots hold at once, and never more than the pool holds
beside its null page.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import numpy as np


def prefill_bucket(n: int, max_len: int) -> int:
    """The padded length the engine prefills a context of ``n`` tokens
    at (``BatchingEngine._pad_ctx`` on a paged engine)."""
    bucket = 8
    while bucket < n:
        bucket *= 2
    return max(n, min(bucket, max_len))


def shapes(mix: dict, dep: dict) -> Dict[str, List[int]]:
    ps, max_len = dep["page_size"], dep["max_len"]
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    out_hi = mix["output_tokens"]["max"]
    pages = lambda toks: (toks - 1) // ps + 1         # noqa: E731
    # page 0 is the null page: a splice or a scrub touches the others
    usable = (dep["cache_pages"] - 1 if dep.get("cache_pages")
              else dep["n_slots"] * pages(max_len))
    starts = [0]
    if mix.get("prefix_tokens"):
        starts.append(mix["prefix_tokens"] // ps)
    return {
        "prefill": sorted({prefill_bucket(n - 1, max_len)
                           for n in range(lo, hi + 1)}),
        "splice": sorted({(s, nb - s) for s in starts
                          for nb in range(pages(lo),
                                          min(pages(hi), usable) + 1)
                          if nb > s}),
        "scrub": list(range(1, min(dep["n_slots"] * pages(hi + out_hi),
                                   usable) + 1)),
    }


def run(fleet, tenants: List[str], mix: dict, dep: dict, log) -> dict:
    """Warm every engine; returns the counts and seconds per kind."""
    import jax
    from repro.runtime import serve
    need = shapes(mix, dep)
    by_dev = {fleet.device_of(t): t for t in tenants}
    took: Dict[str, float] = {}

    def timed(kind, fn):
        t0 = time.monotonic()
        fn()
        took[kind] = took.get(kind, 0.0) + time.monotonic() - t0

    # decode, argmax, one-page invalidation and a small scrub, through the
    # public path: a 32-token prompt whose second decode grows a page
    def serve_one():
        for t in by_dev.values():
            fleet.submit(t, np.arange(32, dtype=np.int32) + 1,
                         max_new_tokens=3)
        if not fleet.run_until_idle():
            raise RuntimeError("warm-up requests did not finish")
    timed("serve", serve_one)

    def warm_device(tenant):
        eng = fleet.engine_for(tenant)
        zero = lambda n: eng._put(np.zeros((n,), np.int32))   # noqa: E731
        buf = None
        for b in need["prefill"]:
            _, buf = eng._prefill(eng.params, eng._put(
                np.ones((1, b), np.int32)))
        # every splice writes into the null page 0; the scrubs below
        # restore it to its initial state
        for start, nb in need["splice"]:
            eng.caches = serve._splice_pages(eng.caches, buf, zero(nb),
                                             start=start)
        if mix.get("prefix_tokens"):
            eng.caches = serve._copy_page(eng.caches, np.int32(0),
                                          np.int32(0))
        del buf
        for n in need["scrub"]:
            eng.caches = serve._scrub_pool_pages(eng.caches, zero(n))
        jax.block_until_ready(eng.caches)

    # one thread per device: each compiles and runs its own engine's
    # programs, so the compiles of different chips overlap
    def warm_all():
        with ThreadPoolExecutor(len(by_dev)) as pool:
            list(pool.map(warm_device, by_dev.values()))
    timed("prefill+splice+scrub", warm_all)
    counts = {k: len(v) for k, v in need.items()}
    log(f"warm-up per device: {counts}; seconds {took}")
    return {"counts": counts, "seconds": took}

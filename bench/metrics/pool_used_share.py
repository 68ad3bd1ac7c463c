"""KV pool pages in use over the pool's pages, mean over the devices and
over fleet rounds, in percent (the pool's own page counts)."""


def read(run):
    steps = run.rec.steps
    if not steps:
        return None
    return 100.0 * sum(s.pool_share for s in steps) / len(steps)

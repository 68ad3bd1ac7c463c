"""Median time a request of the window waited in its tenant's queue: from
its submission to its first admission into an engine slot
(``Request.submitted_at`` to ``Request.admitted_at``). A request still
queued when the window closed counts its wait up to the close, as TTFT
counts a request with no first token, so a growing queue reads higher.
A program whose requests carry no ``admitted_at`` reads nothing."""
from measure import percentile


def read(run):
    reqs = [s for s in run.rec.sent if s.req is not None]
    if not all(hasattr(s.req, "admitted_at") for s in reqs):
        return None
    waits = [run.rec.t1 - s.submitted if s.req.admitted_at is None
             else s.req.admitted_at - s.req.submitted_at for s in reqs]
    t = percentile(waits, 50)
    return None if t is None else t * 1e3

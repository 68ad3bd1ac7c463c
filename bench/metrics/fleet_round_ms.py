"""Median host time of one ``GatewayFleet.step()`` round."""
from measure import percentile


def read(run):
    return percentile([(s.end - s.start) * 1e3 for s in run.rec.steps], 50)

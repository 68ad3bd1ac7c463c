"""Median gap between consecutive output tokens, over every request."""
from measure import percentile


def read(run):
    return percentile(run.gaps_ms(), 50)

"""99th percentile of the gaps between consecutive output tokens."""
from measure import percentile


def read(run):
    return percentile(run.gaps_ms(), 99)

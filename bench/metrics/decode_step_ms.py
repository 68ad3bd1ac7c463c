"""Median device time of one execution of the bound decode program."""
from measure import percentile
from trace_reduce import program_times


def read(run):
    if run.trace is None:
        return None
    t = percentile(program_times(run.trace, run.modules["decode"]), 50)
    return None if t is None else t * 1e3

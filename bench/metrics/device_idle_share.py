"""Share of the traced window in which no operation ran on the device,
mean over the cell's chips, in percent."""
from trace_reduce import idle_share


def read(run):
    return None if run.trace is None else 100.0 * idle_share(run.trace)

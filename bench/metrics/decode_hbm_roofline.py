"""The bytes the traced decode steps need (the architecture module's
``decode_work`` of each execution: for a dense model the weights once,
each slot's live keys and values and one position written per slot) over
what the chip's HBM bandwidth moves in their device time, in percent.
The bound is bandwidth: a decode step of a few slots does far fewer
operations per byte than the chip's balance point."""
from trace_reduce import program_times


def read(run):
    if run.trace is None:
        return None
    times = program_times(run.trace, run.modules["decode"])
    execs, nbytes, _ = run.decode_work(run.traced_steps())
    if not times or not execs:
        return None
    return 100.0 * nbytes / (sum(times) * run.peak["hbm_bytes_per_s"])

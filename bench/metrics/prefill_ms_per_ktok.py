"""Device time of the prefill programs per 1000 real context tokens they
prefilled: padding to the power-of-two bucket shows as a higher number."""
from trace_reduce import program_times


def read(run):
    if run.trace is None:
        return None
    toks, _ = run.prefill_work(run.traced_steps())
    t = sum(program_times(run.trace, run.modules["prefill"]))
    return t * 1e3 / (toks / 1e3) if toks and t else None

"""The operations the traced prefills need (the architecture module's
``prefill_flops`` at each request's real context: the matmuls, causal
attention and, for a hybrid, the SSD scan term by term) over what the
chip does at its bf16 peak in the prefill programs' device time, in
percent."""
from trace_reduce import program_times


def read(run):
    if run.trace is None:
        return None
    _, flops = run.prefill_work(run.traced_steps())
    t = sum(program_times(run.trace, run.modules["prefill"]))
    if not flops or not t:
        return None
    return 100.0 * flops / (t * run.peak["bf16_flops_per_s"])

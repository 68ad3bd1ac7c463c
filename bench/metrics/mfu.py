"""The operations the traced window's prefills and decode steps need
(the architecture module's ``decode_work`` and ``prefill_flops``: for a
dense model 2 per weight per token, plus causal attention), over what the
cell's chips could do at their bf16 peak in the traced window, in
percent."""


def read(run):
    if run.trace is None:
        return None
    steps = run.traced_steps()
    _, _, decode = run.decode_work(steps)
    _, prefill = run.prefill_work(steps)
    t0, t1 = run.trace.window
    peak = (t1 - t0) * run.chips * run.peak["bf16_flops_per_s"]
    return 100.0 * (decode + prefill) / peak if decode + prefill else None

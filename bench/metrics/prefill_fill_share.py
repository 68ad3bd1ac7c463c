"""Real context tokens over the padded tokens the traced prefills ran
(the ``tokens`` and ``padded`` of the program's ``rc3e.engine.prefill``
spans), in percent: the share of prefill work that is not padding."""
import program_spans


def read(run):
    return program_spans.prefill_fill_share(run)

"""Seconds from process start to the first due request: imports,
weights, fleet, program load or compile, warm-up."""


def read(run):
    return run.setup_s

"""Median host time of one engine step outside waiting for its chip: the
program's ``rc3e.fleet.engine_step`` span less its ``rc3e.engine.readback``
child, over the traced engine steps that decoded."""
import program_spans


def read(run):
    return program_spans.engine_host_ms(run)

"""Share of the traced window in which a chip runs no operation while its
own engine's ``rc3e.fleet.engine_step`` span is open, mean over the
cell's chips, in percent."""
import program_spans


def read(run):
    split = program_spans.idle_split(run)
    return None if split is None else split["own"]

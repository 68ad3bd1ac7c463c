"""Share of the traced window in which a chip runs no operation while the
fleet is inside another chip's ``rc3e.fleet.engine_step`` span, mean
over the cell's chips, in percent."""
import program_spans


def read(run):
    split = program_spans.idle_split(run)
    return None if split is None else split["other"]

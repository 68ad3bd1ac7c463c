"""Share of the slots' recurrent-state rows that a paged decode step
reads and writes, in percent: the mean, over the traced decode steps, of
``state_rows`` over ``state_rows_held`` (the rows of recurrent state the
step sweeps, and the engine's slots), which the program stamps on its
``rc3e.engine.decode_dispatch`` spans. It reads 100 while every step
sweeps every slot's state, live or not. A program that stamps neither
reads nothing."""
import program_spans

DISPATCH = "rc3e.engine.decode_dispatch"


def read(run):
    spans = program_spans.in_window(run)
    if spans is None:
        return None
    shares = [s.attrs["state_rows"] / s.attrs["state_rows_held"]
              for s in spans
              if s.name == DISPATCH and s.attrs.get("state_rows_held")]
    return 100.0 * sum(shares) / len(shares) if shares else None

"""Share of the block table that the paged decode's attention sweeps, in
percent: the mean, over the traced decode steps, of ``table_cols_swept``
over ``table_cols`` (block-table columns covered and the table's columns,
summed over the paged attention layers), which the program stamps on its
``rc3e.engine.decode_dispatch`` spans. A program that stamps neither reads
nothing."""
import program_spans

DISPATCH = "rc3e.engine.decode_dispatch"


def read(run):
    spans = program_spans.in_window(run)
    if spans is None:
        return None
    shares = [s.attrs["table_cols_swept"] / s.attrs["table_cols"]
              for s in spans
              if s.name == DISPATCH and s.attrs.get("table_cols")]
    return 100.0 * sum(shares) / len(shares) if shares else None

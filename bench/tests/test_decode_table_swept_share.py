"""``decode_table_swept_share`` on a hand-made list of program spans."""
import pytest

import program_spans
import spec
from loop import Record, Step
from measure import Run
from repro.core.spans import Span

DISPATCH = "rc3e.engine.decode_dispatch"


def _run():
    """A traced window from 10 s to 20 s on the program's clock."""
    rec = Record(9.0, 20.0, [], [Step(10.0, 19.0, 4, [], 0.5)],
                 trace_t0=10.0)
    return Run(rec=rec, arch=None, dims=None, deployment={}, chips=1,
               peak={}, setup_s=1.0, memory_peak=[], device_of={},
               modules={})


def _read(monkeypatch, spans):
    monkeypatch.setattr(program_spans, "recorded", lambda: spans)
    return spec.reader("decode_table_swept_share").read(_run())


def test_mean_share_of_the_traced_decode_steps(monkeypatch):
    def dispatch(t0, **attrs):
        return Span(DISPATCH, t0, t0 + 0.01, None, attrs)

    spans = [
        dispatch(5.0, table_cols_swept=8, table_cols=8),     # before the window
        dispatch(11.0, table_cols_swept=2, table_cols=8),
        Span("rc3e.engine.readback", 11.01, 11.02, None, {}),
        dispatch(12.0, table_cols_swept=4, table_cols=8),
        dispatch(13.0, table_cols_swept=8, table_cols=8),
    ]
    assert _read(monkeypatch, spans) == pytest.approx(
        100.0 * (2 / 8 + 4 / 8 + 8 / 8) / 3)


def test_reads_nothing_where_the_program_stamps_nothing(monkeypatch):
    assert _read(monkeypatch, [Span(DISPATCH, 11.0, 11.01, None, {})]) \
        is None
    assert _read(monkeypatch, []) is None
    assert _read(monkeypatch, None) is None

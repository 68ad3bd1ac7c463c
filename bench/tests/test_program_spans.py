"""The program's spans mapped onto a hand-made two-chip trace: the clock
offset, the refusals, the idle split and the readers that use them."""
import numpy as np
import pytest

import program_spans as ps
import spec
import trace_reduce as tr
from loop import Record, Sent, Step
from measure import Run
from repro.core.spans import Span
from traffic import Arrival

SHIFT = 50.0            # program clock = trace clock + SHIFT


def _trace(fleet_steps=((0.0, 4.5), (5.0, 9.5))):
    dev0 = tr.Device(np.array([[1.0, 2.0], [6.0, 7.0]]), ["a", "b"], [])
    dev1 = tr.Device(np.array([[3.0, 4.0], [8.0, 9.0]]), ["a", "b"], [])
    spans = [("bench.fleet_step", s, e) for s, e in fleet_steps]
    spans.append(("bench.bookkeeping", 9.5, 10.0))
    return tr.Trace({"/device:TPU:0": dev0, "/device:TPU:1": dev1}, spans)


def _spans(jitter=0.0):
    """Two rounds, each an engine step on chip 0 then one on chip 1; the
    first three steps decoded (a readback), one of them a prefill too. A
    round of an earlier window comes first."""
    out = [Span("rc3e.fleet.round", 10.0, 11.0, None, {})]

    def add(name, t0, t1, parent, **attrs):
        out.append(Span(name, t0 + SHIFT, t1 + SHIFT, parent, attrs))
        return len(out) - 1

    for k, (r0, r1) in enumerate(((0.0, 4.4), (5.0, 9.4))):
        rnd = add("rc3e.fleet.round", r0 + (jitter if k else 0.0), r1, None)
        a = add("rc3e.fleet.engine_step", r0 + 0.5, r0 + 2.0, rnd,
                device="d0", chip=0)
        if k == 0:
            add("rc3e.engine.prefill", r0 + 0.6, r0 + 0.7, a, tokens=300,
                padded=512)
            add("rc3e.engine.readback", r0 + 1.0, r0 + 1.9, a)
        else:
            add("rc3e.engine.readback", r0 + 0.6, r0 + 1.3, a)
        b = add("rc3e.fleet.engine_step", r0 + 2.0, r0 + 4.0, rnd,
                device="d1", chip=1)
        if k == 0:
            add("rc3e.engine.prefill", r0 + 2.1, r0 + 2.2, b, tokens=100,
                padded=128)
            add("rc3e.engine.readback", r0 + 2.5, r0 + 3.5, b)
    return out


def _run(trace, admitted=(0.25, 1.25), queued=0):
    dense = spec.arch({"arch": "dense"})
    m = dense.Dims("x", n_layers=2, d_model=8, n_heads=2, n_kv_heads=1,
                   head_dim=4, d_ff=16, vocab=32, tied=False, norm_eps=1e-5,
                   rope_theta=1e4, max_position=64)
    sent = []
    for i, wait in enumerate(admitted):
        req = type("Req", (), {})()
        req.submitted_at, req.admitted_at = 10.0 * i, 10.0 * i + wait
        sent.append(Sent(Arrival(0.0, 0, np.zeros(4, np.int32), 2), 0.0,
                         "t0", req=req))
    for i in range(queued):         # still queued when the window closed
        req = type("Req", (), {})()
        req.submitted_at, req.admitted_at = 0.0, None
        sent.append(Sent(Arrival(0.0, 0, np.zeros(4, np.int32), 2), 0.0,
                         "t0", submitted=SHIFT + 9.6 - 3.0 + i, req=req))
    rec = Record(SHIFT - 1.0, SHIFT + 9.6, sent,
                 [Step(SHIFT, SHIFT + 4.5, 2, [], 0.5)],
                 trace_t0=SHIFT - 0.1)
    return Run(rec=rec, arch=dense, dims=m,
               deployment={"n_slots": 4, "devices": 2},
               chips=2, peak={}, setup_s=1.0, memory_peak=[],
               device_of={}, modules={}, trace=trace)


@pytest.fixture
def spans(monkeypatch):
    def use(s):
        monkeypatch.setattr(ps, "recorded", lambda: s)
    use(_spans())
    return use


def test_offset_maps_rounds_onto_fleet_steps(spans):
    run = _run(_trace())
    window = ps.in_window(run)
    assert len(window) == len(_spans()) - 1          # the old round is out
    assert window[1].parent == 0 and window[0].parent is None
    mapped = ps.on_trace(run)
    assert [s.name for s in mapped] == [s.name for s in window]
    assert mapped[0].t0 == pytest.approx(0.0)
    assert ps.offset([s for s in window if s.name == ps.ROUND],
                     [s for s in run.trace.spans
                      if s[0] == ps.FLEET_STEP]) == pytest.approx(-SHIFT)


def test_no_mapping_without_a_one_to_one_pairing(spans):
    one_step = _run(_trace(fleet_steps=((0.0, 4.5),)))
    assert ps.on_trace(one_step) is None
    assert ps.idle_split(one_step) is None
    spans(_spans(jitter=1e-3))       # one round starts 1 ms late
    assert ps.on_trace(_run(_trace())) is None
    spans(None)                       # a program that records no spans
    run = _run(_trace())
    for name in ("engine_host_ms", "prefill_fill_share",
                 "idle_in_own_step_share", "idle_in_other_steps_share"):
        assert _read(name, run) is None
    spans([])
    assert ps.on_trace(run) is None


def test_idle_split_of_two_chips(spans):
    run = _run(_trace())
    # chip 0 idles 0-1, 2-6, 7-10; chip 1 idles 0-3, 4-8, 9-10. Steps:
    # chip 0 at 0.5-2 and 5.5-7, chip 1 at 2-4 and 7-9; rounds 0-4.4 and
    # 5-9.4. Idle in own steps: chip 0 1.0 s, chip 1 2.0 s; in the other
    # chip's steps: 4.0 s and 3.0 s; in a round outside the steps: 1.8 s
    # each; outside every round: 1.2 s each. The window is 10 s.
    split = ps.idle_split(run)
    assert split == pytest.approx({"own": 15.0, "other": 35.0,
                                   "round": 18.0})
    assert _read("idle_in_own_step_share", run) == pytest.approx(15.0)
    assert _read("idle_in_other_steps_share", run) == pytest.approx(35.0)
    phases = ps.idle_phases(run)
    assert sum(phases.values()) == pytest.approx(
        10.0 - tr.busy_s(run.trace))
    assert phases[ps.OUTSIDE] == pytest.approx(1.2)
    assert phases["rc3e.fleet.round"] == pytest.approx(1.8)
    assert phases[ps.OTHER_CHIPS] == pytest.approx(3.5)
    # readbacks cover 0.4 s (chip 0, 5.6-6) and 0.5 s (chip 1, 2.5-3) of
    # idle time, prefills 0.1 s each; the steps keep the rest of theirs
    assert phases["rc3e.engine.readback"] == pytest.approx((0.4 + 0.5) / 2)
    assert phases["rc3e.engine.prefill"] == pytest.approx(0.1)
    assert phases["rc3e.fleet.engine_step"] == pytest.approx(
        (1.0 - 0.4 - 0.1 + 2.0 - 0.5 - 0.1) / 2)


def test_span_readers(spans):
    run = _run(_trace())
    # host time of the steps that decoded: 1.5-0.9, 2.0-1.0, 1.5-0.7 s
    assert _read("engine_host_ms", run) == pytest.approx(800.0)
    assert _read("prefill_fill_share", run) == pytest.approx(
        100.0 * 400 / 640)
    assert _read("queue_wait_p50_ms", run) == pytest.approx(750.0)
    assert _read("queue_wait_p50_ms", _run(None, admitted=())) is None
    # never admitted: waited from submission to the window's close (3 s,
    # 2 s), so the median of 0.25, 1.25, 2, 3 s rises to 1.625 s
    assert _read("queue_wait_p50_ms", _run(None, queued=2)) == \
        pytest.approx(1625.0)
    # a program that stamps no admission reads nothing
    bare = _run(None)
    for s in bare.rec.sent:
        del s.req.admitted_at
    assert _read("queue_wait_p50_ms", bare) is None


def _read(name, run):
    return spec.reader(name).read(run)

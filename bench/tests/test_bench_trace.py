"""The trace reduction and the arithmetic the metrics share, on a
hand-made trace."""

import numpy as np
import pytest

import spec
import trace_reduce as tr
from loop import Record, Sent, Step
from measure import Run
from traffic import Arrival


def _trace():
    ops = np.array([[1.0, 2.0], [1.5, 3.0], [5.0, 6.0], [9.0, 12.0]])
    dev = tr.Device(ops, ["fusion.1", "fusion.2", "dot.3", "fusion.1"],
                    [("jit_decode", 1.0, 3.0), ("jit_prefill", 5.0, 6.0),
                     ("jit_decode", 9.0, 12.0)])
    spans = [("bench.fleet_step", 0.0, 3.5), ("bench.wait", 3.5, 8.0),
             ("bench.fleet_step", 8.0, 10.0)]
    return tr.Trace({"/device:TPU:0": dev}, spans)


def test_union_idle_and_programs():
    t = _trace()
    assert t.window == (0.0, 10.0)
    busy = tr.busy_intervals(t.devices["/device:TPU:0"].ops, *t.window)
    np.testing.assert_allclose(busy, [[1, 3], [5, 6], [9, 10]])
    assert tr.busy_s(t) == pytest.approx(4.0)
    assert tr.idle_share(t) == pytest.approx(0.6)
    # an execution that runs past the window is not counted
    assert tr.program_times(t, "jit_decode") == [2.0]
    assert tr.program_times(t, "jit_prefill") == [1.0]
    ops = dict(map(tuple, tr.top_ops(t)))
    assert ops == pytest.approx({"jit_decode fusion.1": 2.0,
                                 "jit_decode fusion.2": 1.5,
                                 "jit_prefill dot.3": 1.0})
    assert tr._op_label("%fusion.7 = f32[4]{0} fusion(f32[4]{0} %p), "
                        "kind=kLoop, calls=%f") == "fusion.7 fusion kLoop"
    gaps = dict(map(tuple, tr.idle_gaps(t)))
    # idle 0-1 and 3-3.5 in the first step, 3.5-5 and 6-8 waiting,
    # 8-9 in the second step
    assert gaps == pytest.approx({"bench.fleet_step": 2.5,
                                  "bench.wait": 3.5})


def _run(trace=None):
    dense = spec.arch({"arch": "dense"})
    m = dense.Dims("x", n_layers=2, d_model=8, n_heads=2, n_kv_heads=1,
                   head_dim=4, d_ff=16, vocab=32, tied=False, norm_eps=1e-5,
                   rope_theta=1e4, max_position=64)
    a = Sent(Arrival(0.0, 0, np.zeros(10, np.int32), 3), 0.0, "t0",
             submitted=0.0, token_times=[1.0, 2.0, 3.0])
    b = Sent(Arrival(0.5, 1, np.zeros(6, np.int32), 2), 0.5, "t1",
             submitted=0.6, token_times=[2.0, 3.0])
    steps = [Step(0.5, 1.0, 1, [(0, 0)], 0.5),
             Step(1.5, 2.0, 2, [(0, 1), (1, 0)], 0.5),
             Step(2.5, 3.0, 2, [(0, 2), (1, 1)], 1.0)]
    rec = Record(0.0, 4.0, [a, b], steps, trace_t0=1.2)
    return Run(rec=rec, arch=dense, dims=m,
               deployment={"n_slots": 4, "devices": 1},
               chips=1, peak={"bf16_flops_per_s": 1e12,
                              "hbm_bytes_per_s": 1e9, "hbm_bytes": 1e10},
               setup_s=1.0, memory_peak=[5e9],
               device_of={"t0": "d0", "t1": "d0"},
               modules={"decode": "jit_decode", "prefill": "jit_prefill"},
               trace=trace)


def test_work_arithmetic():
    run = _run()
    m = run.dims
    steps = run.traced_steps()
    assert len(steps) == 2                  # the steps after trace start
    execs, nbytes, flops = run.decode_work(steps)
    assert execs == 2
    # contexts attended: a at j=1,2 -> 11, 12; b at j=0,1 -> 6, 7
    kv = (11 + 12 + 6 + 7) + 4
    assert nbytes == 2 * m.weight_bytes_per_step + kv * \
        m.kv_bytes_per_position
    per_tok = 2 * (2 * m.layer_matmul_params + m.head_params)
    assert flops == pytest.approx(4 * per_tok + 4 * 2 * 2 * 4 * 36)
    toks, pf = run.prefill_work(steps)
    assert toks == 5                         # b's prompt but its last token
    assert pf == pytest.approx(2 * 2 * m.layer_matmul_params * 5
                               + 2 * 2 * 2 * 4 * 5 * 6)
    # layer matmuls: q and o 8*4*2 each, k and v 8*4 each, mlp 3*8*16
    assert m.layer_matmul_params == 64 + 64 + 32 + 32 + 384
    assert m.kv_bytes_per_position == 2 * (2 * 2 * 1 * 4 + 4)


def test_metric_readers():
    run = _run(_trace())
    read = lambda n: spec.reader(n).read(run)       # noqa: E731
    assert read("tok_s_per_chip") == pytest.approx(5 / 4)
    assert read("itl_p50_ms") == pytest.approx(1000.0)
    assert read("batch_occupancy") == pytest.approx(100 * 5 / 3 / 4)
    assert read("hbm_peak_share") == pytest.approx(50.0)
    assert read("device_idle_share") == pytest.approx(60.0)
    assert read("decode_step_ms") == pytest.approx(2000.0)
    assert read("prefill_ms_per_ktok") == pytest.approx(1e3 / (5 / 1e3))
    _, nbytes, dflops = run.decode_work(run.traced_steps())
    assert read("decode_hbm_roofline") == pytest.approx(
        100 * nbytes / (2.0 * 1e9))
    _, pflops = run.prefill_work(run.traced_steps())
    assert read("mfu") == pytest.approx(100 * (dflops + pflops) / (10 * 1e12))
    silent = _run(None)
    for name in ("decode_step_ms", "mfu", "device_idle_share",
                 "decode_hbm_roofline", "prefill_ms_per_ktok"):
        assert spec.reader(name).read(silent) is None


def test_recorded_chip_trace(tmp_path):
    """A 0.3 s trace of smollm-135m served on one TPU v5 lite reduces to
    the numbers that run reported, and the roofline and mfu arithmetic
    holds on its real program times and window."""
    import gzip
    import json
    import pathlib
    fx = pathlib.Path(__file__).resolve().parent / "fixtures"
    want = json.loads((fx / "smollm-decode.json").read_text())
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(gzip.decompress(
        (fx / "smollm-decode.xplane.pb.gz").read_bytes()))
    t = tr.load(str(path))
    assert list(t.devices) == ["/device:TPU:0"]
    t0, t1 = t.window
    assert t1 - t0 == pytest.approx(want["device"]["window_s"], rel=1e-9)
    assert tr.busy_s(t) == pytest.approx(want["device"]["busy_s"], rel=1e-9)
    assert 100 * tr.idle_share(t) == pytest.approx(
        want["metrics"]["device_idle_share"], rel=1e-9)
    times = tr.program_times(t, want["modules"]["decode"])
    assert 1e3 * np.median(times) == pytest.approx(
        want["metrics"]["decode_step_ms"], rel=1e-9)
    # the device's ops lie inside its programs and the host's spans
    d = t.devices["/device:TPU:0"]
    assert d.ops[:, 0].min() >= t0 - 0.01 and d.ops[:, 1].max() <= t1 + 0.01
    ops = tr.top_ops(t)
    assert len(ops) == 10 and all(k.startswith("jit_") for k, _ in ops)
    assert not any(" while" in k for k, _ in ops)
    assert sum(v for _, v in ops) <= tr.busy_s(t) * 1.01
    gaps = dict(map(tuple, tr.idle_gaps(t)))
    assert sum(gaps.values()) == pytest.approx(t1 - t0 - tr.busy_s(t))

    run = _run(t)
    run.modules = dict(want["modules"])
    run.rec.trace_t0 = 0.0
    _, nbytes, dflops = run.decode_work(run.traced_steps())
    assert spec_read("decode_hbm_roofline", run) == pytest.approx(
        100 * nbytes / (sum(times) * 1e9))
    _, pflops = run.prefill_work(run.traced_steps())
    assert spec_read("mfu", run) == pytest.approx(
        100 * (dflops + pflops) / ((t1 - t0) * 1e12))


def spec_read(name, run):
    return spec.reader(name).read(run)

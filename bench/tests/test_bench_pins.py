"""What the benchmark reads does not move when its code moves: the sizes,
the program's configuration, the weight layout, the weights drawn, the
reference's gaps and the work counts of each configuration are pinned
to the values the harness gave before the architecture became a module
(``fixtures/pins/``), and compared exactly."""
import dataclasses
import hashlib
import json
import pathlib

import jax
import numpy as np
import pytest

import reference
import spec
import weights
from loop import Record, Sent, Step
from measure import Run
from traffic import Arrival

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
PINS = FIXTURES / "pins"
ROOT = pathlib.Path(__file__).resolve().parents[2]
CONFIGS = ["phi3-mini-4k", "smollm-135m-x4"]
TINY = ["tiny", "tiny-window"]


def _pins(name):
    return json.loads((PINS / f"{name}.json").read_text())


def _model(path):
    conf = json.loads(path.read_text())
    arch = spec.arch(conf)
    return arch, arch.sizes(path.stem, conf["model"])


def _config(name):
    return _model(ROOT / "bench" / "configs" / f"{name}.json")


def _tiny(name):
    return _model(FIXTURES / "tiny" / "bench" / "configs" / f"{name}.json")


@pytest.mark.parametrize("name", CONFIGS)
def test_layout_is_pinned(name):
    arch, m = _config(name)
    assert {p: {"shape": list(s), "scale": sc, "stacked": st}
            for p, (s, sc, st) in arch.layout(m).items()} == \
        _pins("layouts")[name]


@pytest.mark.parametrize("name", CONFIGS)
def test_sizes_and_program_are_pinned(name):
    arch, m = _config(name)
    want = _pins("programs")[name]
    assert dataclasses.asdict(m) == want["sizes"]
    assert json.loads(json.dumps(dataclasses.asdict(arch.program(m)))) == \
        want["program"]


@pytest.mark.parametrize("name", TINY)
def test_drawn_weights_are_pinned(name):
    arch, m = _tiny(name)
    params = weights.make_params(arch.layout(m), 2**31 + 99, jax.devices()[0])
    got = {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path):
           [str(leaf.dtype), hashlib.blake2b(np.asarray(leaf).tobytes(),
                                             digest_size=8).hexdigest()]
           for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert got == _pins("params")[name]


@pytest.mark.parametrize("name", TINY)
def test_served_gaps_are_pinned(name):
    arch, m = _tiny(name)
    want = _pins("served_gaps")[name]
    seqs = [(np.asarray(p, np.int32), o) for p, o in want["seqs"]]
    gaps, ctl = reference.served_gaps(arch, m, want["seed"], seqs,
                                      control=True)
    for g, w in zip(gaps, want["gaps"]):
        assert g.dtype == np.float32
        np.testing.assert_array_equal(g, np.asarray(w, np.float32))
    for g, w in zip(ctl, want["control_gaps"]):
        np.testing.assert_array_equal(g, np.asarray(w, np.float32))


def _record():
    """Four requests on two devices, prompts on both sides of a 2047
    window."""
    prompts = [300, 2100, 1500, 4000]
    sent = [Sent(Arrival(0.0, i, np.zeros(p, np.int32), 8), 0.0, f"t{i}")
            for i, p in enumerate(prompts)]
    steps = [Step(0.0, 1.0, 4, [(0, 0), (1, 0), (2, 3), (3, 5)], 0.5),
             Step(1.0, 2.0, 4, [(0, 1), (1, 1), (2, 4), (3, 6)], 0.5),
             Step(2.0, 3.0, 2, [(2, 5), (3, 7)], 0.5)]
    return Record(0.0, 4.0, sent, steps, trace_t0=0.0)


@pytest.mark.parametrize("name", CONFIGS)
def test_work_is_pinned(name):
    arch, m = _config(name)
    run = Run(rec=_record(), arch=arch, dims=m, deployment={}, chips=1,
              peak={}, setup_s=0.0, memory_peak=[],
              device_of={"t0": "d0", "t1": "d0", "t2": "d1", "t3": "d1"},
              modules={})
    steps = run.traced_steps()
    want = _pins("work")[name]
    assert list(run.decode_work(steps)) == want["decode"]
    assert list(run.prefill_work(steps)) == want["prefill"]

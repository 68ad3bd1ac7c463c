"""The check on a tiny hybrid configuration (Mamba-2 beside attention) on
the CPU, through the harness's own run: the sound program is correct and
compiles nothing in the window, the control (the reference at fp8) is
not correct under the same limits, and a timed path that mishandles the
recurrent state comes out as not correct."""
import json
import pathlib

import jax
import pytest

import check as check_mod
import run
import spec
from repro.runtime import serve

ROOT = pathlib.Path(__file__).resolve().parent / "fixtures" / "tiny-hybrid"
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
CELL = "tiny-hybrid-rag"
GRANITE = spec.ROOT / "bench" / "configs" / "granite-4.0-h-micro.json"


def _run(control=False):
    bench = spec.load_benchmark(ROOT)
    return bench, run.execute(bench, CELL, 2**31 + 99, 3.0, False,
                              jax.devices(), ROOT, control=control,
                              peak=PEAK)


def test_granite_file_gives_its_source_config_twice():
    """The published config.json sits at the top level of the file, key
    for key, and again as the ``model`` block the harness reads: the two
    copies are one configuration."""
    conf = json.loads(GRANITE.read_text())
    model = conf["model"]
    assert {k: conf[k] for k in model} == model
    m = spec.arch(conf).sizes("granite-4.0-h-micro", model)
    assert (m.vocab, m.n_layers) == (100352, 40)


def test_hybrid_run_is_correct_and_control_is_not():
    bench, r = _run(control=True)
    check = r["check"]
    assert r["correct"], check
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compiles_in_window"] == 0
    assert set(r["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    ctl = r["control_check"]
    assert not check_mod.passes(ctl), (ctl, r["readings"])
    assert ctl["mean_gap"]["value"] >= 3 * check["mean_gap"]["value"], \
        (check, r["readings"])


def test_pages_of_64_compile_nothing_in_window():
    """At pages of 64 the warm-up's 32-token request grows no page, so a
    program that only a decode step growing into a fresh page runs would
    first compile inside the window."""
    bench = spec.load_benchmark(ROOT)
    r = run.execute(bench, "tiny-hybrid-page64", 2**31 + 99, 3.0, False,
                    jax.devices(), ROOT, peak=PEAK)
    assert r["correct"], r["check"]
    assert r["compiles_in_window"] == 0


def _state_frozen(monkeypatch):
    """Every decode step hands back the recurrent state it was given."""
    real = serve.BatchingEngine.use_program

    def use_program(self, compiled):
        def step(params, caches, *rest):
            logits, new = compiled(params, caches, *rest)
            return logits, jax.tree_util.tree_map_with_path(
                lambda path, n, o: o if serve._is_state(path) else n,
                new, caches)
        real(self, step)
    monkeypatch.setattr(serve.BatchingEngine, "use_program", use_program)


def _prefill_ignores_length(monkeypatch):
    """The prefill's padding is a real token (0), so the state it leaves
    runs on past the prompt's real length."""
    real = serve.BatchingEngine._pad_ctx

    def pad_ctx(self, ctx):
        toks = real(self, ctx)
        return self._put(jax.numpy.maximum(toks, 0))
    monkeypatch.setattr(serve.BatchingEngine, "_pad_ctx", pad_ctx)


@pytest.mark.parametrize("fault", [_state_frozen, _prefill_ignores_length],
                         ids=["decode_state_frozen",
                              "prefill_ignores_length"])
def test_broken_state_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    _, r = _run()
    assert not r["correct"], r["check"]
    gap = r["check"]["mean_gap"]
    assert gap["value"] > gap["limit"]

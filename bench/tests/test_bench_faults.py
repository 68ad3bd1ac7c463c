"""The harness drives a run (without its look for a chip) with the timed
path broken underneath, and ``correct`` comes out false: a served token
altered where it is produced, and a decode step that returns its KV
state unchanged."""
import pathlib

import jax
import pytest

import run
import spec
from repro.runtime import serve

ROOT = pathlib.Path(__file__).resolve().parent / "fixtures" / "tiny"
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}


def _alter_token(monkeypatch):
    real = serve._argmax_tokens

    def altered(logits):
        ids = real(logits)
        return ids.at[0].set((ids[0] + 1) % logits.shape[-1])
    monkeypatch.setattr(serve, "_argmax_tokens", altered)


def _state_unchanged(monkeypatch):
    real = serve.BatchingEngine.use_program

    def use_program(self, compiled):
        def step(params, caches, *rest):
            logits, _ = compiled(params, caches, *rest)
            return logits, caches
        real(self, step)
    monkeypatch.setattr(serve.BatchingEngine, "use_program", use_program)


@pytest.mark.parametrize("fault", [_alter_token, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_broken_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    bench = spec.load_benchmark(ROOT)
    r = run.execute(bench, "tiny-chat", 2**31 + 99, 3.0, False,
                    jax.devices(), ROOT, peak=PEAK)
    assert not r["correct"], r["check"]
    gap = r["check"]["mean_gap"]
    assert gap["value"] > gap["limit"]

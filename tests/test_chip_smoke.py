"""chip_smoke.py on the CPU: the script itself must refuse to run without a
TPU, and its phases (the served path against the float32 reference) run
here at a reduced size, on one CPU device and on four virtual ones."""
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, cwd=cwd, env=env, timeout=500)


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_fails_without_tpu(tmp_path, alone):
    """No accelerator: non-zero exit and no result line — from the
    checkout, and from a directory holding only the script."""
    if alone:
        shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
        proc = _run(["chip_smoke.py"], cwd=tmp_path)
    else:
        proc = _run([os.path.join(ROOT, "chip_smoke.py")], cwd=tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


PHASES = """
import sys
sys.path.insert(0, {root!r})
import chip_smoke as cs
from repro.configs import get_config, reduced
from repro.configs.base import GeometryConfig
cfg = reduced(get_config("smollm-135m")).replace(
    geometry=GeometryConfig(kernel_force={force!r}))
"""


@pytest.mark.parametrize("paged", [False, True])
def test_serve_phase_matches_reference_on_cpu(paged):
    """The one-chip phase at a reduced size, with the Pallas decode kernel
    in interpret mode: every request served, audit passed, logits within
    the script's limits of the f32 reference."""
    code = PHASES.format(root=ROOT, force="interpret") + textwrap.dedent(f"""
        out = cs.phase_serve(cfg, paged={paged}, n_requests=5,
                             prompt_lens=(8, 40), max_new=4, max_len=64)
        print("ERRS", [round(e["rel_rms"], 4) for e in out["errors"]])
    """)
    proc = _run(["-c", code], cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "ERRS" in proc.stdout


def test_four_chip_phase_on_virtual_devices():
    """The four-replica phase on four virtual CPU devices: one engine per
    device, caches on distinct devices, a cross-device hand-off mid-decode,
    logits within limits on both devices the moved request used."""
    code = PHASES.format(root=ROOT, force="") + textwrap.dedent("""
        out = cs.phase_four_chips(cfg, max_len=64, prompt_lens=(10, 30),
                                  max_new=6, handoff_after=3)
        print("CHIPS", out["errors"][0]["chips"])
    """)
    proc = _run(["-c", code], cwd=ROOT, env_extra={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "CHIPS [0, 1]" in proc.stdout

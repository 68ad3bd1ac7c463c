"""The check on a tiny configuration on the CPU: the sound program is
correct, and the control (the reference at fp8, one step below the
served bfloat16) comes out as not correct under the same limits."""
import pathlib

import jax

import check as check_mod
import run
import spec

ROOT = pathlib.Path(__file__).resolve().parent / "fixtures" / "tiny"
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}


def test_sound_run_is_correct_and_control_is_not():
    bench = spec.load_benchmark(ROOT)
    r = run.execute(bench, "tiny-chat", 2**31 + 99, 3.0, False,
                    jax.devices(), ROOT, control=True, peak=PEAK)
    check = r["check"]
    assert r["correct"], check
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compiles_in_window"] == 0
    assert set(r["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert list(r)[-1] == "check"
    ctl = r["control_check"]
    assert not check_mod.passes(ctl), (ctl, r["readings"])
    assert ctl["mean_gap"]["value"] >= 3 * check["mean_gap"]["value"], \
        (check, r["readings"])


def test_windowed_run_is_correct():
    """Prompts past the window: the program's sliding-window attention on
    the paged path agrees with the reference's windowed mask."""
    bench = spec.load_benchmark(ROOT)
    r = run.execute(bench, "tiny-window", 2**31 + 7, 3.0, False,
                    jax.devices(), ROOT, peak=PEAK)
    assert r["correct"], r["check"]
    assert r["compiles_in_window"] == 0


def test_reference_window_masks_only_past_it():
    import jax.numpy as jnp
    import numpy as np

    import weights
    dense = spec.arch({"arch": "dense"})
    Dims = dense.Dims
    base = Dims("w", n_layers=1, d_model=64, n_heads=4, n_kv_heads=2,
                head_dim=16, d_ff=128, vocab=512, tied=True, norm_eps=1e-5,
                rope_theta=1e4, max_position=256)
    p = weights.layer(dense.layout(base), 5, *dense.layer_at(base, 0))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(40, 64)),
                    jnp.float32)
    full = np.asarray(dense.layer(base, 0, p, x, None))
    wide = np.asarray(dense.layer(
        Dims(**{**base.__dict__, "window": 40}), 0, p, x, None))
    narrow = np.asarray(dense.layer(
        Dims(**{**base.__dict__, "window": 24}), 0, p, x, None))
    np.testing.assert_array_equal(full, wide)
    np.testing.assert_array_equal(full[:24], narrow[:24])
    assert np.abs(full[24:] - narrow[24:]).max(axis=-1).min() > 1e-4

"""The float32 reference forward pass, and the served path against it on
seeded random weights at a small size: full-sequence forward, prefill then
decode through the dense cache (XLA attention and the Pallas decode kernel
in interpret mode), and through the paged pool."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import GeometryConfig
from repro.models import get_model
from repro.models.reference import reference_logits

S = 24


@pytest.fixture(scope="module")
def f32_model():
    cfg = reduced(get_config("smollm-135m")).replace(dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, S)
    return cfg, params, toks, np.asarray(reference_logits(cfg, params, toks))


def test_forward_matches_reference(f32_model):
    cfg, params, toks, ref = f32_model
    model = get_model(cfg)
    h, _ = model.forward(params, {"tokens": jnp.asarray(toks)[None]})
    got = np.asarray(model.logits(params, h)[0])
    np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("force", ["ref", "interpret"])
def test_prefill_then_decode_matches_reference(f32_model, force):
    """Prefill half the sequence, then decode the rest one token at a time
    through the dense cache; every decode step's logits match the
    reference's row at that position."""
    cfg, params, toks, ref = f32_model
    model = get_model(cfg.replace(geometry=GeometryConfig(
        kernel_force=force)))
    half = S // 2
    h, caches = model.prefill(params, {"tokens": jnp.asarray(toks[:half])[
        None]}, max_len=64)
    np.testing.assert_allclose(
        np.asarray(model.logits(params, h)[0]), ref[:half], atol=1e-4,
        rtol=1e-4)
    for p in range(half, S):
        logits, caches = model.decode(
            params, caches, jnp.asarray([[toks[p]]], jnp.int32),
            jnp.asarray([p], jnp.int32))
        np.testing.assert_allclose(np.asarray(logits[0, 0]), ref[p],
                                   atol=1e-4, rtol=1e-4)


def test_paged_decode_matches_reference(f32_model):
    """Decode every position through the paged pool (one page-strided
    block table), token by token from an empty pool."""
    cfg, params, toks, ref = f32_model
    model = get_model(cfg)
    ps, nb = 8, 4
    caches = model.make_paged_caches(nb + 1, ps)
    bt = jnp.arange(1, nb + 1, dtype=jnp.int32)[None]
    for p in range(S):
        logits, caches = model.decode_paged(
            params, caches, jnp.asarray([[toks[p]]], jnp.int32),
            jnp.asarray([p], jnp.int32), bt)
        np.testing.assert_allclose(np.asarray(logits[0, 0]), ref[p],
                                   atol=1e-4, rtol=1e-4)


def test_reference_refuses_what_it_does_not_cover():
    cfg = reduced(get_config("gemma2-9b"))
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="softcap"):
        reference_logits(cfg, params, np.arange(4))

"""Layer-level properties: RoPE, norms, SSD chunk-invariance, MLA absorbed
decode == expanded form, local attention == masked full attention."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.layers import (AttnOpts, MLAOpts, SSMOpts, apply_rope,
                          attn_forward, init_attention, init_mla, init_ssm,
                          mla_forward, rms_norm, softcap, ssm_forward)
from repro.configs.base import MLAConfig, SSMConfig
from repro.layers.ssm import ssd_scan


def test_rope_preserves_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 4, 64))
    pos = jnp.broadcast_to(jnp.arange(8)[None], (2, 8))
    y = apply_rope(x, pos, 10000.0)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(x), axis=-1),
                               np.linalg.norm(np.asarray(y), axis=-1),
                               rtol=1e-5)


def test_rope_relative_property():
    """<rope(q,i), rope(k,j)> depends only on i-j."""
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 1, 64))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 64))

    def score(i, j):
        qi = apply_rope(q, jnp.array([[i]]), 10000.0)
        kj = apply_rope(k, jnp.array([[j]]), 10000.0)
        return float(jnp.sum(qi * kj))

    assert abs(score(5, 3) - score(105, 103)) < 1e-3
    assert abs(score(7, 0) - score(1007, 1000)) < 1e-3


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_rms_norm_unit_rms(seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (4, 32)) * 7
    y = rms_norm(x, jnp.zeros(32))
    rms = jnp.sqrt(jnp.mean(jnp.square(y), axis=-1))
    np.testing.assert_allclose(np.asarray(rms), 1.0, atol=1e-3)


def test_softcap_bounds():
    x = jnp.linspace(-1000, 1000, 101)
    y = softcap(x, 30.0)
    assert float(jnp.abs(y).max()) <= 30.0
    np.testing.assert_allclose(np.asarray(softcap(x, 0.0)), np.asarray(x))


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_ssd_chunk_invariance(chunk):
    """SSD output must not depend on the chunk size."""
    B, S, H, P, G, N = 2, 64, 4, 8, 2, 16
    key = jax.random.PRNGKey(0)
    xs = jax.random.normal(key, (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1), (B, S, H)))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (H,)))
    Bm = jax.random.normal(jax.random.PRNGKey(3), (B, S, G, N)) * 0.3
    Cm = jax.random.normal(jax.random.PRNGKey(4), (B, S, G, N)) * 0.3
    D = jnp.ones((H,))
    y_ref, s_ref = ssd_scan(xs, dt, A, Bm, Cm, D, chunk=S)
    y, s = ssd_scan(xs, dt, A, Bm, Cm, D, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               atol=1e-4, rtol=1e-3)


def test_ssd_state_carry_equals_concat():
    """scan(x1) then scan(x2 | state) == scan([x1;x2])."""
    B, S, H, P, G, N = 1, 32, 2, 8, 1, 8
    key = jax.random.PRNGKey(5)
    xs = jax.random.normal(key, (B, 2 * S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(6),
                                           (B, 2 * S, H)))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(7), (H,)))
    Bm = jax.random.normal(jax.random.PRNGKey(8), (B, 2 * S, G, N)) * 0.3
    Cm = jax.random.normal(jax.random.PRNGKey(9), (B, 2 * S, G, N)) * 0.3
    D = jnp.zeros((H,))
    y_full, s_full = ssd_scan(xs, dt, A, Bm, Cm, D, chunk=16)
    y1, s1 = ssd_scan(xs[:, :S], dt[:, :S], A, Bm[:, :S], Cm[:, :S], D, 16)
    y2, s2 = ssd_scan(xs[:, S:], dt[:, S:], A, Bm[:, S:], Cm[:, S:], D, 16,
                      init_state=s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], axis=1)),
                               np.asarray(y_full), atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               atol=1e-4, rtol=1e-3)


def test_fresh_mamba2_layer_is_live():
    """init_ssm stores the gated RMSNorm's weight as w - 1 (zeros) and the
    layer applies 1 + w: a freshly initialised Mamba-2 layer gives a
    non-zero output at every position, in the prefill and in decode."""
    from repro.layers.ssm import init_ssm_cache, ssm_decode
    opts = SSMOpts(d_model=64, cfg=SSMConfig(d_state=16, head_dim=16,
                                             chunk=8))
    p = init_ssm(jax.random.PRNGKey(0), opts)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 64))
    y, _ = ssm_forward(p, x, opts)
    assert float(jnp.abs(y).max(axis=-1).min()) > 1e-3
    yd, _ = ssm_decode(p, x[:, :1], init_ssm_cache(2, opts, jnp.float32),
                       opts)
    assert float(jnp.abs(yd).max(axis=-1).min()) > 1e-3


def test_local_attention_equals_masked_full():
    """Sliding-window path (key slicing) == full attention with window mask."""
    opts_local = AttnOpts(n_heads=4, n_kv_heads=2, head_dim=16, window=8,
                          q_chunk=8)
    opts_ref = AttnOpts(n_heads=4, n_kv_heads=2, head_dim=16, window=8,
                        q_chunk=0)
    p = init_attention(jax.random.PRNGKey(0), 32, opts_local)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 32))
    pos = jnp.broadcast_to(jnp.arange(64)[None], (2, 64))
    y1, _ = attn_forward(p, x, pos, opts_local)
    y2, _ = attn_forward(p, x, pos, opts_ref)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=1e-5, rtol=1e-4)


def test_mla_absorbed_decode_equals_expanded():
    """The compressed-cache absorbed decode must equal the expanded form."""
    from repro.layers.mla import fill_mla_cache, init_mla_cache, mla_decode
    mcfg = MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                     qk_rope_head_dim=8, v_head_dim=16)
    opts = MLAOpts(n_heads=4, cfg=mcfg)
    p = init_mla(jax.random.PRNGKey(0), 64, opts)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 64))
    pos = jnp.broadcast_to(jnp.arange(9)[None], (2, 9))
    y_full, (c_kv, k_rope) = mla_forward(p, x, pos, opts)
    # prefill 8, decode the 9th
    cache = init_mla_cache(2, 16, opts, x.dtype)
    cache = fill_mla_cache(cache, c_kv[:, :8], k_rope[:, :8], pos[:, :8])
    y_dec, _ = mla_decode(p, x[:, 8:9], pos[:, 8:9], cache, opts)
    np.testing.assert_allclose(np.asarray(y_dec[:, 0]),
                               np.asarray(y_full[:, 8]),
                               atol=1e-4, rtol=1e-3)


def test_data_pipeline_determinism_and_host_sharding():
    from repro.data import DataConfig, DataPipeline
    g = DataPipeline(DataConfig(vocab_size=64, seq_len=16, batch_size=8))
    b1 = g.batch_at(3)
    b2 = g.batch_at(3)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # host-sharded pipelines tile the same global batch
    h0 = DataPipeline(DataConfig(vocab_size=64, seq_len=16, batch_size=8,
                                 n_hosts=2, host_index=0)).batch_at(3)
    h1 = DataPipeline(DataConfig(vocab_size=64, seq_len=16, batch_size=8,
                                 n_hosts=2, host_index=1)).batch_at(3)
    np.testing.assert_array_equal(
        np.concatenate([h0["tokens"], h1["tokens"]]), b1["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["labels"][:, :-1], b1["tokens"][:, 1:])

"""Ahead-of-time compiles for a described TPU v5e chip (no chip needed).

The TPU compiler is installed with JAX, and it compiles for a topology
that is described but not attached. These tests hand it the serving path's
kernels and decode steps at smollm-135m's full width, so a block shape or
VMEM budget the chip would refuse fails here. Nothing runs: results and
times are out of scope. The topology is described inside a fixture, so
only the test process that runs this file loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ATTN_LOCAL, GeometryConfig
from repro.kernels import ops
from repro.models import get_model
from repro.runtime.paged import default_pool_pages
from repro.runtime.serve import make_paged_serve_step, make_serve_step

B, L = 8, 2048          # the serving fleet's slots and cache length
PAGE = 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip's executables cannot be read back from the
    # persistent cache: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _placed(sharding, tree):
    return jax.tree.map(lambda x: _sds(sharding, x.shape, x.dtype), tree)


def _kernel_model(name="smollm-135m"):
    cfg = get_config(name)
    return get_model(cfg.replace(
        geometry=GeometryConfig(kernel_force="kernel")))


@pytest.mark.parametrize("arch,cache_len", [
    ("smollm-135m", 2048),
    ("qwen3-moe-30b-a3b", 4096),      # head_dim 128, 8 query heads per KV
])
@pytest.mark.parametrize("int8", [False, True])
def test_decode_kernel_compiles(one_chip, arch, cache_len, int8):
    cfg = get_config(arch)
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    kv = jnp.int8 if int8 else jnp.bfloat16
    args = [_sds(one_chip, (B, hq, d), jnp.bfloat16),
            _sds(one_chip, (B, hkv, cache_len, d), kv),
            _sds(one_chip, (B, hkv, cache_len, d), kv),
            _sds(one_chip, (B, cache_len), jnp.int32),
            _sds(one_chip, (B,), jnp.int32)]
    if int8:
        args += [_sds(one_chip, (B, hkv, cache_len), jnp.float32)] * 2

        def f(q, k, v, kpos, cur, ks, vs):
            return ops.decode_attention(q, k, v, kpos, cur, k_scale=ks,
                                        v_scale=vs, force="kernel")
    else:
        def f(q, k, v, kpos, cur):
            return ops.decode_attention(q, k, v, kpos, cur, force="kernel")
    text = jax.jit(f).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_full_width_dense_decode_step(one_chip):
    """smollm-135m's whole decode step, with the Pallas decode kernel on
    every layer, as the fleet binds it: 8 slots over 2048 positions."""
    model = _kernel_model()
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    caches = jax.eval_shape(lambda: model.make_caches(B, L))
    args = (_placed(one_chip, params), _placed(one_chip, caches),
            _sds(one_chip, (B, 1), jnp.int32),
            _sds(one_chip, (B,), jnp.int32))
    compiled = jax.jit(make_serve_step(model)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 2 * 2 ** 30


def test_full_width_paged_decode_step(one_chip):
    """smollm-135m's paged decode step over the default page pool."""
    model = _kernel_model()
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    pages = default_pool_pages(B, L // PAGE)
    pool = jax.eval_shape(lambda: model.make_paged_caches(pages, PAGE))
    args = (_placed(one_chip, params), _placed(one_chip, pool),
            _sds(one_chip, (B, 1), jnp.int32),
            _sds(one_chip, (B,), jnp.int32),
            _sds(one_chip, (B, L // PAGE), jnp.int32))
    compiled = jax.jit(make_paged_serve_step(model)).lower(*args).compile()
    assert compiled.memory_analysis().argument_size_in_bytes < 2 * 2 ** 30


def _materialised(hlo: str):
    """(dtype, dims) of every array an instruction outside a fusion body
    writes: what the compiled program holds in memory between ops."""
    fused = set(re.findall(r"calls=%([\w.\-]+)", hlo))
    out, skip = [], False
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        if head:
            skip = head.group(1) in fused
        elif not skip:
            m = re.match(r"\s+(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]", line)
            if m:
                out.append((m.group(1),
                            tuple(int(d) for d in m.group(2).split(",") if d)))
    return out


def test_phi3_paged_decode_step_sweeps_pages_in_bf16(one_chip):
    """phi3-mini's paged decode step as the benchmark deploys it (4 slots,
    4096 positions, 289 pages of 16, every layer windowed at 2047): the
    chunked page sweep compiles as a loop inside the layer scan, and the
    gathered K and V enter the dots in bf16, with no f32 copy of them."""
    cfg = get_config("phi3-mini-3.8b").replace(
        pattern=(ATTN_LOCAL,), window=2047, dtype="bfloat16",
        param_dtype="bfloat16")
    model = get_model(cfg)
    slots, max_len, pages = 4, 4096, 289
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    pool = jax.eval_shape(lambda: model.make_paged_caches(pages, PAGE))
    args = (_placed(one_chip, params), _placed(one_chip, pool),
            _sds(one_chip, (slots, 1), jnp.int32),
            _sds(one_chip, (slots,), jnp.int32),
            _sds(one_chip, (slots, max_len // PAGE), jnp.int32))
    hlo = jax.jit(make_paged_serve_step(model)).lower(*args).compile() \
        .as_text()
    assert hlo.count(" while(") == 2          # the layer scan, the sweep
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    page_of_batch = slots * PAGE * kv * hd
    f32_kv = [dims for dtype, dims in _materialised(hlo)
              if dtype == "f32" and dims[-2:] == (kv, hd)
              and np.prod(dims) >= page_of_batch]
    assert f32_kv == []


def test_granite_hybrid_paged_programs(one_chip):
    """granite-4.0-h-micro at its published widths and depth as the
    benchmark deploys it (16 slots, 1025 pages of 64, 6528 positions): the
    paged decode step, whose caches tree holds each Mamba-2 layer's
    per-slot state beside the attention layers' pages, and the prefill at
    its longest bucket, 6528 tokens padded with -1, which is no multiple
    of the attention's query chunk or of the SSD chunk."""
    from repro.runtime.serve import make_prefill_step
    cfg = get_config("granite-4.0-h-micro").replace(param_dtype="bfloat16")
    model = get_model(cfg)
    slots, max_len, page, pages = 16, 6528, 64, 1025
    params = _placed(one_chip, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0))))
    pool = jax.eval_shape(
        lambda: model.make_paged_caches(pages, page, slots))
    state = [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(
        pool)[0] if getattr(path[-1], "key", None) == "state"]
    assert sum(leaf.size for leaf in state) == 36 * slots * 64 * 64 * 128
    args = (params, _placed(one_chip, pool),
            _sds(one_chip, (slots, 1), jnp.int32),
            _sds(one_chip, (slots,), jnp.int32),
            _sds(one_chip, (slots, max_len // page), jnp.int32))
    decode = jax.jit(make_paged_serve_step(model)).lower(*args).compile()
    assert decode.memory_analysis().argument_size_in_bytes < 9 * 2 ** 30
    step = make_prefill_step(model, max_len, clamp_window=False)
    prefill = jax.jit(lambda p, t: step(p, {"tokens": t})).lower(
        params, _sds(one_chip, (1, max_len), jnp.int32)).compile()
    mem = prefill.memory_analysis()
    assert mem.temp_size_in_bytes < 4 * 2 ** 30
